"""Window of kind ``fit_loop_parts``: ``fit_loop``'s window as it is (whole
fits back to back from host arrays, the same sample of fitted pipelines
scoring the held-out rows), with three more things compared after it, all
taken from what each sampled fit left in its model: the fitted featurizer's
own output on held-out rows and the first block's fitted weights, against
the plain reference's; and those weights against a plain solve of the
fit's OWN first block of training features (``x0``: what its featurizer
and scaler handed its solver), which holds the solver to its precision
whatever the featurizers' streams round.  Held-out scores alone pass a
featurizer and a solver through each other's noise; the parts tell which
one moved (as ``fit_loop_dual`` compares dual coefficients and
``solve_loop`` the first block's weights).

``fit_loop`` is loaded through the harness, as every kind is: what
``tests/faulty_run.py`` plants in a driver's ``upload`` reaches this kind's
fits too."""

from __future__ import annotations

from benchmark import compare, harness

fit_loop = harness.load_module("drivers", "fit_loop")


class Driver(fit_loop.Driver):
    def answers(self) -> list:
        """Per sampled fit: held-out scores by the program's own apply, the
        fitted featurizer's features and the first block's weights."""
        fitted = dict([self.first, *self.picked, self.last])
        scores = super().answers()
        with self.span("readback"):
            return [
                (i, {"scores": s, **self.adapter.fitted_parts(
                    fitted[i], self.data["held_x"],
                    self.adapter.fit_inputs(self.data, self.cell, i)[0], self.cell, self.cfg)})
                for i, s in scores
            ]

    def reference(self, ref, precision="highest", answers=None):
        return [
            self.adapter.reference_answers(
                ref, self.cfg, self.cell, self.data, self.seed, precision, index, got["x0"]
            )
            for index, got in answers
        ]

    @staticmethod
    def as_answers(want, answers=None) -> list:
        """A reference's output in the place of the program's answers (the
        control): its weights from the program's ``x0`` stand where the
        program's own weights are held to that solve."""
        return [(index, {**w, "w0_of_x0": w["w0_given_x0"], "x0": got["x0"]})
                for (index, got), w in zip(answers, want)]

    @staticmethod
    def compare(answers, want) -> dict:
        readings = fit_loop.Driver.compare(
            [(i, got["scores"]) for i, got in answers], [w["scores"] for w in want]
        )
        pairs = [(got, w) for (_, got), w in zip(answers, want)]
        readings["features_rmse_over_std"] = max(
            compare.rmse_over_std(g["features"], w["features"]) for g, w in pairs)
        readings["features_median_row_over_std"] = max(
            compare.median_row_rmse_over_centered_std(g["features"], w["features"])
            for g, w in pairs)
        readings["w0_relative_error"] = max(
            compare.relative_error(g["w0"], w["w0"]) for g, w in pairs)
        readings["w0_solver_relative_error"] = max(
            compare.relative_error(g.get("w0_of_x0", g["w0"]), w["w0_given_x0"])
            for g, w in pairs)
        return readings
