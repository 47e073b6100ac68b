"""Window of kind ``fit_loop_dual``: ``fit_loop``'s window as it is (whole
fits back to back from host arrays, the same sample of fitted pipelines
scoring the held-out rows), with a second thing compared after it: the dual
coefficients that each sampled fit left in its model, against the plain
reference's.  A kernel model's predictor multiplies at the MXU's default,
so held-out scores cannot show the precision of the fit; its coefficients
can (as ``solve_loop`` compares the first block's weights).

``fit_loop`` is loaded through the harness, as every kind is: what
``tests/faulty_run.py`` plants in a driver's ``upload`` reaches this kind's
fits too."""

from __future__ import annotations

from benchmark import compare, harness

fit_loop = harness.load_module("drivers", "fit_loop")


class Driver(fit_loop.Driver):
    def answers(self) -> list:
        """Per sampled fit: held-out scores by the program's own apply, and
        the fitted dual coefficients."""
        fitted = dict([self.first, *self.picked, self.last])
        scores = super().answers()
        with self.span("readback"):
            return [
                (i, {"scores": s, **self.adapter.dual_coefficients(fitted[i], self.cfg)})
                for i, s in scores
            ]

    def reference(self, ref, precision="highest", answers=None):
        return [
            self.adapter.reference_answers(
                ref, self.cfg, self.cell, self.data, self.seed, precision, index
            )
            for index, _ in answers
        ]

    @staticmethod
    def compare(answers, want) -> dict:
        readings = fit_loop.Driver.compare(
            [(i, got["scores"]) for i, got in answers], [w["scores"] for w in want]
        )
        for name in ("alpha0", "alpha"):
            readings[name + "_relative_error"] = max(
                compare.relative_error(got[name], w[name]) for (_, got), w in zip(answers, want)
            )
        return readings
