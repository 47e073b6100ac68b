"""Window of kind ``solve_loop``: the solver's entry back to back on rows
that stay resident in their shards (made on the devices in set-up).  The
unit of work is one solve.  After the window the first and the last
solve's fitted mappers predict held-out rows through the program's own
apply; the plain reference solves once on the same shards.
"""

from __future__ import annotations

import time

import jax

from benchmark import compare


class Driver:
    unit = "solves"

    def __init__(self, cell, cfg, adapter, seed, devices, span):
        self.cell, self.cfg, self.adapter, self.seed = cell, cfg, adapter, seed
        self.span = span
        self.mesh = adapter.mesh_for(devices)
        self.data = None
        self.est = None
        self.models = []

    def _solve(self):
        from keystone_tpu.parallel.mesh import use_mesh

        with use_mesh(self.mesh):
            with self.span("dispatch"):
                model = self.adapter.solve(self.est, self.data)
            with self.span("wait"):
                jax.block_until_ready(model.weights)
        return model

    def setup(self, ref=None):
        with self.span("upload"):
            self.data = self.adapter.make_data(self.cfg, self.cell, self.seed, self.mesh)
            jax.block_until_ready(self.data)
        self.est = self.adapter.estimator(self.cfg, self.cell)
        self._solve()  # compiles or loads the one program
        t0 = time.perf_counter()
        self._solve()
        return {"warm_fit_s": time.perf_counter() - t0}

    def window(self, seconds: float) -> dict:
        units, first, last = 0, None, None
        t0 = time.perf_counter()
        while True:
            last = self._solve()
            first = first or last
            units += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.models = [first, last] if units > 1 else [last]
        return {"units": units, "elapsed": elapsed, "failed": 0}

    def metrics(self, counters: dict) -> dict:
        return {"fit_s": counters["elapsed"] / counters["units"]}

    def answers(self) -> list:
        from keystone_tpu.parallel.mesh import use_mesh

        with use_mesh(self.mesh), self.span("readback"):
            return [self.adapter.answers(m, self.data) for m in self.models]

    def release(self):
        self.models = []

    def reference(self, ref, precision="highest", answers=None):
        return self.adapter.reference_answers(ref, self.cfg, self.cell, self.data, precision)

    @staticmethod
    def as_answers(want, answers=None) -> list:
        """A reference's output in the place of the program's answers (the
        control)."""
        return [want]

    @staticmethod
    def compare(answers, want) -> dict:
        return {
            "pred_rmse_over_std": max(
                compare.rmse_over_std(a["pred"], want["pred"]) for a in answers
            ),
            "pred_max_gap_over_std": max(
                compare.max_gap_over_std(a["pred"], want["pred"]) for a in answers
            ),
            "w0_relative_error": max(
                compare.relative_error(a["w0"], want["w0"]) for a in answers
            ),
        }

    def ops(self) -> dict:
        return self.adapter.ops(self.cfg, self.cell)
