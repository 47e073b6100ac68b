"""Window of kind ``score_loop``: bulk scoring back to back, host images in
and host scores out through the fitted pipeline's own call.  Each call gets
another view of the seeded images (another offset into one buffer: no call
sees the array object, or the rows in the order, of the call before).  The
unit of work is one image.  After the window a sample of rows, drawn from
the seed, of the first and the last call's scores is compared with the plain
reference's scores of the same images.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare


class Driver:
    unit = "images"

    def __init__(self, cell, cfg, adapter, seed, devices, span):
        self.cell, self.cfg, self.adapter, self.seed = cell, cfg, adapter, seed
        self.span = span
        self.data = None
        self.fitted = None
        self.calls = 0
        self.kept = []  # (view index, scores)

    def _view(self, index: int):
        lo = (index % self.cell["views"]) * self.cell["view_step"]
        return self.data["images"][lo: lo + self.cell["images"]]

    def _call(self):
        images = self._view(self.calls)
        with self.span("dispatch"):
            scores = self.adapter.score(self.fitted, images)
        self.calls += 1
        return scores

    def setup(self, ref=None):
        self.data = self.adapter.make_data(self.cfg, self.cell, self.seed, ref)
        with self.span("dispatch"):
            self.fitted = self.adapter.scorer(self.cfg, self.data)
        self._call()  # compiles or loads every program of a call
        t0 = time.perf_counter()
        self._call()
        return {"warm_fit_s": time.perf_counter() - t0}

    def window(self, seconds: float) -> dict:
        first_call = self.calls
        first = last = None
        t0 = time.perf_counter()
        while True:
            index = self.calls
            last = (index, self._call())
            first = first or last
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        calls = self.calls - first_call
        self.kept = [first, last] if calls > 1 else [last]
        return {"units": calls * self.cell["images"], "elapsed": elapsed, "failed": 0,
                "calls": calls}

    def metrics(self, counters: dict) -> dict:
        # all images scored over all the window's seconds
        return {"score_images_per_s": counters["units"] / counters["elapsed"]}

    def answers(self) -> list:
        rng = np.random.default_rng([self.seed % (2**31), 43])
        out = []
        for index, scores in self.kept:
            rows = np.sort(rng.choice(self.cell["images"], self.cell["checked_rows"],
                                      replace=False))
            out.append((index, rows, scores[rows]))
        return out

    def release(self):
        self.kept, self.fitted = [], None

    def reference(self, ref, precision="highest", answers=None):
        """Reference scores of the checked rows of each kept call."""
        want = []
        for index, rows, _ in answers:
            images = self._view(index)[rows]
            want.append(self.adapter.reference_scores(ref, self.cfg, self.data, images, precision))
        return want

    @staticmethod
    def as_answers(want, answers) -> list:
        return [(i, rows, w) for (i, rows, _), w in zip(answers, want)]

    @staticmethod
    def compare(answers, want) -> dict:
        return {
            "score_rmse_over_std": max(
                compare.rmse_over_centered_std(got, w) for (_, _, got), w in zip(answers, want)
            ),
            "score_worst_row_over_std": max(
                compare.worst_row_rmse_over_centered_std(got, w)
                for (_, _, got), w in zip(answers, want)
            ),
        }

    def ops(self) -> dict:
        return self.adapter.ops(self.cfg, self.cell)
