"""Window of kind ``fit_loop``: whole ``build(...).fit().block_until_ready()``
back to back, each from HOST arrays of its own (another window of the
seeded rows under another Dataset name, so that no memo, artifact tier or
trace cache keyed on the data can answer for it).  The unit of work is one
fit.

After the window the fitted pipelines of a sample of the fits (the first,
the last, and draws from the seed) score the held-out rows through their
own public call; the plain reference fits the same rows of each sampled fit
and scores the same held-out rows.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare


def upload(x, labels, name: str):
    """HOST arrays to Datasets under a name of this fit's own: the upload is
    part of a fit."""
    from keystone_tpu.workflow import Dataset

    return Dataset(x, name=name), Dataset(labels, name=name + "-labels")


class Driver:
    unit = "fits"

    def __init__(self, cell, cfg, adapter, seed, devices, span):
        self.cell, self.cfg, self.adapter, self.seed = cell, cfg, adapter, seed
        self.span = span
        self.data = None
        # (fit index, fitted pipeline) of the window's first fit, of seeded picks
        # and of its last fit: a fitted model is up to 262 MB on the device
        self.first, self.picked, self.last = None, [], None
        self.rng = np.random.default_rng([seed % (2**31), 41])
        self.next_index = 0

    # ------------------------------------------------------------ set-up
    def setup(self, ref=None):
        t0 = time.perf_counter()
        self.data = self.adapter.make_data(self.cfg, self.cell, self.seed, ref)
        t1 = time.perf_counter()
        self._one_fit()  # compiles or loads every program of the fit
        t2 = time.perf_counter()
        self._one_fit()  # the warm fit
        warm = time.perf_counter() - t2
        return {"warm_fit_s": warm, "data_s": t1 - t0, "first_fit_s": t2 - t1}

    def _one_fit(self):
        x, labels = self.adapter.fit_inputs(self.data, self.cell, self.next_index)
        name = f"bench-{self.cell['name']}-s{self.seed}-fit{self.next_index}"
        with self.span("upload"):
            train = upload(x, labels, name)
        with self.span("dispatch"):
            fitted = self.adapter.build(self.cfg, self.cell, self.seed, *train, self.data).fit()
        with self.span("wait"):
            fitted.block_until_ready()
        self.next_index += 1
        return fitted

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        first = self.next_index
        extra = int(self.cell.get("checked_fits", 2)) - 2
        t0 = time.perf_counter()
        while True:
            index = self.next_index
            self.last = (index, self._one_fit())
            if self.first is None:
                self.first = self.last
            elif len(self.picked) < extra:
                self.picked.append(self.last)
            elif extra > 0 and self.rng.integers(index - first) < extra:  # reservoir
                self.picked[int(self.rng.integers(extra))] = self.last
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        units = self.next_index - first
        return {"units": units, "elapsed": elapsed, "failed": 0,
                "inputs_reused": max(0, units - self.cell["views"])}

    def metrics(self, counters: dict) -> dict:
        # the whole window's seconds over the whole fits completed in it
        return {"fit_s": counters["elapsed"] / counters["units"]}

    # ------------------------------------------------------------- check
    def answers(self) -> list:
        """Held-out scores of the sampled fits, by the program's own apply."""
        sample = {i: f for i, f in [self.first, *self.picked, self.last]}
        with self.span("readback"):
            return [(i, self.adapter.held_out_answers(f, self.data["held_x"]))
                    for i, f in sorted(sample.items())]

    def release(self):
        self.first, self.picked, self.last = None, [], None

    def reference(self, ref, precision="highest", answers=None):
        """Held-out scores of the plain reference, fitted on the rows of each
        sampled fit."""
        return [
            self.adapter.reference_scores(
                ref, self.cfg, self.cell, self.data, self.seed, precision, index
            )
            for index, _ in answers
        ]

    @staticmethod
    def as_answers(want, answers=None) -> list:
        """A reference's output in the place of the program's answers (the
        control)."""
        return [(index, w) for (index, _), w in zip(answers, want)]

    @staticmethod
    def compare(answers, want) -> dict:
        pairs = [(got, w) for (_, got), w in zip(answers, want)]
        worst = lambda number: max(number(g, w) for g, w in pairs)  # noqa: E731
        return {
            "score_rmse_over_std": worst(compare.rmse_over_std),
            "score_max_gap_over_std": worst(compare.max_gap_over_std),
            "score_median_row_over_std": worst(compare.median_row_rmse_over_centered_std),
            "score_worst_row_over_std": worst(compare.worst_row_rmse_over_centered_std),
        }

    def ops(self) -> dict:
        return self.adapter.ops(self.cfg, self.cell)
