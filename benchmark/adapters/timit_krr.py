"""What ties the ``timit-kernel-krr`` configuration to the program: the
data it is fed, the program's own entry
(``KernelRidgeTimitPipeline.build_scorer``), how what it fitted scores
held-out rows, the dual coefficients it fitted, and the operations of one
fit.  Only this file and the driver import ``keystone_tpu``."""

from __future__ import annotations

from benchmark import ops_count_krr
from benchmark.adapters import timit

#: the same seeded frames, in the same windows, as ``timit-rf.fit``: the
#: paper's own comparison is random features against the exact kernel
fit_inputs = timit.fit_inputs


def entry():
    from keystone_tpu.pipelines.kernel_ridge_timit import KernelRidgeTimitPipeline

    return KernelRidgeTimitPipeline


def make_data(cfg: dict, cell: dict, seed: int, ref=None) -> dict:
    entry()  # a program without the entry fails here, before any data is made
    return timit.make_data(cfg, cell, seed, ref)


def epochs(cfg: dict, cell: dict) -> int:
    return cell.get("num_epochs", cfg["num_epochs"])


def config(cfg: dict, cell: dict):
    return entry().Config(
        gamma=cfg["gamma"], lam=cfg["lam"], block_size=cfg["block_size"],
        num_epochs=epochs(cfg, cell), num_classes=cfg["num_classes"],
    )


def build(cfg: dict, cell: dict, seed: int, train_x, train_labels, data=None):
    """The unfitted pipeline of one fit: the entry's own ``build_scorer``."""
    return entry().build_scorer(config(cfg, cell), train_x, train_labels)


def held_out_answers(fitted, held_x):
    """Raw class scores of held-out rows through the fitted pipeline's own
    public call."""
    from keystone_tpu.workflow import Dataset

    return fitted(Dataset(held_x)).get().numpy()


def dual_coefficients(fitted, cfg: dict) -> dict:
    """The fitted dual coefficients, as host arrays: the first block's
    (final after its own step of a one-epoch sweep: no later step touches
    them, and no product but the distance gemm has entered them) and all of
    them (every block after the first has F, and so both other products, in
    its right-hand side)."""
    import numpy as np

    model = entry().fitted_model(fitted)
    alpha = np.asarray(model.alpha)[: model.train_n]
    return {"alpha0": alpha[: cfg["block_size"]], "alpha": alpha}


def reference_answers(ref, cfg: dict, cell: dict, data: dict, seed: int, precision,
                      index: int) -> dict:
    x, labels = fit_inputs(data, cell, index)
    out = ref.fit_and_score(cfg, x, labels, data["held_x"], epochs=epochs(cfg, cell),
                            precision=precision)
    return {"scores": out["scores"], "alpha": out["alpha"],
            "alpha0": out["alpha"][: cfg["block_size"]]}


def ops(cfg: dict, cell: dict) -> dict:
    args = (cell["n"], cfg["input_dim"], cfg["num_classes"], cfg["block_size"],
            epochs(cfg, cell))
    return {
        "solver_flops": ops_count_krr.krr_flops(*args),
        "solver_bytes": ops_count_krr.krr_bytes(*args),
        "featurize_flops": 0.0,
        "featurize_bytes": 0.0,
    }
