"""What ties the ``bwls-fv-sharded`` configuration to the program: rows made
on the devices straight into their shards, the program's entry
(``BlockWeightedLeastSquaresEstimator.fit_arrays`` under the mesh), how the
fitted weights' predictions are read, and the operations of one solve.
Only this file and the driver import ``keystone_tpu``."""

from __future__ import annotations

from benchmark import datagen, ops_count


def mesh_for(devices):
    from keystone_tpu.parallel.mesh import default_mesh

    return default_mesh(list(devices))


def make_data(cfg: dict, cell: dict, seed: int, mesh) -> dict:
    from keystone_tpu.parallel.mesh import data_sharding

    rows = data_sharding(mesh, 2)
    d, k = cfg["num_features"], cfg["num_classes"]
    x, y = datagen.fv_like_rows(cell["n"], d, k, seed, shardings=(rows, rows))
    held_x, _ = datagen.fv_like_rows(
        cell["held_out"], d, k, seed, shardings=(rows, rows), stream=1
    )
    return {"x": x, "y": y, "held_x": held_x}


def estimator(cfg: dict, cell: dict):
    from keystone_tpu.models.block_weighted_ls import BlockWeightedLeastSquaresEstimator

    return BlockWeightedLeastSquaresEstimator(
        block_size=cfg["block_size"], num_iter=cell["num_epochs"], lam=cfg["lam"],
        mixture_weight=cfg["mixture_weight"],
    )


def solve(est, data: dict):
    return est.fit_arrays(data["x"], data["y"])


def answers(model, data: dict) -> dict:
    """What one solve produced, as host arrays: the first block's weights
    (final after one sweep: no later step touches them) and the fitted
    mapper's own predictions on held-out rows."""
    import numpy as np

    return {
        "w0": np.asarray(model.weights[0]),
        "pred": np.asarray(model.apply_batch(data["held_x"])),
    }


def reference_answers(ref, cfg: dict, cell: dict, data: dict, precision: str) -> dict:
    w0, pred = ref.fit_and_predict(
        cfg, data["x"], data["y"], data["held_x"], epochs=cell["num_epochs"],
        precision=precision,
    )
    return {"w0": w0, "pred": pred}


def ops(cfg: dict, cell: dict) -> dict:
    args = (cell["n"], cfg["num_features"], cfg["num_classes"], cfg["block_size"],
            cell["num_epochs"])
    return {
        "solver_flops": ops_count.solver_flops(*args),
        "solver_bytes": ops_count.solver_bytes(*args),
        "featurize_flops": 0.0,
        "featurize_bytes": 0.0,
    }
