#!/usr/bin/env python3
"""Drive the rest of a run (``harness.main --rehearse``: it skips the look
for a chip) with the timed path BROKEN underneath, to see ``correct`` come
out false:

    python3 benchmark/tests/faulty_run.py --fault <name> --workload <cell> [--chip]

  none            nothing broken (the sound run beside the faults)
  state_unchanged the solver's step returns its state unchanged (weights 0)
  half_batch      half of the rows left out, the fit taken over the rest
  no_exchange     the exchange between chips left out: every device solves
                  with the Gramian of its own rows
  answer_altered  one answer altered where it is produced (one row of scores
                  reversed)

``--chip`` runs the same at the cell's own size on the chip (no
``--rehearse``); ``chip_limits.py --faults`` reads the faults there beside
the sound runs, in one process, which is how their readings in PERF.md were
taken.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def alter_one(out):
    """One answer altered: a row of scores reversed."""
    out = out.copy()
    out[out.shape[0] // 2] = out[out.shape[0] // 2][::-1]
    return out


def plant(fault: str):
    """Break the timed path; returns the call that mends it again."""
    from benchmark import harness

    real_load = harness.load_module
    mend = [lambda: setattr(harness, "load_module", real_load)]

    def patched_adapter(adapter):
        if fault == "half_batch" and hasattr(adapter, "solve"):
            solve = adapter.solve
            adapter.solve = lambda est, data: solve(
                est, {**data, "x": data["x"][: data["x"].shape[0] // 2],
                      "y": data["y"][: data["y"].shape[0] // 2]})
        if fault == "answer_altered":
            for name in ("held_out_answers", "score"):
                if hasattr(adapter, name):
                    fn = getattr(adapter, name)
                    setattr(adapter, name, lambda *a, _fn=fn, **k: alter_one(_fn(*a, **k)))
            if hasattr(adapter, "answers"):
                answers = adapter.answers

                def altered_answers(model, data):
                    out = answers(model, data)
                    out["pred"] = out["pred"].copy()
                    out["pred"][7] = out["pred"][7][::-1]
                    return out

                adapter.answers = altered_answers
        return adapter

    def load(kind, name, here=harness.HERE):
        module = real_load(kind, name, here)
        if kind == "drivers" and fault == "half_batch" and hasattr(module, "upload"):
            upload = module.upload
            module.upload = lambda x, labels, name: upload(
                x[: len(x) // 2], labels[: len(x) // 2], name)
        return patched_adapter(module) if kind == "adapters" else module

    harness.load_module = load

    if fault == "state_unchanged":
        import jax.numpy as jnp

        from keystone_tpu.models import block_weighted_ls as bw

        real = bw._weighted_bcd_fit

        def unchanged(*a, **k):
            w, xm, ym = real(*a, **k)
            return jnp.zeros_like(w), xm, ym  # the carry as it went in

        bw._weighted_bcd_fit = unchanged
        mend.append(lambda: setattr(bw, "_weighted_bcd_fit", real))
    if fault == "no_exchange":
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from keystone_tpu.models import block_weighted_ls as bw
        from keystone_tpu.parallel.mesh import DATA_AXIS, current_mesh

        def local_only(a, b, out_spec=None, mesh=None):
            spec = P(DATA_AXIS, None)
            return jax.shard_map(
                lambda x, y: jnp.matmul(x.T, y, precision="highest"),
                mesh=mesh or current_mesh(), in_specs=(spec, spec), out_specs=P(),
                check_vma=False,
            )(a, b)

        real_matmul, real_gram = bw.sharded_matmul, bw.sharded_gram
        bw.sharded_matmul = local_only
        bw.sharded_gram = lambda a, mesh=None: local_only(a, a, mesh=mesh)
        mend.append(lambda: (setattr(bw, "sharded_matmul", real_matmul),
                             setattr(bw, "sharded_gram", real_gram)))
    return lambda: [m() for m in mend]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--fault", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", default="5")
    p.add_argument("--seconds", default="0.3")
    p.add_argument("--chip", action="store_true")
    args = p.parse_args(argv)
    from benchmark import harness

    if not args.chip:  # pin the CPU before a fault's imports start JAX
        harness.open_cell(args.workload, rehearse=True)
    if args.fault != "none":
        plant(args.fault)
    run = ["--workload", args.workload, "--seed", args.seed, "--seconds", args.seconds,
           "--trace", "0"]
    return harness.main(run if args.chip else run + ["--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
