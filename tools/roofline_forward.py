"""Per-stage roofline of the headline forward (round-3 review item 2c).

Uses the jax.profiler DEVICE TRACE (events
carry per-HLO-op ``device_duration_ps``, ``model_flops``,
``bytes_accessed``, and source attribution), so per-op device times are
exact — no noisy wall-clock differencing.  For every compiled op of the
bench.py headline program (batch 128, SIFT bin 4 + smoothing, K=256 FV,
1000-class scoring) it reports:

- measured device microseconds per batch (median across traced runs)
- FLOPs and HBM bytes (XLA's per-op counters; the Pallas FV custom call
  is priced analytically — XLA cannot see inside it)
- the roofline bound  max(flops/peak_mxu, bytes/peak_hbm)  and the
  achieved fraction — ops at ≥~75% of bound are done; ops far below it
  with big byte counts name the fusion lever.

Also prints total device-busy time per iteration vs the program's wall
marginal time (the overlap/dispatch picture).

Run on the chip:  python tools/roofline_forward.py [--json] [--ms]
                  [--precision {f32,default,bf16_apply,sweep}]

``--ms`` profiles the multi-scale vl_phow config instead (bins
(4,6,8,10) + per-scale smoothing, batch 64 — the densest config the
reference ran, and bench.py's second first-class forward metric).

``--precision`` pins the matmul policy for the profiled program:
``f32`` forces full-precision featurize matmuls, ``default`` is the
shipped policy (auto: bf16 featurize on TPU), ``bf16_apply`` activates
the apply-side bf16 path (utils/precision.py).  ``sweep`` runs all
three back-to-back and prints one summary JSON line per mode with the
achieved TF/s and mfu_bf16_eff — the per-mode numbers the r6 tentpole
is judged on.  Every output carries ``precision``/``achieved_tflops``/
``mfu_bf16_eff`` fields either way.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import tempfile
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root, for bench
from bench import (  # noqa: E402
    BATCH,
    GMM_K,
    IMAGE_HW,
    MS_BATCH,
    MS_BIN_SIZES,
    MS_SMOOTHING,
    PCA_DIMS,
    SIFT_STEP,
    build_forward,
    flops_per_image,
)

BIN_SIZE = 4  # the headline single-scale bin (build_forward default)
_MS = "--ms" in sys.argv
_RUN_BATCH = MS_BATCH if _MS else BATCH
_BIN_SIZES = MS_BIN_SIZES if _MS else (BIN_SIZE,)
_SMOOTHING = MS_SMOOTHING if _MS else None  # None → build_forward default
if "--batch" in sys.argv:  # e.g. --batch 256: probe the large-batch decay
    _idx = sys.argv.index("--batch") + 1
    if _idx >= len(sys.argv) or not sys.argv[_idx].isdigit():
        sys.exit("usage: roofline_forward.py [--json] [--ms] [--batch N]")
    _RUN_BATCH = int(sys.argv[_idx])

#: --precision: the matmul policy the profiled program compiles under.
#: CLI names map onto utils/precision modes ("default" == the shipped
#: auto policy); "sweep" loops all three.
_PRECISION_CLI_TO_MODE = {"f32": "f32", "default": "auto", "bf16_apply": "bf16_apply"}
_PRECISION = "default"
if "--precision" in sys.argv:
    _idx = sys.argv.index("--precision") + 1
    if _idx >= len(sys.argv) or sys.argv[_idx] not in (
        *_PRECISION_CLI_TO_MODE,
        "sweep",
    ):
        sys.exit(
            "usage: roofline_forward.py [--precision {f32,default,bf16_apply,sweep}]"
        )
    _PRECISION = sys.argv[_idx]

TRACE_ITERS = 8
#: v5e bf16-grade MXU peak and HBM stream peak — per-op bounds use the
#: bf16 rate for matmul/conv ops (XLA runs default-precision f32 matmuls
#: as bf16-grade passes) and the f32 VPU-ish rate is not modeled: for
#: elementwise ops the bound is always bytes.
_PEAK_MXU = 1.97e14
_PEAK_HBM = 8.1e11


def _build_and_warm(precision_cli: str):
    """Set the matmul policy, build + warm the jitted forward."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.utils import precision as _prec
    from keystone_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    _prec.set_matmul(_PRECISION_CLI_TO_MODE[precision_cli])
    kw = {"bin_sizes": _BIN_SIZES}
    if _SMOOTHING is not None:
        kw["smoothing_magnif"] = _SMOOTHING
    fwd = jax.jit(build_forward(**kw))
    x = jnp.asarray(
        np.random.default_rng(1)
        .uniform(0, 1, (_RUN_BATCH, 128, 128, 3))
        .astype(np.float32)
    )
    for _ in range(3):
        np.asarray(fwd(x)[:1, :8])  # compile + warm
    return fwd, x


def _wall_marginal(fwd, x) -> float:
    """Marginal seconds/batch: slope between a 20- and a 60-iteration
    pipelined run (real device→host read as the sync)."""

    def run(k):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = fwd(x)
        np.asarray(out[:1, :8])
        return time.perf_counter() - t0

    t20, t60 = run(20), run(60)
    return (t60 - t20) / 40.0


def measure_wall(precision_cli: str) -> float:
    """Wall-marginal seconds/batch under one policy (no profiler trace —
    the --precision sweep wants the per-mode TF/s, not per-op tables)."""
    fwd, x = _build_and_warm(precision_cli)
    return _wall_marginal(fwd, x)


def run_and_trace(logdir: str, precision_cli: str = "default"):
    import jax

    fwd, x = _build_and_warm(precision_cli)
    wall_marginal = _wall_marginal(fwd, x)
    with jax.profiler.trace(logdir):
        out = None
        for _ in range(TRACE_ITERS):
            out = fwd(x)
        np.asarray(out[:1, :8])
    return wall_marginal


def parse_trace(logdir: str):
    paths = sorted(
        glob.glob(os.path.join(logdir, "plugins/profile/*/*.trace.json.gz"))
    )
    if not paths:
        raise RuntimeError(f"no trace.json.gz under {logdir}")
    with gzip.open(paths[-1]) as f:
        t = json.load(f)
    evs = t.get("traceEvents", t) if isinstance(t, dict) else t
    # device-op events: ph=X with an hlo_category arg (host pid events
    # have none); tid varies (queue), pid is the device process
    ops = [
        e
        for e in evs
        if e.get("ph") == "X"
        and isinstance(e.get("args"), dict)
        and "hlo_category" in e["args"]
    ]
    return ops


def aggregate(ops):
    """op label → dict(us_per_run, flops, bytes, category, n)."""
    by_name = defaultdict(list)
    for e in ops:
        a = e["args"]
        by_name[e["name"]].append(
            (
                int(a.get("device_duration_ps", 0)) / 1e6,  # ps → µs
                float(a.get("model_flops", 0) or 0),
                float(a.get("raw_bytes_accessed", a.get("bytes_accessed", 0)) or 0),
                a.get("hlo_category", "?"),
                a.get("tf_op", "") or a.get("source", ""),
            )
        )
    rows = {}
    for name, vals in by_name.items():
        d = [v[0] for v in vals]
        # each run emits the op once; occurrences = ceil(n/TRACE_ITERS)
        per_run = float(np.sum(d)) / TRACE_ITERS
        rows[name] = {
            "us_per_run": per_run,
            "flops": vals[0][1] * len(vals) / TRACE_ITERS,
            "bytes": vals[0][2] * len(vals) / TRACE_ITERS,
            "category": vals[0][3],
            "attr": vals[0][4][:70],
            "n": len(vals),
        }
    return rows


def main():
    if _PRECISION == "sweep":
        # per-mode TF/s + mfu_bf16_eff: one JSON line per policy, same
        # program, traced-free wall marginal (bench.py's slope idea)
        for cli in ("f32", "default", "bf16_apply"):
            wall = measure_wall(cli)
            ips = _RUN_BATCH / wall
            tf = ips * flops_per_image(_BIN_SIZES) / 1e12
            print(
                json.dumps(
                    {
                        "precision": cli,
                        "batch": _RUN_BATCH,
                        "wall_marginal_us": round(wall * 1e6, 1),
                        "images_per_sec": round(ips, 1),
                        "achieved_tflops": round(tf, 2),
                        "mfu_bf16_eff": round(tf * 1e12 / _PEAK_MXU, 3),
                    }
                )
            )
        return

    logdir = tempfile.mkdtemp(prefix="ks-roofline-")
    wall = run_and_trace(logdir, _PRECISION)
    rows = aggregate(parse_trace(logdir))

    # price the Pallas FV custom call analytically (model_flops = 0 for
    # custom calls XLA can't see inside).  Match by NAME — a category
    # match would hand the FV count to any other zero-flop custom call
    # in a future trace.  T derives from the bench geometry, not a
    # hardcoded 784.
    from keystone_tpu.ops.sift import sift_output_count

    t_desc = sift_output_count(IMAGE_HW, IMAGE_HW, SIFT_STEP, _BIN_SIZES)
    for name, r in rows.items():
        if "fisher" in name.lower() and r["flops"] == 0:
            r["flops"] = 4 * 2 * t_desc * PCA_DIMS * GMM_K * _RUN_BATCH
            r["analytic_flops"] = True

    total_dev = sum(r["us_per_run"] for r in rows.values())
    out_rows = []
    for name, r in sorted(rows.items(), key=lambda kv: -kv[1]["us_per_run"]):
        bound_us = max(r["flops"] / _PEAK_MXU, r["bytes"] / _PEAK_HBM) * 1e6 / 1.0
        # flops/bytes are per-run totals; us_per_run the same
        pct = bound_us / r["us_per_run"] if r["us_per_run"] > 0.5 else None
        binding = (
            "flops" if r["flops"] / _PEAK_MXU >= r["bytes"] / _PEAK_HBM else "bytes"
        )
        out_rows.append(
            {
                "op": name,
                "category": r["category"],
                "us_per_batch": round(r["us_per_run"], 1),
                "share_of_device": round(r["us_per_run"] / total_dev, 3),
                "gflops": round(r["flops"] / 1e9, 2),
                "mbytes": round(r["bytes"] / 1e6, 1),
                "bound_us": round(bound_us, 1),
                "binding": binding,
                "pct_of_bound": pct and round(pct, 2),
                "attr": r["attr"],
            }
        )
    achieved_tf = _RUN_BATCH / wall * flops_per_image(_BIN_SIZES) / 1e12
    result = {
        "batch": _RUN_BATCH,
        "precision": _PRECISION,
        "wall_marginal_us": round(wall * 1e6, 1),
        "device_busy_us": round(total_dev, 1),
        "overlap_or_gap_us": round(wall * 1e6 - total_dev, 1),
        "images_per_sec": round(_RUN_BATCH / wall, 1),
        "achieved_tflops": round(achieved_tf, 2),
        "mfu_bf16_eff": round(achieved_tf * 1e12 / _PEAK_MXU, 3),
        "analytic_flops_per_image": flops_per_image(_BIN_SIZES),
        "ops": out_rows,
    }
    if "--json" in sys.argv:
        print(json.dumps(result))
        return
    print(
        f"batch={_RUN_BATCH}  wall={wall*1e6:.0f}us/batch  device-busy="
        f"{total_dev:.0f}us  ({_RUN_BATCH/wall:,.0f} images/s)"
    )
    print(
        f"{'op':<28}{'us':>7}{'%dev':>6}{'GF':>7}{'MB':>8}{'bound':>7}"
        f"{'bind':>7}{'x-off':>7}  attr"
    )
    for r in out_rows:
        if r["us_per_batch"] < 0.5:
            continue
        pct = f"{r['pct_of_bound']:.2f}" if r["pct_of_bound"] else "—"
        print(
            f"{r['op'][:27]:<28}{r['us_per_batch']:>7.1f}"
            f"{100*r['share_of_device']:>5.0f}%{r['gflops']:>7.2f}"
            f"{r['mbytes']:>8.1f}{r['bound_us']:>7.1f}{r['binding']:>7}"
            f"{pct:>7}  {r['attr'][:40]}"
        )


if __name__ == "__main__":
    main()
