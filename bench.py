"""Headline benchmark — ImageNet-scale FV pipeline throughput + MFU.

Measures the north-star path (rounds 1–5, not re-measured): dense SIFT → PCA(64) → GMM
Fisher vector (K=256, T=784 descriptors/image — the regime the reference's
ImageNetSiftLcsFV pipeline ran, SURVEY.md §2.3) → power/L2 normalization →
1000-class block-linear scoring, end to end on device, steady state, on
one TPU chip.  This config engages the Pallas FV kernel (γ = T·K = 200k
elements ≫ the 32k crossover).

Prints ONE JSON line with:
  value / unit     — sustained images/sec/chip (marginal per-batch time)
  tflops           — analytic FLOPs/image × ips (FLOP accounting below)
  mfu_f32          — tflops / 49 Tf/s (TPU v5 lite f32 peak; XLA runs
                     default-precision f32 matmuls as bf16-grade MXU
                     passes, so >1.0 is possible for matmul-dense configs)
  vs_baseline      — speedup over the SAME JAX program on one host CPU
                     (stand-in: the reference publishes no numbers and its
                     mount is empty)

Methodology: throughput is the *marginal* per-batch time of a pipelined
dispatch stream: t(n) = fixed_sync + n·per_iter, fitted by Theil–Sen
(median of pairwise slopes) over interleaved runs of several lengths.
The run-end synchronization is ``block_until_ready``, which waits for the
device on today's runtime (chip_smoke.py's sync probe re-tests it on every
run: 0.3047 s blocked vs 0.3049 s synced by a device→host read, my chip
run, PR 21).

Since r4 the one JSON line also carries the two first-class companion
metrics the reference's published story is about (round-3 review item 1):
``fit``        — end-to-end north-star FIT (two-branch featurize →
                 weighted BCD; synthetic ImageNet config n=2048@128px,
                 K=64, 64 classes): fit_seconds / fit_images_per_sec
                 with bands, plus the solver-phase TFLOP/s measured
                 standalone at the post-featurize shape.
``multiscale`` — forward throughput at the densest config the
                 reference ran (vl_phow bins (4,6,8,10) + per-scale
                 smoothing, T=2520 descriptors/image).

Since r5 it also carries the at-scale artifacts (round-4 review item 5):
``solver_at_scale`` — weighted-BCD at n=65536×d=16384×k=64 (solver-grade
true-f32 TF/s with band); ``fit_at_scale`` — the full two-branch fit at
n=8192 (the shape-stable chunked-apply regime).

Since r6 the line also carries ``precision_sweep`` — the headline
forward re-measured under each matmul policy (f32 / auto / bf16_apply,
one pinned-env subprocess leg per mode; BENCH_PRECISION_LEGS legs each,
0 disables) so the bf16 apply-path win (and any regression) lands in
the round's bench artifact as first-class ips / mfu_bf16_eff numbers next to the
headline.

Usage: python bench.py           # TPU (or default backend) + cached CPU leg
       python bench.py --cpu     # CPU-baseline leg only
       python bench.py --sweep   # batch sweep (prints one line per batch)
       python bench.py --leg-fit # one fit+solver leg (one JSON line)
       python bench.py --leg-ms  # one multi-scale forward leg
       python bench.py --leg-solver-scale   # one at-scale solver leg
       python bench.py --leg-fit-scale      # one n=8192 fit leg
       python bench.py --leg-kernel         # kernel tier in-core-vs-OC A/B
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

BATCH = 128  # measured optimum on v5 lite (rounds 1–5, not re-measured batch sweep)
IMAGE_HW = 128
SIFT_STEP = 4  # -> 28x28 = 784 descriptors/image
GMM_K = 256
PCA_DIMS = 64
NUM_CLASSES = 1000
WARMUP = 3
RUN_LENGTHS = (10, 25, 40, 60, 80)
REPS = 3

# --- multi-scale leg: the densest config the reference's ImageNet
# pipeline ran (vl_phow bins + per-scale smoothing; SURVEY §2.3,
# rounds 1–5, not re-measured) — T=2520 descriptors/image
MS_BATCH = 64
MS_BIN_SIZES = (4, 6, 8, 10)
MS_SMOOTHING = 6.0

# --- fit leg: the end-to-end north-star FIT (two-branch featurize →
# weighted BCD) on the synthetic ImageNet config rounds 1–5, not re-measured has tracked
# since r1 (n=2048 at 128px, K=64, 64 classes)
FIT_N = 2048
FIT_CLASSES = 64
FIT_GMM_K = 64
FIT_EPOCHS = 2
FIT_SOLVER_BLOCK = 4096

# --- at-scale legs (round-4 review item 5: the numbers that prove the
# framework trains at reference scale must be per-round artifacts, not
# prose).  Solver: the n=65536×d=16384 weighted-BCD shape (19-23 TF/s
# true-f32 in rounds 1–5, not re-measured); data is generated ON DEVICE
# (no 4.3 GB host copy is made or moved).  Fit: the full two-branch fit
# at n=8192 (4× the tracked
# config — exercises the chunked-apply path whose programs stop scaling
# with n).
ATSCALE_N, ATSCALE_D, ATSCALE_K = 65536, 16384, 64
ATSCALE_EPOCHS = 1
FIT_SCALE_N = 8192
SCALE_LEGS = int(os.environ.get("BENCH_SCALE_LEGS", "2"))

# --- kernel leg (ISSUE 13): the kernel solver tier — blockwise
# Gauss–Seidel KRR, in-core vs the out-of-core streamed gram-block
# sweep on the SAME problem (the solver family arXiv:1602.05310 adds
# over upstream, and a genuinely different compute shape from the
# feature-block BCD: nb² gram gemms per epoch instead of nb Gramians).
# The A/B tracks: kernel-sweep TFLOP/s both ways, the OC feed's
# device_busy_fraction + transfer_seconds (is the stream keeping the
# device busy?), prediction r² between the two fits (must stay ≥
# 0.999), and how many times the on-disk row store exceeds the OC
# sweep's device-resident working set (2 staged row blocks + the
# (α, F, Y) carries) — the honest out-of-core claim.  The default
# geometry keeps that ratio > 4× while the whole leg stays
# minutes-scale on CPU; raise BENCH_KERNEL_N toward the million-row
# regime on real hardware.
KERNEL_LEGS = int(os.environ.get("BENCH_KERNEL_LEGS", "1"))
KERNEL_N = int(os.environ.get("BENCH_KERNEL_N", "8192"))
KERNEL_D = int(os.environ.get("BENCH_KERNEL_D", "256"))
KERNEL_K = int(os.environ.get("BENCH_KERNEL_K", "8"))
KERNEL_BLOCK = int(os.environ.get("BENCH_KERNEL_BLOCK", "512"))
KERNEL_EPOCHS = int(os.environ.get("BENCH_KERNEL_EPOCHS", "2"))
KERNEL_GAMMA = float(os.environ.get("BENCH_KERNEL_GAMMA", "0.002"))

# --- precision-mode sweep (ISSUE 2): the headline forward under each
# matmul policy, one subprocess leg per (mode, leg) with KEYSTONE_MATMUL
# pinned in the child env — so policy resolution, trace caches, and the
# persistent compile cache are per-mode clean.  "f32" = full-precision
# featurize policy, "auto" = the default (bf16 featurize on TPU),
# "bf16_apply" = the opt-in apply path (utils/precision.py) whose
# mfu_bf16_eff delta vs "auto" is the r6 headline claim.  On CPU hosts
# all three resolve inert and the sweep just measures noise — it still
# runs so the artifact shape is identical everywhere.
PRECISION_MODES = ("f32", "auto", "bf16_apply")
PRECISION_LEGS = int(os.environ.get("BENCH_PRECISION_LEGS", "1"))

# --- serve leg (ISSUE 5): the online-serving subsystem under overload
# (tools/serve_bench.py open-loop generator, offered QPS > capacity via
# a serve.batch delay plan emulating a heavier model).  The numbers the
# round artifact tracks: achieved QPS, p50/p99 latency, mean batch
# occupancy (>1 = micro-batching is amortizing program launches), shed
# rate (excess load counted, not queued unboundedly), deadline misses
# (0 = every completed request beat its deadline).
SERVE_LEGS = int(os.environ.get("BENCH_SERVE_LEGS", "1"))
SERVE_QPS = 1500.0
SERVE_DURATION_S = 2.0
SERVE_MAX_BATCH = 16
SERVE_QUEUE_BOUND = 64
SERVE_DEADLINE_MS = 250.0
SERVE_BATCH_DELAY_MS = 10.0

# --- recorder-overhead pair (ISSUE 9): the flight-recorder tax, pinned.
# An in-process A/B — the identical steady-state workload against a
# recorder-on and a recorder-off service, order-alternating rounds with
# a discarded warmup — because the overload leg above sits ON the
# collapse cliff, where achieved QPS swings tens of percent run-to-run
# and no 5%-budget claim is measurable.  The artifact records per-mode
# medians and the on/off ratios (budget: within 5% of 1.0).
SERVE_OVERHEAD_QPS = float(os.environ.get("BENCH_SERVE_OVERHEAD_QPS", "300"))
SERVE_OVERHEAD_ROUNDS = int(os.environ.get("BENCH_SERVE_OVERHEAD_ROUNDS", "4"))

# --- fleet leg (ISSUE 8): the replica fleet + live blue/green hot-swap
# under the same open-loop generator.  Offered load sits ABOVE one
# replica's capacity (max_batch rows per 40 ms-delayed flush ≈ 0.7k QPS)
# and below the fleet's, so achieved QPS is the scaling claim: the
# N-replica leg must sustain more than the 1-replica leg run with the
# IDENTICAL config (recorded side by side).  The emulated model is
# deliberately HEAVY (40 ms per flush): flush time must dominate the
# per-request host work (submit path, future resolution — all GIL-bound
# Python) or a 2-core CI host measures the GIL, not the fleet.  A swap
# fires at the offer window's midpoint; the artifact tracks per-replica
# occupancy (router balance) and the swap pause p99 across legs (must
# stay far under one flush interval — commit is a pointer swap, priming
# is off-path).
FLEET_LEGS = int(os.environ.get("BENCH_FLEET_LEGS", "1"))
FLEET_REPLICAS = int(os.environ.get("BENCH_FLEET_REPLICAS", "4"))
FLEET_QPS = 2000.0
FLEET_DURATION_S = 3.0
FLEET_MAX_BATCH = 32
FLEET_QUEUE_BOUND = 256
FLEET_DEADLINE_MS = 1500.0
FLEET_BATCH_DELAY_MS = 40.0

# --- hedging leg (ISSUE 10): the same open-loop workload against a
# 2-replica fleet with ONE injected straggler (replica 0 stalls every
# flush), hedging on vs off, order-alternated in one process (the
# run_overhead_pair discipline — below the collapse knee, so the A/B is
# measurable).  The acceptance claim: hedging cuts p99 (queued flushes
# escape the straggler's queue) at ≤ 5% achieved-QPS cost — losers are
# claim-skips, not duplicate device work.
HEDGE_LEGS = int(os.environ.get("BENCH_HEDGE_LEGS", "1"))
HEDGE_QPS = float(os.environ.get("BENCH_HEDGE_QPS", "250"))
HEDGE_ROUNDS = int(os.environ.get("BENCH_HEDGE_ROUNDS", "4"))
HEDGE_STRAGGLER_MS = 60.0
HEDGE_FLOOR_MS = 10.0

# --- AOT artifact legs (ISSUE 11): cold-start-to-first-prediction and
# supervisor restart-to-rejoin, each as an artifact-vs-compile A/B over
# an identical published registry version — every sample in a fresh
# subprocess with a fresh (empty) persistent compile cache, so the
# delta IS the pre-lowered executable tier, not leftover process
# warmth.  Both arms' first predictions must match bit-for-bit.
ARTIFACT_LEGS = int(os.environ.get("BENCH_ARTIFACT_LEGS", "1"))
ARTIFACT_AB_ROUNDS = int(os.environ.get("BENCH_ARTIFACT_ROUNDS", "2"))

# --- multi-tenant leg (ISSUE 14): N co-served pipelines sharing a
# featurization prefix through the cross-pipeline stage pool, vs the
# IDENTICAL service with sharing disabled — in-process A/B,
# order-alternating rounds with a discarded warmup (the
# run_overhead_pair discipline: the claim is a ratio, so both arms
# share process warmth).  The artifact tracks the aggregate-QPS
# speedup (acceptance: ≥ 1.5× with a prefix-dominated workload), the
# per-tenant p99 fairness ratio under equal offered load (acceptance:
# ≤ 1.25), pool hit/eviction counts, and a shared-vs-unshared
# bit-identity pin (sharing is an execution strategy, not a numerics
# change).
TENANT_LEGS = int(os.environ.get("BENCH_TENANT_LEGS", "1"))
TENANT_COUNT = int(os.environ.get("BENCH_TENANT_COUNT", "3"))
TENANT_QPS = float(os.environ.get("BENCH_TENANT_QPS", "12000"))
TENANT_ROUNDS = int(os.environ.get("BENCH_TENANT_ROUNDS", "3"))
TENANT_BRANCHES = int(os.environ.get("BENCH_TENANT_BRANCHES", "12"))
TENANT_MAX_BATCH = int(os.environ.get("BENCH_TENANT_MAX_BATCH", "64"))

# --- process fleet leg (ISSUE 15): thread-vs-process A/B on a
# COMPUTE-BOUND workload — a deterministic pure-Python featurizer that
# holds the GIL (like real tokenize/ngram stages), offered above
# capacity so achieved QPS measures capacity.  Worker threads serialize
# on the GIL through that stage; worker processes compute in parallel,
# so on an N-core host the process fleet's speedup approaches
# min(workers, cores) while threads stay pinned near 1 core.  The leg
# reports the scheduler-affinity core count and gates the >= 1.8x
# acceptance only where >= 2 cores exist (a 1-core host cannot express
# the claim; there the gate is process overhead <= 30%).  Thread/
# process predictions must match bit-for-bit, and the autoscale
# sub-leg must scale 1 -> N under open-loop load and back down idle
# with zero dropped or hung requests.  NOTE: the PR-8 fleet leg above
# is STALL-dominated by construction (batch_delay_ms is an injected,
# GIL-RELEASING sleep) — its fleet_speedup measures router concurrency
# over emulated device stalls and was never a multi-core hardware
# claim; THIS leg is the multi-core compute claim.
PROC_LEGS = int(os.environ.get("BENCH_PROC_LEGS", "1"))
PROC_WORKERS = int(os.environ.get("BENCH_PROC_WORKERS", "2"))
PROC_QPS = float(os.environ.get("BENCH_PROC_QPS", "2500"))
PROC_ROUNDS = int(os.environ.get("BENCH_PROC_ROUNDS", "3"))
PROC_DURATION_S = float(os.environ.get("BENCH_PROC_DURATION", "2.5"))
PROC_BURN_ROUNDS = int(os.environ.get("BENCH_PROC_BURN", "2000"))
AUTOSCALE_QPS = float(os.environ.get("BENCH_AUTOSCALE_QPS", "2000"))
AUTOSCALE_DURATION_S = float(os.environ.get("BENCH_AUTOSCALE_DURATION", "4"))
INGRESS_LEGS = int(os.environ.get("BENCH_INGRESS_LEGS", "1"))
INGRESS_DURATION_S = float(os.environ.get("BENCH_INGRESS_DURATION", "1.5"))
INGRESS_ROUNDS = int(os.environ.get("BENCH_INGRESS_ROUNDS", "2"))
INGRESS_SHARDS = int(os.environ.get("BENCH_INGRESS_SHARDS", "2"))

# --- plan leg (ISSUE 20): the cost-based physical planner's A/B — the
# same fitted pipeline with the sampled PhysicalPlan installed (stage
# winners + derived serving knobs) vs the static defaults, on the raw
# forward leg and the open-loop serve leg, plus a live PlanTuner retune
# under the workload zoo's drift scenario.  Acceptance: speedup >= 1.0
# (off-TPU both arms run identical physics, so ~1.0 is the honest
# expectation) and the drift retune improves windowed p99 or reverts
# through the bake guard with zero lost futures.
PLAN_LEGS = int(os.environ.get("BENCH_PLAN_LEGS", "1"))
PLAN_QPS = float(os.environ.get("BENCH_PLAN_QPS", "300"))
PLAN_DURATION_S = float(os.environ.get("BENCH_PLAN_DURATION", "2.5"))
PLAN_DRIFT_DURATION_S = float(os.environ.get("BENCH_PLAN_DRIFT_DURATION", "3"))


def _f32_peak() -> float:
    """TPU v5 lite f32 peak, from the repo's single roofline source."""
    from keystone_tpu.workflow.profiling import _ROOFLINE_PEAKS

    return _ROOFLINE_PEAKS["TPU v5 lite"][0]


_BF16_EFFECTIVE_PEAK = 1.97e14  # TPU v5 lite bf16-grade MXU peak (~197 Tf/s);
# XLA executes default-precision f32 matmuls as bf16-grade passes, so this
# is the honest utilization denominator for the matmul-dense stages
N_LEGS = int(os.environ.get("BENCH_LEGS", "3"))  # ≥3 resynced samples
_BASELINE_CACHE = os.path.join(os.path.dirname(__file__), ".bench_cpu_baseline.json")
# bump whenever the methodology, config, or the measured PROGRAM changes
# so stale caches die (v5: SIFT windowing default moved to the matmul
# path; v6: kills any cache written in the window where r4's first cut
# accidentally benchmarked with SIFT smoothing disabled; v7: the
# per-scale Gaussian blur moved to banded-matrix einsums — the CPU leg
# runs the same program)
_BASELINE_VERSION = 7


def build_forward(bin_sizes=(4,), smoothing_magnif: float = 6.0):
    # smoothing default matches SIFTExtractor's constructor (6.0): the
    # headline program has included the per-scale smoothing since r1,
    # and r4's first cut accidentally disabled it (making the headline
    # incomparable to r2/r3 and to the cached CPU baseline)
    import jax.numpy as jnp

    from keystone_tpu.models.block_ls import BlockLinearMapper
    from keystone_tpu.models.gmm import GaussianMixtureModel
    from keystone_tpu.models.pca import PCATransformer
    from keystone_tpu.ops import (
        GrayScaler,
        NormalizeRows,
        SIFTExtractor,
        SignedHellingerMapper,
    )
    from keystone_tpu.ops.fisher import FisherVector

    rng = np.random.default_rng(0)
    sift = SIFTExtractor(
        step=SIFT_STEP, bin_sizes=bin_sizes, smoothing_magnif=smoothing_magnif
    )
    pca = PCATransformer(
        jnp.asarray(np.linalg.qr(rng.normal(size=(128, PCA_DIMS)))[0], jnp.float32),
        mean=jnp.zeros((128,), jnp.float32),
    )
    gmm = GaussianMixtureModel(
        jnp.full((GMM_K,), 1.0 / GMM_K, jnp.float32),
        jnp.asarray(rng.normal(size=(GMM_K, PCA_DIMS)), jnp.float32),
        jnp.ones((GMM_K, PCA_DIMS), jnp.float32),
    )
    fv_dim = 2 * GMM_K * PCA_DIMS
    block = 4096
    nb = -(-fv_dim // block)
    blm = BlockLinearMapper(
        jnp.asarray(
            0.01 * rng.normal(size=(nb, block, NUM_CLASSES)), jnp.float32
        ),
        block,
    )
    gray, hell, norm = GrayScaler(), SignedHellingerMapper(), NormalizeRows()
    fv = FisherVector(gmm)

    def forward(images):
        g = gray.apply_batch(images)
        desc, mask = sift.apply_batch(g)
        desc, mask = pca.apply_batch(desc, mask=mask)
        feats = fv.apply_batch(desc, mask=mask)
        feats = norm.apply_batch(hell.apply_batch(feats))
        return blm.apply_batch(feats)

    return forward


def flops_per_image(bin_sizes=(4,), smoothing: bool = True) -> float:
    """Analytic FLOPs/image of the forward path (2·MACs convention).

    XLA's compiled cost analysis can't price the Pallas FV custom call,
    so the count is assembled per stage; elementwise work is ignored
    (<5% of total).  T = number of dense-SIFT descriptors per image.
    """
    from keystone_tpu.ops.sift import _window_matrix, sift_output_count

    t = sift_output_count(IMAGE_HW, IMAGE_HW, SIFT_STEP, bin_sizes)
    d_sift = 128
    # SIFT windowing (matmul path, the r3 default), per scale: two dense
    # einsums — (P, H)×(H, W·8) then (Q, W)×(W, P·8), P = Q = 4·centers
    sift = 0
    for b in bin_sizes:
        p = _window_matrix(IMAGE_HW, SIFT_STEP, b)[0].shape[0]
        sift += 2 * p * IMAGE_HW * IMAGE_HW * 8 + 2 * p * IMAGE_HW * p * 8
    if smoothing:
        # per-scale Gaussian blur as banded (extent, extent) einsums
        # (the r4 matmul strategy): one (H,H)×(H,W) + one (W,W)-side
        # pass over the single grayscale channel per scale (~2% of the
        # single-scale total; ADVICE r4 — these run on the MXU and
        # belong in the executed-FLOPs accounting)
        sift += len(bin_sizes) * (
            2 * IMAGE_HW * IMAGE_HW * IMAGE_HW
            + 2 * IMAGE_HW * IMAGE_HW * IMAGE_HW
        )
    pca = 2 * t * d_sift * PCA_DIMS
    # FV kernel: 4 MXU contractions of T×D×K (x²·inv, x·μinv, γᵀx, γᵀx²)
    fv = 4 * 2 * t * PCA_DIMS * GMM_K
    blm = 2 * (2 * GMM_K * PCA_DIMS) * NUM_CLASSES
    return float(sift + pca + fv + blm)


def measure_ips(
    batch: int,
    run_lengths=RUN_LENGTHS,
    reps: int = REPS,
    warmup: int = WARMUP,
    bin_sizes=None,
    smoothing_magnif: float | None = None,
) -> float:
    import jax

    # None → build_forward's own defaults.  Duplicating those defaults
    # here is what broke the r4 headline (a 0.0 copy silently overrode
    # the restored 6.0): forward ONLY what the caller explicitly set.
    kw = {}
    if bin_sizes is not None:
        kw["bin_sizes"] = bin_sizes
    if smoothing_magnif is not None:
        kw["smoothing_magnif"] = smoothing_magnif
    forward = jax.jit(build_forward(**kw))
    images = np.random.default_rng(1).uniform(
        0, 1, (batch, IMAGE_HW, IMAGE_HW, 3)
    ).astype(np.float32)
    import jax.numpy as jnp

    images = jnp.asarray(images)

    def sync(out):
        return out.block_until_ready()

    for _ in range(warmup):
        sync(forward(images))

    def run(iters: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = forward(images)
        sync(out)
        return time.perf_counter() - t0

    # t(n) = fixed_sync + n·per_iter.  Theil–Sen slope (median of pairwise
    # slopes) over interleaved lengths×reps: robust to host jitter and
    # to ambient device-clock drift.
    points = []
    for _ in range(reps):
        for n in run_lengths:
            points.append((n, run(n)))
    slopes = [
        (tj - ti) / (nj - ni)
        for i, (ni, ti) in enumerate(points)
        for nj, tj in points[i + 1:]
        if nj != ni
    ]
    per_iter = float(np.median(slopes)) if slopes else float("nan")
    # physical plausibility cap: a host hiccup mid-measurement can
    # leave the pairwise-slope median absurdly small (rounds 1–5, not
    # re-measured: a 1.5M-ips multi-scale leg ≈ 25× the chip's possible
    # rate).  Any
    # reading beyond 2× the bf16 MXU peak over the program's analytic
    # FLOPs is a broken measurement, not a fast chip.
    cap_per_iter = (
        batch * flops_per_image(bin_sizes or (4,)) / (2.0 * _BF16_EFFECTIVE_PEAK)
    )
    if not per_iter > 0 or per_iter < cap_per_iter:
        n_max = max(run_lengths)
        per_iter = float(
            np.median([t / n for n, t in points if n == n_max])
        )
        sys.stderr.write(
            "bench: slope estimator degenerate/implausible; "
            "reporting sync-dominated mean\n"
        )
    return batch / per_iter


def measure_fit(n: int = FIT_N) -> dict:
    """One end-to-end north-star FIT leg: synthetic ImageNet config
    through the REAL app build (two FV branches with in-graph
    PCA/GMM vocabulary fits, CSE-merged featurize, weighted BCD solve),
    honestly blocked at the end.  Data generation happens OUTSIDE the
    timer — it is loader cost, not fit cost.

    The leg runs under a run ledger (keystone_tpu.obs) and returns its
    obs summary (stage top-k, retry totals, solver convergence points,
    memory watermarks) under ``"obs"`` so every the round's bench artifact carries
    the operational context of its own fit.  Ledger overhead is a
    handful of JSONL writes per stage plus one tiny host callback per
    solver epoch — noise against a minutes-scale fit."""
    import tempfile
    import time as _time

    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.obs import ledger as obs_ledger
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import (
        Config,
        ImageNetSiftLcsFV,
    )

    cfg = Config(
        num_classes=FIT_CLASSES,
        synthetic_n=n,
        image_size=IMAGE_HW,
        gmm_k=FIT_GMM_K,
        pca_dims=PCA_DIMS,
        num_epochs=FIT_EPOCHS,
        solver_block_size=FIT_SOLVER_BLOCK,
    )
    train = ImageNetLoader.synthetic(
        n, FIT_CLASSES, size=(IMAGE_HW, IMAGE_HW), seed=1
    )
    obs_dir = tempfile.mkdtemp(prefix="kst_bench_obs_")
    obs_ledger.start_run(obs_dir)
    try:
        t0 = _time.perf_counter()
        fitted = (
            ImageNetSiftLcsFV.build(cfg, train.data, train.labels)
            .fit()
            .block_until_ready()
        )
        # block_until_ready above is the sync; read_back() then brings
        # one element of EVERY fitted array to the host for the
        # finiteness check — cheaper than the 1-image probe score the
        # first r4 cut used (scoring traces ~5 one-row programs per
        # fresh process: 6–7 s of NON-fit work charged to fit_seconds;
        # rounds 1–5, not re-measured).  The read is UNCONDITIONAL
        # (python -O strips asserts; only the validity checks live in
        # them).
        scalars = fitted.read_back()
        dt = _time.perf_counter() - t0
        assert scalars.size >= 1
        assert np.all(np.isfinite(scalars))
        del fitted
    finally:
        # a failed leg must not leave its ledger attached to the process
        # (the solver legs that follow would trace with obs on)
        led = obs_ledger.active()
        ledger_path = led.path if led is not None else None
        obs_ledger.stop_run()
    obs_summary = None
    dataflow = {}
    if ledger_path is not None:
        try:
            from tools.obs_report import summarize

            s = summarize(ledger_path, top_k=5)
            conv = s.get("convergence") or {}
            obs_summary = {
                "stage_top": s.get("stage_top"),
                "retries": s.get("retries"),
                "memory": s.get("memory"),
                "solver_epochs": {k: len(v) for k, v in conv.items()},
                "io": {
                    k: v
                    for k, v in (s.get("io") or {}).items()
                    if isinstance(v, (int, float)) and v
                },
            }
            dataflow = s.get("dataflow") or {}
        except Exception as e:  # the summary must never fail the leg
            obs_summary = {"error": repr(e)[:200]}
    out = {
        "fit_seconds": dt,
        "fit_images_per_sec": n / dt,
        "obs": obs_summary,
    }
    # first-class dataflow accounts (ISSUE 7): seconds the host spent
    # blocked on device results / on host→device staging during the
    # fit, and the busy share of the FIT wall clock (the obs summary's
    # own fraction is over ledger wall time, which includes the report
    # tail — the fit-relative number is the round-over-round metric)
    busy = dataflow.get("device_busy_seconds")
    if busy is not None:
        out["device_busy_seconds"] = busy
        out["transfer_seconds"] = dataflow.get("transfer_seconds", 0.0)
        out["device_busy_fraction"] = busy / dt if dt > 0 else None
    return out


def solver_flops(n: int, d: int, k: int, bs: int, epochs: int) -> float:
    """Analytic FLOPs of the weighted-BCD solve (2·MACs): per epoch and
    block — Gramian AᵀA (2·n·w²), Aᵀtarget (2·n·w·k), the target and
    residual updates (≈4·n·w·k) — summed over blocks with the LAST
    block's true width w (not bs: charging a ragged tail as a full
    block would inflate the reported TFLOP/s); the w×w Cholesky factors
    are negligible at these shapes."""
    per_epoch = 0
    for lo in range(0, d, bs):
        w = min(bs, d - lo)
        per_epoch += 2 * n * w * w + 6 * n * w * k
    return float(epochs * per_epoch)


def measure_solver() -> dict:
    """Solver-phase TFLOP/s: the weighted-BCD fit alone on synthetic
    features at exactly the north-star post-featurize shape
    (n=FIT_N, d = two branches × 2·K·D, k=FIT_CLASSES)."""
    import time as _time

    import jax

    from keystone_tpu.models.block_weighted_ls import (
        BlockWeightedLeastSquaresEstimator,
    )

    n, k = FIT_N, FIT_CLASSES
    d = 2 * (2 * FIT_GMM_K * PCA_DIMS)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), rng.integers(0, k, size=n)] = 1.0
    est = BlockWeightedLeastSquaresEstimator(
        block_size=FIT_SOLVER_BLOCK,
        num_iter=FIT_EPOCHS,
        lam=1e-4,
        mixture_weight=0.25,
    )
    import jax.numpy as jnp

    xd, yd = jnp.asarray(x), jnp.asarray(y)
    model = est.fit_arrays(xd, yd)  # warmup leg pays the compile
    np.asarray(model.flat_weights[:1, :1])
    t0 = _time.perf_counter()
    model = est.fit_arrays(xd, yd)
    np.asarray(model.flat_weights[:1, :1])  # sync (a small host read)
    dt = _time.perf_counter() - t0
    tf = solver_flops(n, d, k, FIT_SOLVER_BLOCK, FIT_EPOCHS) / dt / 1e12
    return {"solver_seconds": dt, "solver_tflops": tf}


def measure_solver_at_scale() -> dict:
    """Weighted-BCD solver at reference scale: n=65536 × d=16384 × k=64
    (~80-93% of the correctness-pinned true-f32 peak in rounds 1–5, not
    re-measured).  Data is generated ON DEVICE — the measurement does
    not need a 4.3 GB host copy."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from keystone_tpu.models.block_weighted_ls import (
        BlockWeightedLeastSquaresEstimator,
    )

    x = jax.random.normal(
        jax.random.PRNGKey(3), (ATSCALE_N, ATSCALE_D), jnp.float32
    )
    lab = jax.random.randint(
        jax.random.PRNGKey(4), (ATSCALE_N,), 0, ATSCALE_K
    )
    y = 2.0 * jax.nn.one_hot(lab, ATSCALE_K, dtype=jnp.float32) - 1.0
    est = BlockWeightedLeastSquaresEstimator(
        block_size=FIT_SOLVER_BLOCK,
        num_iter=ATSCALE_EPOCHS,
        lam=1e-4,
        mixture_weight=0.25,
    )
    model = est.fit_arrays(x, y)  # warmup leg pays compile + data gen
    np.asarray(model.flat_weights[:1, :1])
    t0 = _time.perf_counter()
    model = est.fit_arrays(x, y)
    np.asarray(model.flat_weights[:1, :1])  # real device→host sync
    dt = _time.perf_counter() - t0
    tf = (
        solver_flops(ATSCALE_N, ATSCALE_D, ATSCALE_K, FIT_SOLVER_BLOCK, ATSCALE_EPOCHS)
        / dt
        / 1e12
    )
    return {"solver_scale_seconds": dt, "solver_scale_tflops": tf}


def kernel_flops(n_rows: int, d: int, k: int, bs: int, epochs: int) -> float:
    """Analytic FLOPs of the blockwise KRR sweep (2·MACs): per epoch
    and block — the (n × bs) kernel column gemm (2·n·bs·d), the F
    update (2·n·bs·k), the block target (2·bs²·k), and the bs³/3
    Cholesky.  Identical for the in-core and out-of-core sweeps (the
    OC form computes the same column block as nb tiles)."""
    nb = -(-n_rows // bs)
    per_epoch = nb * (
        2 * n_rows * bs * d + 2 * n_rows * bs * k + 2 * bs * bs * k + bs**3 / 3
    )
    return float(epochs * per_epoch)


def measure_kernel_at_scale() -> dict:
    """Kernel solver tier A/B: one in-core blockwise KRR fit and one
    out-of-core streamed gram-block fit of the SAME seeded problem,
    with the OC leg's dataflow accounts (device-busy fraction, transfer
    seconds) read from the metrics registry and prediction parity
    reported as r²."""
    import shutil
    import tempfile
    import time as _time

    import jax.numpy as jnp

    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        KernelRidgeRegressionEstimator,
    )
    from keystone_tpu.obs import metrics
    from keystone_tpu.workflow.blockstore import RowBlockStore
    from keystone_tpu.workflow.dataset import Dataset

    n, d, k, bs = KERNEL_N, KERNEL_D, KERNEL_K, KERNEL_BLOCK
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, k)).astype(np.float32)
    y = np.tanh(x @ w / np.sqrt(d)).astype(np.float32)
    xt = rng.normal(size=(512, d)).astype(np.float32)
    est = KernelRidgeRegressionEstimator(
        GaussianKernelGenerator(KERNEL_GAMMA),
        lam=1e-4,
        block_size=bs,
        num_epochs=KERNEL_EPOCHS,
    )
    flops = kernel_flops(n, d, k, bs, KERNEL_EPOCHS)

    # ---- in-core sweep (warmup pays the compile)
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    model = est.fit_arrays(xd, yd)
    np.asarray(model.alpha[:1, :1])
    t0 = _time.perf_counter()
    model = est.fit_arrays(xd, yd)
    np.asarray(model.alpha[:1, :1])  # real device→host sync
    in_seconds = _time.perf_counter() - t0
    p_in = np.asarray(model.apply_batch(jnp.asarray(xt)))

    # ---- out-of-core sweep: spill once (timed separately), then stream
    spill_root = tempfile.mkdtemp(prefix="bench_krr_")
    try:
        t0 = _time.perf_counter()
        store = RowBlockStore.from_array(spill_root, x, bs)
        spill_seconds = _time.perf_counter() - t0
        labels = Dataset(yd, n=n)
        oc_model = est.fit_store(store, labels)  # warmup: compiles steps
        before = metrics.REGISTRY.snapshot()["histograms"]
        t0 = _time.perf_counter()
        oc_model = est.fit_store(store, labels)
        np.asarray(oc_model.alpha[:1, :1])
        oc_seconds = _time.perf_counter() - t0
        after = metrics.REGISTRY.snapshot()["histograms"]

        def _delta(name):
            hi = (after.get(name) or {}).get("sum", 0.0) or 0.0
            lo = (before.get(name) or {}).get("sum", 0.0) or 0.0
            return float(hi - lo)

        transfer_seconds = _delta("blockstore.stage_wait_seconds")
        device_busy_seconds = _delta("device.busy_seconds")
        p_oc = np.asarray(oc_model.apply_batch(jnp.asarray(xt)))
        store_bytes = store.nbytes()
    finally:
        shutil.rmtree(spill_root, ignore_errors=True)

    ss_res = float(((p_oc - p_in) ** 2).sum())
    ss_tot = float(((p_in - p_in.mean(axis=0)) ** 2).sum())
    r2 = 1.0 - ss_res / ss_tot if ss_tot > 0 else None
    nb = store.num_blocks
    # the OC sweep's peak device residency: two staged (bs, d) row
    # blocks (current + the window's in-flight transfer) plus the
    # (α, F, Y) per-block carries — everything else stays on disk
    resident_bytes = 2 * bs * d * 4 + 3 * nb * bs * k * 4
    return {
        "kernel_tflops": in_seconds and flops / in_seconds / 1e12,
        "kernel_seconds": in_seconds,
        "oc_kernel_tflops": oc_seconds and flops / oc_seconds / 1e12,
        "oc_kernel_seconds": oc_seconds,
        "oc_spill_seconds": spill_seconds,
        "oc_vs_incore_r2": r2,
        "device_busy_seconds": device_busy_seconds,
        "transfer_seconds": transfer_seconds,
        "device_busy_fraction": (
            device_busy_seconds / oc_seconds if oc_seconds > 0 else None
        ),
        "oc_store_bytes": int(store_bytes),
        "oc_resident_bytes": int(resident_bytes),
        "oc_over_resident_x": round(store_bytes / resident_bytes, 2),
    }


def cpu_baseline_ips() -> float:
    if os.path.exists(_BASELINE_CACHE):
        try:
            with open(_BASELINE_CACHE) as f:
                cached = json.load(f)
            if cached.get("v") == _BASELINE_VERSION:
                return float(cached["ips"])
        except Exception:
            pass
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cpu"],
        capture_output=True,
        text=True,
        timeout=3600,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    try:
        ips = float(json.loads(line)["cpu_ips"])
    except Exception:
        sys.stderr.write(f"cpu baseline failed: {proc.stderr[-500:]}\n")
        return 0.0
    with open(_BASELINE_CACHE, "w") as f:
        json.dump({"ips": ips, "v": _BASELINE_VERSION}, f)
    return ips


def device_info() -> dict:
    """The device this process measures on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def main() -> int:
    """A measuring process (any ``--leg*`` / ``--sweep`` / ``--cpu``
    flag) owns its device: one process per chip.  With no such flag
    this process only ORCHESTRATES — it never initialises a JAX backend
    (a parent that holds the chip starves every child that needs it)."""
    args = sys.argv[1:]
    measuring = "--cpu" in args or "--sweep" in args or any(
        a.startswith("--leg") for a in args
    )
    if not measuring:
        return orchestrate()
    if "--cpu" in args:
        # through the environment, before jax is imported: the leg's own
        # children (process workers, serve_bench's per-arm subprocesses)
        # inherit it, where a jax.config setting pinned this process only
        # and left them to go for the one chip
        os.environ["JAX_PLATFORMS"] = "cpu"
    from keystone_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    device = device_info()
    if device["platform"] != "tpu" and "--cpu" not in args:
        sys.stderr.write(
            f"bench.py: needs a TPU, found {device}; pass --cpu to measure "
            "on the CPU knowingly\n"
        )
        return 2
    # first stdout line of every measuring process: where it ran
    print(json.dumps({"device": device}), flush=True)
    run_leg(args)
    return 0


def run_leg(args) -> None:
    if args == ["--cpu"]:
        # same per-image program + same marginal-time estimator, scaled
        # down (the CPU leg is ~3 orders slower)
        ips = measure_ips(batch=32, run_lengths=(1, 2, 3), reps=2, warmup=1)
        print(json.dumps({"cpu_ips": ips}))
        return

    if "--sweep" in args:
        for b in (32, 64, 128, 256, 512):
            try:
                ips = measure_ips(b, run_lengths=(10, 25, 40), reps=2)
            except Exception as e:
                print(json.dumps({"batch": b, "error": repr(e)[:200]}))
                continue
            tf = ips * flops_per_image() / 1e12
            print(
                json.dumps(
                    {"batch": b, "ips": round(ips, 1),
                     "tflops": round(tf, 2),
                     "mfu_f32": round(tf * 1e12 / _f32_peak(), 3)}
                )
            )
        return

    if "--leg" in args:
        # one independent sample for the band (fresh process = fresh
        # backend init, which is where the ±10–25% ambient device-clock
        # spread lives — rounds 1–5, not re-measured)
        # flops_per_image and the peak ride along: the parent computes
        # TFLOP/s and MFU without importing the program (it stays off JAX)
        print(
            json.dumps(
                {
                    "leg_ips": measure_ips(BATCH),
                    "flops_per_image": flops_per_image(),
                    "f32_peak": _f32_peak(),
                }
            )
        )
        return

    if "--leg-ms" in args:
        print(
            json.dumps(
                {
                    "leg_ips": measure_ips(
                        MS_BATCH,
                        run_lengths=(10, 25, 40),
                        reps=2,
                        bin_sizes=MS_BIN_SIZES,
                        smoothing_magnif=MS_SMOOTHING,
                    )
                }
            )
        )
        return

    if "--leg-fit" in args:
        out = measure_fit()
        out.update(measure_solver())
        print(json.dumps(out))
        return

    if "--leg-serve" in args:
        from tools import serve_bench

        svc, item_shape = serve_bench.build_service(
            max_batch=SERVE_MAX_BATCH,
            queue_bound=SERVE_QUEUE_BOUND,
            deadline_ms=SERVE_DEADLINE_MS,
            # tracing on by default (the shipping config); the
            # recorder-overhead pin is its own in-process A/B leg
            # (--leg-serve-overhead), since THIS leg sits on the
            # overload collapse cliff where ratios are unmeasurable
            recorder=os.environ.get("BENCH_SERVE_RECORDER", "1") != "0",
        )
        try:
            rep = serve_bench.run_bench(
                svc,
                item_shape,
                qps=SERVE_QPS,
                duration=SERVE_DURATION_S,
                deadline_ms=SERVE_DEADLINE_MS,
                batch_delay_ms=SERVE_BATCH_DELAY_MS,
            )
        finally:
            svc.close()
        print(json.dumps(rep))
        return

    if "--leg-serve-overhead" in args:
        from tools import serve_bench

        print(
            json.dumps(
                serve_bench.run_overhead_pair(
                    qps=SERVE_OVERHEAD_QPS,
                    duration=SERVE_DURATION_S,
                    rounds=SERVE_OVERHEAD_ROUNDS,
                    max_batch=SERVE_MAX_BATCH,
                    deadline_ms=500.0,
                )
            )
        )
        return

    if "--leg-serve-fleet" in args:
        from tools import serve_bench

        svc, item_shape = serve_bench.build_service(
            max_batch=FLEET_MAX_BATCH,
            queue_bound=FLEET_QUEUE_BOUND,
            deadline_ms=FLEET_DEADLINE_MS,
            replicas=FLEET_REPLICAS,
        )
        try:
            rep = serve_bench.run_bench(
                svc,
                item_shape,
                qps=FLEET_QPS,
                duration=FLEET_DURATION_S,
                deadline_ms=FLEET_DEADLINE_MS,
                batch_delay_ms=FLEET_BATCH_DELAY_MS,
                swap_pipeline=serve_bench.build_pipeline(seed=1),
            )
        finally:
            svc.close()
        print(json.dumps(rep))
        return

    if "--leg-serve-hedge" in args:
        from tools import serve_bench

        print(
            json.dumps(
                serve_bench.run_straggler_ab(
                    qps=HEDGE_QPS,
                    duration=SERVE_DURATION_S,
                    rounds=HEDGE_ROUNDS,
                    replicas=2,
                    max_batch=SERVE_MAX_BATCH,
                    straggler_ms=HEDGE_STRAGGLER_MS,
                    hedge_ms=HEDGE_FLOOR_MS,
                )
            )
        )
        return

    if "--leg-serve-tenants" in args:
        from tools import serve_bench

        print(
            json.dumps(
                serve_bench.run_tenants_ab(
                    qps=TENANT_QPS,
                    duration=SERVE_DURATION_S,
                    rounds=TENANT_ROUNDS,
                    tenants=TENANT_COUNT,
                    branches=TENANT_BRANCHES,
                    max_batch=TENANT_MAX_BATCH,
                )
            )
        )
        return

    if "--leg-serve-procs" in args:
        from tools import serve_bench

        print(
            json.dumps(
                {
                    "procs_ab": serve_bench.run_procs_ab(
                        qps=PROC_QPS,
                        duration=PROC_DURATION_S,
                        rounds=PROC_ROUNDS,
                        workers=PROC_WORKERS,
                        burn_rounds=PROC_BURN_ROUNDS,
                    ),
                    "autoscale": serve_bench.run_autoscale_scenario(
                        qps=AUTOSCALE_QPS,
                        duration=AUTOSCALE_DURATION_S,
                        max_workers=max(2, PROC_WORKERS),
                        burn_rounds=PROC_BURN_ROUNDS,
                    ),
                }
            )
        )
        return

    if "--leg-serve-ingress" in args:
        from tools import serve_bench

        print(
            json.dumps(
                serve_bench.run_ingress_ab(
                    duration=INGRESS_DURATION_S,
                    rounds=INGRESS_ROUNDS,
                    shards=INGRESS_SHARDS,
                )
            )
        )
        return

    if "--leg-serve-artifacts" in args:
        from tools import serve_bench

        print(
            json.dumps(
                {
                    "cold_start": serve_bench.run_cold_start_ab(
                        rounds=ARTIFACT_AB_ROUNDS
                    ),
                    "restart": serve_bench.run_restart_ab(
                        rounds=ARTIFACT_AB_ROUNDS
                    ),
                }
            )
        )
        return

    if "--leg-plan" in args:
        from tools import serve_bench

        print(
            json.dumps(
                serve_bench.run_plan_ab(
                    qps=PLAN_QPS,
                    duration=PLAN_DURATION_S,
                    drift_duration=PLAN_DRIFT_DURATION_S,
                )
            )
        )
        return

    if "--leg-solver-scale" in args:
        print(json.dumps(measure_solver_at_scale()))
        return

    if "--leg-kernel" in args:
        print(json.dumps(measure_kernel_at_scale()))
        return

    if "--leg-fit-scale" in args:
        out = measure_fit(n=FIT_SCALE_N)
        print(json.dumps(out))
        return

    raise SystemExit(f"bench.py: unknown measuring flags {args}")


def orchestrate() -> int:
    # Every metric is a MEDIAN over ≥3 process-level legs, with the
    # min/max band in the JSON — a single invocation's number can sit
    # anywhere in a ±25% band (round-2 review item 7).  EVERY leg is a
    # child process, one after another: this parent never touches a JAX
    # backend, so each child finds the chip free.  The first leg pays
    # any compile; later legs ride the compilation cache.
    failed_legs: list = []
    devices: list = []

    def subprocess_leg(flag: str, required=("leg_ips",), env=None, cpu=False):
        # cpu=True: the leg spawns process workers of its own, which
        # cannot share a chip with the leg that spawned them
        # (ChipOwnershipError); its claim is host-side, so it and all its
        # descendants run on the CPU backend knowingly (JAX_PLATFORMS=cpu
        # in the leg's environment) and its "device" says so
        try:
            # the run itself sits INSIDE the try: one hung leg must not
            # abort the whole multi-leg artifact — it is recorded in
            # failed_legs and the run exits non-zero at the end
            child_env = None
            if env:
                child_env = {**os.environ, **env}
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), flag]
                + (["--cpu"] if cpu else []),
                capture_output=True,
                text=True,
                timeout=3600,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env=child_env,
            )
            if proc.returncode != 0:
                raise RuntimeError(f"exit code {proc.returncode}")
            lines = proc.stdout.strip().splitlines()
            device = json.loads(lines[0])["device"]
            devices.append(device)
            leg = json.loads(lines[-1])
            # one malformed leg (e.g. a stray JSON log line on stdout)
            # must skip, not crash the whole multi-leg run
            if not isinstance(leg, dict) or any(k not in leg for k in required):
                raise ValueError(f"leg output missing {required}: {leg!r}")
            leg["device"] = device
            return leg
        except Exception as e:
            # proc is unbound when the run itself timed out/raised
            err = getattr(locals().get("proc"), "stderr", "") or ""
            sys.stderr.write(f"bench leg {flag} failed ({e}): {err[-300:]}\n")
            failed_legs.append(flag)
            return None

    def band(vals):
        return {
            "min": round(min(vals), 2),
            "max": round(max(vals), 2),
            "n_legs": len(vals),
        }

    def dataflow_fields(legs) -> dict:
        """Median device-busy / transfer accounts over a fit leg set —
        the first-class fields the tentpole's success metric tracks
        (device_busy_fraction must RISE round over round as the feed
        stops starving the device)."""
        out = {}
        for key, digits in (
            ("device_busy_seconds", 3),
            ("transfer_seconds", 3),
            ("device_busy_fraction", 4),
        ):
            vals = [
                float(lg[key]) for lg in legs if lg.get(key) is not None
            ]
            if vals:
                out[key] = round(float(np.median(vals)), digits)
        return out

    samples = []
    for _ in range(max(1, N_LEGS)):
        leg = subprocess_leg(
            "--leg", required=("leg_ips", "flops_per_image", "f32_peak")
        )
        if leg:
            samples.append(float(leg["leg_ips"]))
            image_flops = float(leg["flops_per_image"])
            f32_peak = float(leg["f32_peak"])
    if not samples:
        sys.stderr.write("bench: no forward leg succeeded; nothing to report\n")
        return 1
    ips = float(np.median(samples))
    tf = ips * image_flops / 1e12

    # fit + multi-scale legs, same band discipline
    fit_legs = [
        lg
        for lg in (
            subprocess_leg(
                "--leg-fit",
                required=("fit_seconds", "fit_images_per_sec", "solver_tflops"),
            )
            for _ in range(N_LEGS)
        )
        if lg
    ]
    ms_legs = [lg for lg in (subprocess_leg("--leg-ms") for _ in range(N_LEGS)) if lg]

    # at-scale legs (round-4 review item 5): the solver shape that proves
    # MXU-grade training throughput, and the n=8192 full fit that
    # exercises the shape-stable chunked-apply path — both as per-round
    # artifacts with bands (SCALE_LEGS process legs each)
    solver_scale_legs = [
        lg
        for lg in (
            subprocess_leg("--leg-solver-scale", required=("solver_scale_tflops",))
            for _ in range(SCALE_LEGS)
        )
        if lg
    ]
    fit_scale_legs = [
        lg
        for lg in (
            subprocess_leg("--leg-fit-scale", required=("fit_seconds",))
            for _ in range(SCALE_LEGS)
        )
        if lg
    ]

    # kernel leg (ISSUE 13): the kernel solver tier's in-core-vs-OC A/B
    kernel_legs = [
        lg
        for lg in (
            subprocess_leg(
                "--leg-kernel",
                required=("kernel_tflops", "oc_kernel_tflops", "oc_vs_incore_r2"),
            )
            for _ in range(KERNEL_LEGS)
        )
        if lg
    ]

    # serve leg (ISSUE 5): the online endpoint under deterministic
    # overload — one process leg (the serving layer's numbers are
    # scheduler-dominated, not device-clock-dominated)
    serve_legs = [
        lg
        for lg in (
            subprocess_leg(
                "--leg-serve", required=("achieved_qps", "p50_ms")
            )
            for _ in range(SERVE_LEGS)
        )
        if lg
    ]
    # recorder-overhead pin (ISSUE 9): in-process A/B of the identical
    # steady-state workload with the flight recorder on vs off — the
    # tracing tax must keep p99 and achieved QPS within 5%
    serve_overhead_leg = (
        subprocess_leg("--leg-serve-overhead", required=("overhead",))
        if serve_legs
        else None
    )

    # fleet leg (ISSUE 8): the N-replica fleet + mid-run hot-swap, and
    # ONE 1-replica leg with the identical config — their achieved-QPS
    # ratio is the recorded scaling claim.  On CPU hosts the child needs
    # the host platform split into N devices (appended, so a TPU host's
    # existing XLA_FLAGS survive; the flag is inert off-CPU).
    fleet_env = {
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={FLEET_REPLICAS}"
        ).strip()
    }
    fleet_legs = [
        lg
        for lg in (
            subprocess_leg(
                "--leg-serve-fleet",
                required=("achieved_qps", "replica_occupancy"),
                env=fleet_env,
            )
            for _ in range(FLEET_LEGS)
        )
        if lg
    ] if FLEET_LEGS > 0 else []
    fleet_single_leg = (
        subprocess_leg(
            "--leg-serve-fleet",
            required=("achieved_qps",),
            env={**fleet_env, "BENCH_FLEET_REPLICAS": "1"},
        )
        if fleet_legs
        else None
    )

    # hedging leg (ISSUE 10): the straggler A/B — hedging on vs off
    # against an injected per-replica stall; needs 2 host devices
    hedge_leg = (
        subprocess_leg(
            "--leg-serve-hedge", required=("hedging",), env=fleet_env
        )
        if HEDGE_LEGS > 0
        else None
    )

    # AOT artifact legs (ISSUE 11): cold-start + restart-to-rejoin,
    # artifact vs compile (the driver leg spawns its own per-arm
    # subprocesses with fresh compile caches)
    artifact_leg = (
        subprocess_leg(
            "--leg-serve-artifacts", required=("cold_start", "restart"), cpu=True
        )
        if ARTIFACT_LEGS > 0
        else None
    )

    # multi-tenant leg (ISSUE 14): shared-vs-unshared A/B over N
    # co-served pipelines sharing a featurization prefix
    tenant_leg = (
        subprocess_leg(
            "--leg-serve-tenants",
            required=("aggregate_qps_shared", "predictions_identical"),
        )
        if TENANT_LEGS > 0
        else None
    )

    # process fleet leg (ISSUE 15): thread-vs-process A/B on the
    # compute-bound workload + the 1→N→1 autoscale scenario
    proc_leg = (
        subprocess_leg(
            "--leg-serve-procs", required=("procs_ab", "autoscale"), cpu=True
        )
        if PROC_LEGS > 0
        else None
    )

    # ingress leg (ISSUE 17): threaded HTTP/JSON vs binary-batch A/B on
    # one service — the front-end ceiling, tracked per round
    ingress_leg = (
        subprocess_leg(
            "--leg-serve-ingress",
            required=("speedup", "predictions_identical"),
        )
        if INGRESS_LEGS > 0
        else None
    )

    # plan leg (ISSUE 20): planned vs static-default A/B + the live
    # drift-retune sub-check
    plan_leg = (
        subprocess_leg("--leg-plan", required=("speedup", "drift_retune"))
        if PLAN_LEGS > 0
        else None
    )

    # precision-mode sweep: same headline program and estimator, one
    # process leg per mode (KEYSTONE_MATMUL pinned in the child).  The
    # "auto" mode IS the headline measurement when the parent env does
    # not pin a policy, so those already-collected samples are reused
    # instead of paying a redundant subprocess leg.
    precision_sweep = {}
    for mode in PRECISION_MODES if PRECISION_LEGS > 0 else ():
        if mode == "auto" and not os.environ.get("KEYSTONE_MATMUL"):
            vals = list(samples)
        else:
            vals = [
                float(lg["leg_ips"])
                for lg in (
                    subprocess_leg("--leg", env={"KEYSTONE_MATMUL": mode})
                    for _ in range(PRECISION_LEGS)
                )
                if lg
            ]
        if not vals:
            continue
        mips = float(np.median(vals))
        mtf = mips * image_flops / 1e12
        precision_sweep[mode] = {
            "images_per_sec": round(mips, 1),
            "band": band(vals),
            "tflops": round(mtf, 2),
            "mfu_f32": round(mtf * 1e12 / f32_peak, 3),
            "mfu_bf16_eff": round(mtf * 1e12 / _BF16_EFFECTIVE_PEAK, 3),
        }

    cpu_ips = cpu_baseline_ips()
    vs = ips / cpu_ips if cpu_ips > 0 else None
    out = {
        "device": devices[0],
        "failed_legs": failed_legs,
        "metric": "imagenet_fv_pipeline_throughput",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs, 2) if vs else None,
        "band": band(samples),
        "tflops": round(tf, 2),
        "mfu_f32": round(tf * 1e12 / f32_peak, 3),
        "mfu_bf16_eff": round(tf * 1e12 / _BF16_EFFECTIVE_PEAK, 3),
        "config": {
            "batch": BATCH, "image_hw": IMAGE_HW, "sift_step": SIFT_STEP,
            "gmm_k": GMM_K, "pca_dims": PCA_DIMS, "classes": NUM_CLASSES,
        },
    }
    if precision_sweep:
        out["precision_sweep"] = precision_sweep
    if fit_legs:
        fit_s = [float(lg["fit_seconds"]) for lg in fit_legs]
        out["fit"] = {
            "fit_seconds": round(float(np.median(fit_s)), 2),
            "fit_images_per_sec": round(
                float(np.median([lg["fit_images_per_sec"] for lg in fit_legs])), 1
            ),
            "band_seconds": band(fit_s),
            "solver_tflops": round(
                float(np.median([lg["solver_tflops"] for lg in fit_legs])), 2
            ),
            "solver_band_tflops": band(
                [float(lg["solver_tflops"]) for lg in fit_legs]
            ),
            "config": {
                "n": FIT_N, "image_hw": IMAGE_HW, "gmm_k": FIT_GMM_K,
                "classes": FIT_CLASSES, "epochs": FIT_EPOCHS,
                "solver_block": FIT_SOLVER_BLOCK,
            },
        }
        out["fit"].update(dataflow_fields(fit_legs))
        # operational context of the fit (stage top-k, retry totals,
        # memory watermarks) from the first leg's run ledger, so the
        # perf trajectory in the round's bench artifact explains itself
        obs_leg = next((lg.get("obs") for lg in fit_legs if lg.get("obs")), None)
        if obs_leg:
            out["fit"]["obs"] = obs_leg
    if ms_legs:
        ms = [float(lg["leg_ips"]) for lg in ms_legs]
        out["multiscale"] = {
            "images_per_sec": round(float(np.median(ms)), 1),
            "band": band(ms),
            "config": {
                "batch": MS_BATCH,
                "bin_sizes": list(MS_BIN_SIZES),
                "smoothing_magnif": MS_SMOOTHING,
            },
        }
    if kernel_legs:
        med = lambda key, digits=3: round(  # noqa: E731
            float(np.median([float(lg[key]) for lg in kernel_legs
                             if lg.get(key) is not None])), digits
        )
        out["kernel_at_scale"] = {
            "tflops": med("kernel_tflops"),
            "oc_tflops": med("oc_kernel_tflops"),
            "seconds": med("kernel_seconds", 2),
            "oc_seconds": med("oc_kernel_seconds", 2),
            "oc_spill_seconds": med("oc_spill_seconds", 2),
            # the acceptance gates: r² ≥ 0.999 parity and a populated
            # dataflow account for the streamed feed
            "oc_vs_incore_r2": med("oc_vs_incore_r2", 6),
            "device_busy_fraction": med("device_busy_fraction", 4),
            "transfer_seconds": med("transfer_seconds"),
            "oc_over_resident_x": med("oc_over_resident_x", 2),
            "band_tflops": band(
                [float(lg["kernel_tflops"]) for lg in kernel_legs]
            ),
            "config": {
                "n": KERNEL_N, "d": KERNEL_D, "k": KERNEL_K,
                "block": KERNEL_BLOCK, "epochs": KERNEL_EPOCHS,
                "gamma": KERNEL_GAMMA,
            },
        }
    if solver_scale_legs:
        tfs = [float(lg["solver_scale_tflops"]) for lg in solver_scale_legs]
        out["solver_at_scale"] = {
            "tflops": round(float(np.median(tfs)), 2),
            "band_tflops": band(tfs),
            "config": {
                "n": ATSCALE_N, "d": ATSCALE_D, "k": ATSCALE_K,
                "epochs": ATSCALE_EPOCHS, "block": FIT_SOLVER_BLOCK,
            },
        }
    if serve_legs:
        # one leg's full report, medians over legs for the headline keys
        sv = dict(serve_legs[0])
        if len(serve_legs) > 1:
            for key in ("achieved_qps", "p50_ms", "p95_ms", "p99_ms"):
                vals = [
                    float(lg[key]) for lg in serve_legs if lg.get(key) is not None
                ]
                if vals:
                    sv[key] = round(float(np.median(vals)), 2)
        if serve_overhead_leg:
            # ratios near 1.0 = the recorder lives inside its overhead
            # budget (acceptance: within 5%)
            sv["recorder_overhead"] = serve_overhead_leg
        out["serve"] = sv
    if fleet_legs:
        fv = dict(fleet_legs[0])
        if len(fleet_legs) > 1:
            for key in ("achieved_qps", "p50_ms", "p95_ms", "p99_ms"):
                vals = [
                    float(lg[key]) for lg in fleet_legs if lg.get(key) is not None
                ]
                if vals:
                    fv[key] = round(float(np.median(vals)), 2)
        pauses_ms = [
            1000.0 * float(lg["swap"]["pause_seconds"])
            for lg in fleet_legs
            if lg.get("swap") and lg["swap"].get("pause_seconds") is not None
        ]
        if pauses_ms:
            fv["swap_pause_p99_ms"] = round(
                float(np.percentile(pauses_ms, 99)), 4
            )
        if fleet_single_leg and fleet_single_leg.get("achieved_qps"):
            single = float(fleet_single_leg["achieved_qps"])
            fv["single_replica_achieved_qps"] = round(single, 1)
            if single > 0 and fv.get("achieved_qps"):
                fv["fleet_speedup"] = round(
                    float(fv["achieved_qps"]) / single, 2
                )
        # the honest framing of fleet_speedup (PR-8's report implied a
        # hardware-scaling claim; it never was one): the emulated model
        # is an injected GIL-RELEASING sleep, so the ratio measures
        # router/queue concurrency over device stalls.  Multi-core
        # COMPUTE scaling is the serve_procs section's claim.
        fv["scaling_note"] = (
            "stall-dominated by construction (batch_delay_ms releases "
            "the GIL): measures router concurrency, not multi-core "
            "compute — see serve_procs for the compute-bound claim"
        )
        out["serve_fleet"] = fv
    if proc_leg:
        # the ISSUE-15 acceptance: >= 1.8x thread->process speedup on a
        # compute-bound workload where >= 2 cores exist (cores_limited
        # marks hosts that cannot express the claim), bit-identical
        # predictions, and a clean 1→N→1 autoscale scenario
        out["serve_procs"] = proc_leg
    if ingress_leg:
        # the ISSUE-17 acceptance: binary batch path >= 3x the threaded
        # HTTP/JSON per-datum QPS ceiling, p99 for both arms,
        # predictions bit-identical across JSON and binary
        out["serve_ingress"] = ingress_leg
    if plan_leg:
        # the ISSUE-20 acceptance: the planned configuration matches or
        # beats static defaults (speedup >= 1.0) and the live drift
        # retune improves p99 or reverts via the bake guard with zero
        # lost futures
        out["plan"] = plan_leg
    if hedge_leg:
        # p99_ratio < 1 = hedging rescued the straggler's queue;
        # qps_cost <= 0.05 = the acceptance budget
        out["serve_hedge"] = hedge_leg
    if tenant_leg:
        # speedup >= 1.5 = the shared stage pool pays (ISSUE 14
        # acceptance); fairness_p99_ratio <= 1.25 = DRR fair share;
        # predictions_identical pins shared-vs-unshared bit-parity
        out["serve_tenants"] = tenant_leg
    if artifact_leg:
        # speedup > 1 on both legs = the artifact tier beats fresh
        # compilation for cold start AND supervisor heal;
        # predictions_match pins artifact-vs-compile bit-parity
        for section in artifact_leg.values():
            if isinstance(section, dict):
                section.pop("samples", None)  # medians suffice in the artifact
        out["serve_artifacts"] = artifact_leg
    if fit_scale_legs:
        fss = [float(lg["fit_seconds"]) for lg in fit_scale_legs]
        out["fit_at_scale"] = {
            "fit_seconds": round(float(np.median(fss)), 2),
            "band_seconds": band(fss),
            "fit_images_per_sec": round(
                float(
                    np.median(
                        [lg["fit_images_per_sec"] for lg in fit_scale_legs]
                    )
                ),
                1,
            ),
            "config": {
                "n": FIT_SCALE_N, "image_hw": IMAGE_HW, "gmm_k": FIT_GMM_K,
                "classes": FIT_CLASSES, "epochs": FIT_EPOCHS,
            },
        }
        out["fit_at_scale"].update(dataflow_fields(fit_scale_legs))
    print(json.dumps(out))
    if failed_legs:
        sys.stderr.write(f"bench: {len(failed_legs)} leg(s) failed: {failed_legs}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
