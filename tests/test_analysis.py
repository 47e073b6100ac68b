"""Pre-flight pipeline analyzer (keystone_tpu/analysis).

Three suites:

- **false-positive gate**: every bundled pipeline (all 8 apps, built
  over tiny synthetic data) analyzes to ZERO findings, and the solver
  precision lint is clean under every KEYSTONE_MATMUL mode — the
  analyzer is only trustworthy if a clean pipeline stays clean;
- **seeded-defect corpus**: at least one planted bug per pass (a–d) is
  caught — mis-shaped stage, host-stream mis-wiring, f64 downcast,
  bf16 leaking into a 'solver', unknown fault site, infeasible
  deadline, breaker-without-fallback, signature collision, dataset
  name collision, unfitted-estimator apply;
- **wiring**: Pipeline.fit(validate=)/KEYSTONE_VALIDATE, freeze
  validation, the cli `check` subcommand, the DOT findings overlay,
  and the inertness of the default path.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.analysis import (
    AnalysisReport,
    Finding,
    PipelineValidationError,
    analyze,
    check_fn,
)
from keystone_tpu.analysis import precision as precision_pass
from keystone_tpu.analysis.bundled import BUNDLED, build_bundled
from keystone_tpu.workflow import Dataset, Pipeline
from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.transformer import Transformer


class Scale(Transformer):
    """Minimal well-behaved device transformer for fixtures."""

    def __init__(self, k: float):
        self.k = float(k)

    def params(self):
        return (self.k,)

    def apply_batch(self, xs, mask=None):
        return xs * self.k


class FixedDot(Transformer):
    """Multiplies by a fixed (d, d) matrix — mis-shaped inputs fail."""

    def __init__(self, d: int):
        self.d = d
        self.w = jnp.eye(d, dtype=jnp.float32)

    def params(self):
        return (self.d,)

    def apply_batch(self, xs, mask=None):
        return xs @ self.w


# ------------------------------------------------------ false-positive gate
@pytest.mark.parametrize("name", BUNDLED)
def test_bundled_pipeline_zero_findings(name):
    pipe, example = build_bundled(name)
    report = analyze(pipe, example=example)
    assert not report.findings, report.render()


def test_solver_precision_lint_clean_all_modes():
    """Pass (b) over every registered solver entry under every
    KEYSTONE_MATMUL mode (bf16_apply force-resolved): the PR-2
    byte-identity pins, generalized to a checker, hold for every
    solver."""
    findings = precision_pass.run()
    assert not findings, "\n".join(f.render() for f in findings)


def test_solver_registry_covers_every_family():
    names = {n for n, _ in precision_pass.SOLVER_ENTRIES}
    assert {
        "lbfgs.dense",
        "lbfgs.sparse",
        "block_ls",
        "block_weighted_ls",
        "kernel_ridge",
    } <= names


# ------------------------------------------------- pass (a): shapes/dtypes
def test_shape_mismatch_detected():
    pipe = Pipeline.of(Scale(2.0)).and_then(FixedDot(8))
    report = analyze(pipe, example=np.zeros((4, 12), np.float32))
    assert [f.code for f in report.errors] == ["shape-mismatch"]
    f = report.errors[0]
    assert f.pass_id == "shapes" and f.node is not None
    assert f.label == "FixedDot"


def test_clean_pipeline_no_findings():
    pipe = Pipeline.of(Scale(2.0)).and_then(FixedDot(8))
    report = analyze(pipe, example=np.zeros((4, 8), np.float32))
    assert not report.findings, report.render()


def test_untraceable_stage_is_not_a_false_positive():
    """Tracer/concretization errors mention 'shape' too — they must
    classify as untraceable (UNKNOWN), not shape-mismatch: the runtime
    executes these stages on the unjitted fallback, so refusing them
    would break the zero-false-positive contract (review finding)."""

    class DataDependent(Transformer):
        def params(self):
            return ()

        def apply_batch(self, xs, mask=None):
            if float(np.asarray(jnp.sum(xs))) > 0:  # concretizes a tracer
                return xs
            return -xs

    class HostNumpy(Transformer):
        def params(self):
            return ()

        def apply_batch(self, xs, mask=None):
            return jnp.asarray(np.asarray(xs) * 2.0)

    for t in (DataDependent(), HostNumpy()):
        pipe = Pipeline.of(t).and_then(Scale(1.0))
        report = analyze(pipe, example=np.zeros((4, 8), np.float32))
        assert not report.findings, report.render()
    # ...and the stages really do run on the eager fallback
    out = DataDependent()(
        Dataset(np.ones((4, 8), np.float32), shard=False)
    )
    assert out.numpy().shape == (4, 8)


def test_f64_input_downcast_warning():
    pipe = Pipeline.of(Scale(2.0))
    report = analyze(pipe, example=np.zeros((4, 8), np.float64))
    codes = {f.code for f in report.warnings}
    assert "dtype-downcast" in codes
    assert not report.errors  # a downcast warns, it does not refuse


def test_f64_datum_literal_downcast_warning():
    # a raw f64 datum bound into the graph (Dataset literals convert at
    # construction, so the datum path is where the analyzer can still
    # see the original dtype)
    lazy = Pipeline.of(Scale(1.0)).apply_datum(np.zeros(4, np.float64))
    report = analyze(lazy)
    assert any(f.code == "dtype-downcast" for f in report.warnings)


def test_host_stream_into_device_stage_is_error():
    from keystone_tpu.workflow.dataset import StreamDataset

    stream = StreamDataset(lambda: iter([["a", "b"]]), n=2, host=True)
    g = G.Graph()
    g, src = g.add_source()
    g, dsn = g.add_node(G.DatasetOperator(stream), ())
    g, t = g.add_node(G.TransformerOperator(Scale(1.0)), (dsn,))
    g, sink = g.add_sink(t)
    report = analyze(Pipeline(g, src, sink))
    assert [f.code for f in report.errors] == ["host-stream-device-stage"]


def test_unfitted_estimator_reference_detected():
    """A DelegatingOperator whose dep 0 is not an estimator output —
    the executor would raise TypeError at run time, possibly hours in."""
    data = Dataset(np.zeros((4, 3), np.float32), shard=False)
    g = G.Graph()
    g, src = g.add_source()
    g, dsn = g.add_node(G.DatasetOperator(data), ())
    g, dlg = g.add_node(G.DelegatingOperator(), (dsn, src))
    g, sink = g.add_sink(dlg)
    report = analyze(Pipeline(g, src, sink))
    assert "bad-delegate" in {f.code for f in report.errors}


def test_gather_mismatch_detected():
    class Widen(Transformer):
        def __init__(self, extra):
            self.extra = extra

        def params(self):
            return (self.extra,)

        def apply_batch(self, xs, mask=None):
            # reshapes the batch axis — branches disagree beyond features
            return jnp.repeat(xs, self.extra, axis=0)

    pipe = Pipeline.gather([Scale(1.0), Widen(2)])
    report = analyze(pipe, example=np.zeros((4, 8), np.float32))
    assert "gather-mismatch" in {f.code for f in report.errors}


def test_unfitted_estimator_is_error_in_apply_mode():
    from keystone_tpu.models import LinearMapEstimator

    data = Dataset(np.zeros((8, 4), np.float32), shard=False)
    labels = Dataset(np.ones((8, 2), np.float32), shard=False)
    pipe = Pipeline.of(Scale(1.0)).and_then(
        LinearMapEstimator(lam=0.1), data, labels
    )
    assert analyze(pipe, mode="fit").ok
    report = analyze(pipe, mode="apply")
    assert "unfitted-estimator" in {f.code for f in report.errors}


def test_kernel_mapper_shape_mismatch_detected():
    """The kernel-tier shapes case (ISSUE 13): a fitted kernel mapper
    whose input feature dim disagrees with its train rows fails
    pre-flight with a kernel-specific finding, not mid-sweep."""
    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        KernelBlockLinearMapper,
    )

    kern = GaussianKernelGenerator(0.1)
    tx = jnp.zeros((64, 8), jnp.float32)
    m = KernelBlockLinearMapper(kern, tx, jnp.zeros((64, 3)), 16, 64)
    rep = analyze(Pipeline.of(m), example=np.zeros((4, 8), np.float32))
    assert not rep.findings, rep.render()
    rep = analyze(Pipeline.of(m), example=np.zeros((4, 9), np.float32))
    assert [f.code for f in rep.errors] == ["kernel-shape-mismatch"]


def test_kernel_mapper_bad_state_detected():
    """Misshaped fitted kernel state (α rows vs train rows) is the
    explode-mid-sweep class the explicit case exists for."""
    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        KernelBlockLinearMapper,
    )

    m = KernelBlockLinearMapper(
        GaussianKernelGenerator(0.1),
        jnp.zeros((64, 8), jnp.float32),
        jnp.zeros((48, 3)),  # 48 α rows against 64 train rows
        16,
        64,
    )
    rep = analyze(Pipeline.of(m), example=np.zeros((4, 8), np.float32))
    assert [f.code for f in rep.errors] == ["kernel-bad-state"]


def test_oc_kernel_mapper_checked_without_reading_blocks(tmp_path):
    """The out-of-core mapper is validated from its store's METADATA
    alone (analysis must never stream train blocks off disk), and a
    missing backing store is a pre-flight error."""
    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        OutOfCoreKernelBlockLinearMapper,
    )
    from keystone_tpu.workflow.blockstore import RowBlockStore

    store = RowBlockStore.from_array(
        str(tmp_path / "s"), np.zeros((64, 8), np.float32), 16
    )
    m = OutOfCoreKernelBlockLinearMapper(
        GaussianKernelGenerator(0.1), store.directory,
        jnp.zeros((64, 3)), 64,
    )
    from keystone_tpu.obs import metrics

    reads0 = metrics.REGISTRY.counter_value("blockstore.reads") or 0
    rep = analyze(Pipeline.of(m), example=np.zeros((4, 8), np.float32))
    assert not rep.findings, rep.render()
    assert (metrics.REGISTRY.counter_value("blockstore.reads") or 0) == reads0
    rep = analyze(Pipeline.of(m), example=np.zeros((4, 9), np.float32))
    assert [f.code for f in rep.errors] == ["kernel-shape-mismatch"]

    gone = OutOfCoreKernelBlockLinearMapper(
        GaussianKernelGenerator(0.1), str(tmp_path / "missing"),
        jnp.zeros((64, 3)), 64,
    )
    rep = analyze(Pipeline.of(gone), example=np.zeros((4, 8), np.float32))
    assert [f.code for f in rep.errors] == ["kernel-bad-state"]


def test_degenerate_kernel_generator_detected():
    """γ ≤ 0 / NaN on an UNFITTED kernel estimator fails pre-flight —
    exp(0)=1 everywhere converges to garbage silently otherwise."""
    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        KernelRidgeRegressionEstimator,
    )
    from keystone_tpu.models.nystrom import NystromFeatures

    for bad_gamma in (0.0, float("nan")):
        est = KernelRidgeRegressionEstimator(
            GaussianKernelGenerator(bad_gamma)
        )
        pipe = Pipeline.from_estimator(
            est,
            Dataset(np.zeros((8, 4), np.float32)),
            Dataset(np.zeros((8, 2), np.float32)),
        )
        rep = analyze(pipe, example=np.zeros((4, 4), np.float32))
        assert "bad-kernel-generator" in [f.code for f in rep.errors]

    nys = NystromFeatures(GaussianKernelGenerator(-1.0), 8)
    pipe = Pipeline.from_estimator(
        nys, Dataset(np.zeros((8, 4), np.float32))
    )
    rep = analyze(pipe, example=np.zeros((4, 4), np.float32))
    assert "bad-kernel-generator" in [f.code for f in rep.errors]


# --------------------------------------------------- pass (b): precision
def test_planted_bf16_solver_is_flagged():
    def bad(a, b):
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))

    avals = (jax.ShapeDtypeStruct((4, 4), np.float32),) * 2
    codes = [f.code for f in check_fn(bad, *avals, name="planted")]
    assert "bf16-solver-input" in codes
    assert "non-f32-accumulation" in codes  # bf16 output too


def test_apply_policy_leak_into_solver_is_flagged():
    """The exact defect class the pass exists for: someone routes the
    apply-side bf16 helpers into solver math; under bf16_apply (forced
    on CPU) the leak is visible in the jaxpr."""
    from keystone_tpu.utils import precision as prec

    def leaky_solver(a, b):
        return prec.apply_dot(a, b)

    avals = (jax.ShapeDtypeStruct((4, 4), np.float32),) * 2
    with prec.matmul("bf16_apply"), prec.force_bf16_apply():
        findings = check_fn(leaky_solver, *avals, name="leaky")
    assert [f.code for f in findings] == ["bf16-solver-input"]
    # ...and the same function is clean when the policy is inert,
    # which is why the sweep must force-resolve bf16_apply
    with prec.matmul("f32"):
        assert not check_fn(leaky_solver, *avals, name="leaky")


def test_checker_recurses_into_scan():
    def scanned(a, b):
        def step(c, _):
            return c @ b.astype(jnp.bfloat16).astype(jnp.float32) @ jnp.eye(
                4, dtype=jnp.bfloat16
            ), None

        out, _ = jax.lax.scan(step, a, None, length=2)
        return out

    avals = (jax.ShapeDtypeStruct((4, 4), np.float32),) * 2
    assert any(
        f.code == "bf16-solver-input"
        for f in check_fn(scanned, *avals, name="scan")
    )


# -------------------------------------------------- pass (c): robustness
def test_unknown_fault_site_in_env_plan(monkeypatch):
    monkeypatch.setenv("KEYSTONE_FAULTS", "bogus.site:raise")
    report = analyze(Pipeline.of(Scale(1.0)))
    assert [f.code for f in report.errors] == ["bad-fault-plan"]
    assert "bogus.site" in report.errors[0].message


def test_valid_fault_plan_is_clean(monkeypatch):
    monkeypatch.setenv("KEYSTONE_FAULTS", "executor.stage:times=0")
    assert analyze(Pipeline.of(Scale(1.0))).ok


def test_mandatory_stage_under_breaker_warns(monkeypatch):
    monkeypatch.setenv("KEYSTONE_BREAKER_THRESHOLD", "2")
    report = analyze(Pipeline.of(Scale(1.0)))
    assert [f.code for f in report.warnings] == ["mandatory-under-breaker"]
    # a pipeline whose stages all degrade is clean under breakers
    report = analyze(Pipeline.of(Scale(1.0).with_fallback(Scale(0.0))))
    assert not report.findings, report.render()


def test_infeasible_deadline_warns():
    pipe, example = build_bundled("MnistRandomFFT")
    report = analyze(pipe, example=example, deadline=1e-6)
    assert "deadline-infeasible" in {f.code for f in report.warnings}
    # errors stay empty: an infeasible budget is a configuration smell,
    # not a refusal
    assert not report.errors


# -------------------------------------------------- pass (d): signatures
class UnderSpecified(Transformer):
    """params() omits ``k`` — the planted collision."""

    def __init__(self, k: float):
        self.k = float(k)

    def params(self):
        return ("underspecified",)

    def apply_batch(self, xs, mask=None):
        return xs * self.k


def test_signature_collision_detected():
    pipe = Pipeline.gather([UnderSpecified(1.0), UnderSpecified(2.0)])
    report = analyze(pipe, example=np.zeros((4, 8), np.float32))
    errs = [f for f in report.errors if f.code == "signature-collision"]
    assert errs and "'k'" in errs[0].message


def test_equal_state_instances_do_not_collide():
    pipe = Pipeline.gather([UnderSpecified(1.0), UnderSpecified(1.0)])
    report = analyze(pipe, example=np.zeros((4, 8), np.float32))
    assert not report.findings, report.render()


def test_array_valued_collision_detected():
    class ArrayParam(Transformer):
        def __init__(self, seed):
            self.w = jnp.asarray(
                np.random.RandomState(seed).randn(4).astype(np.float32)
            )

        def params(self):
            return ("arrayparam",)  # omits w

        def apply_batch(self, xs, mask=None):
            return xs * self.w

    pipe = Pipeline.gather([ArrayParam(0), ArrayParam(1)])
    report = analyze(pipe, example=np.zeros((4, 4), np.float32))
    assert "signature-collision" in {f.code for f in report.errors}


@pytest.mark.parametrize("case", ["same-recipe-twice", "recipe-vs-reassigned"])
def test_recipe_signed_nodes_are_stable_and_never_collide(case):
    """A node that signs with the recipe of its seeded draw
    (``utils/hashing.py``) under the pass's determinism and collision
    checks: the same recipe twice is one stable identity over equal
    state; a twin whose weights were reassigned signs by content, so the
    two differ and nothing is there to collide."""
    from keystone_tpu.analysis.signatures import collision_signatures
    from keystone_tpu.ops import CosineRandomFeatures

    a = CosineRandomFeatures.init(8, 4, gamma=0.5, seed=3)
    b = CosineRandomFeatures.init(8, 4, gamma=0.5, seed=3)
    if case == "recipe-vs-reassigned":
        b.w = b.w + 1.0
    assert a.signature() == a.signature() and b.signature() == b.signature()
    assert (a.signature() == b.signature()) is (case == "same-recipe-twice")
    pipe = Pipeline.gather([a, b])
    report = analyze(pipe, example=np.zeros((4, 8), np.float32))
    assert not report.findings, report.render()
    assert not collision_signatures(pipe.graph)


def test_dataset_name_collision_detected():
    from keystone_tpu.models import LinearMapEstimator

    d1 = Dataset(np.zeros((8, 4), np.float32), shard=False, name="train")
    d2 = Dataset(np.zeros((6, 4), np.float32), shard=False, name="train")
    labels = Dataset(np.ones((8, 2), np.float32), shard=False)
    l2 = Dataset(np.ones((6, 2), np.float32), shard=False)
    pipe = Pipeline.gather(
        [
            Pipeline.of(Scale(1.0)).and_then(
                LinearMapEstimator(lam=0.1), d1, labels
            ),
            Pipeline.of(Scale(2.0)).and_then(
                LinearMapEstimator(lam=0.2), d2, l2
            ),
        ]
    )
    report = analyze(pipe)
    assert "dataset-name-collision" in {f.code for f in report.errors}


def test_unstable_signature_detected():
    import itertools

    counter = itertools.count()

    class Unstable(Transformer):
        def params(self):
            return (next(counter),)

        def apply_batch(self, xs, mask=None):
            return xs

    report = analyze(Pipeline.of(Unstable()))
    assert "unstable-signature" in {f.code for f in report.errors}


# ----------------------------------------------------------- report schema
def test_report_render_and_dict():
    rep = AnalysisReport(
        [
            Finding("warning", "shapes", "dtype-downcast", "w", node=3, label="X"),
            Finding("error", "shapes", "shape-mismatch", "boom", node=5, label="Y"),
        ]
    )
    text = rep.render()
    # errors render first, with graph locations
    assert text.splitlines()[0].startswith("ERROR")
    assert "n5[Y]" in text and "n3[X]" in text
    d = rep.to_dict()
    assert d["errors"] == 1 and d["warnings"] == 1
    with pytest.raises(PipelineValidationError) as ei:
        rep.raise_for_errors()
    assert ei.value.report is rep


# ----------------------------------------------------------------- wiring
def _broken_fit_pipeline():
    """Estimator branch whose featurizer cannot accept the bound data."""
    from keystone_tpu.models import LinearMapEstimator

    data = Dataset(np.zeros((8, 12), np.float32), shard=False)
    labels = Dataset(np.ones((8, 2), np.float32), shard=False)
    return Pipeline.of(FixedDot(8)).and_then(
        LinearMapEstimator(lam=0.1), data, labels
    )


def test_fit_validate_refuses_broken_pipeline():
    with pytest.raises(PipelineValidationError) as ei:
        _broken_fit_pipeline().fit(validate=True)
    assert "shape-mismatch" in str(ei.value)


def test_fit_validate_env_gate(monkeypatch):
    monkeypatch.setenv("KEYSTONE_VALIDATE", "1")
    with pytest.raises(PipelineValidationError):
        _broken_fit_pipeline().fit()
    # explicit validate=False overrides the env (and the fit then fails
    # at device time instead — not exercised here)
    monkeypatch.setenv("KEYSTONE_VALIDATE", "0")
    with pytest.raises(PipelineValidationError):
        _broken_fit_pipeline().fit(validate=True)


def test_fit_validate_passes_clean_pipeline():
    from keystone_tpu.models import LinearMapEstimator

    data = Dataset(np.random.RandomState(0).randn(16, 4).astype(np.float32))
    labels = Dataset(np.ones((16, 2), np.float32))
    pipe = Pipeline.of(Scale(1.0)).and_then(
        LinearMapEstimator(lam=0.1), data, labels
    )
    fitted = pipe.fit(validate=True)
    out = fitted(np.zeros((4, 4), np.float32)).get()
    assert out.numpy().shape == (4, 2)
    # freeze validation accepts the fitted pipeline too
    applier = fitted.freeze(validate=True, example=(4,))
    assert applier(np.zeros((4, 4), np.float32)).numpy().shape == (4, 2)


def test_freeze_validate_flags_mis_shaped_example():
    fitted = Pipeline.of(FixedDot(8)).fit(validate=True)
    with pytest.raises(PipelineValidationError):
        fitted.freeze(validate=True, example=(12,))
    assert fitted.freeze(validate=True, example=(8,)) is not None


def test_cli_check_bundled(tmp_path, capsys):
    from keystone_tpu import cli

    dot = tmp_path / "graph.dot"
    rc = cli.main(
        ["check", "MnistRandomFFT", "--no-solver-lint", "--dot", str(dot)]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "no findings" in out
    assert dot.exists() and "digraph" in dot.read_text()


def test_cli_check_saved_model_roundtrip(tmp_path, capsys):
    from keystone_tpu import cli

    fitted = Pipeline.of(FixedDot(8)).fit()
    path = tmp_path / "model.pkl"
    fitted.save(str(path))
    assert cli.main(["check", "--model", str(path), "--no-solver-lint",
                     "--example-shape", "8"]) == 0
    capsys.readouterr()
    # a mis-shaped example spec makes the same model fail the check
    rc = cli.main(["check", "--model", str(path), "--no-solver-lint",
                   "--example-shape", "12"])
    out = capsys.readouterr().out
    assert rc == 1 and "shape-mismatch" in out


def test_cli_check_unknown_name():
    from keystone_tpu import cli

    assert cli.main(["check", "NoSuchPipeline", "--no-solver-lint"]) == 2


def test_to_dot_findings_overlay():
    pipe = Pipeline.of(Scale(2.0)).and_then(FixedDot(8))
    report = analyze(pipe, example=np.zeros((4, 12), np.float32))
    dot = pipe.to_dot(findings=report.findings)
    assert "#ff9999" in dot and "shape-mismatch" in dot
    # graph-level findings render as a note node
    dot2 = pipe.to_dot(
        findings=[Finding("warning", "robustness", "bad-fault-plan", "m")]
    )
    assert "analysis_findings" in dot2 and "#ffe680" in dot2


def test_default_fit_path_stays_inert(monkeypatch):
    """validate off (the default): fit never imports the analysis
    package — the solver byte-identity pins ride on this."""
    import sys

    from keystone_tpu.models import LinearMapEstimator

    for mod in [m for m in sys.modules if m.startswith("keystone_tpu.analysis")]:
        monkeypatch.delitem(sys.modules, mod, raising=False)
    monkeypatch.delenv("KEYSTONE_VALIDATE", raising=False)
    data = Dataset(np.random.RandomState(0).randn(16, 4).astype(np.float32))
    labels = Dataset(np.ones((16, 2), np.float32))
    Pipeline.of(Scale(1.0)).and_then(
        LinearMapEstimator(lam=0.1), data, labels
    ).fit().freeze()
    assert not any(
        m.startswith("keystone_tpu.analysis") for m in sys.modules
    )


# -------------------------------------------------------------- satellites
def test_inject_rejects_unknown_site_plan_object():
    from keystone_tpu import faults

    plan = faults.FaultPlan([faults.SiteSpec("typo.site")])
    with pytest.raises(faults.UnknownFaultSiteError) as ei:
        with faults.inject(plan):
            pass
    assert "typo.site" in str(ei.value)
    assert "executor.stage" in str(ei.value)  # lists the registered sites
    assert isinstance(ei.value, faults.FaultPlanError)  # typed subclass


def test_parse_plan_unknown_site_typed_error():
    from keystone_tpu import faults

    with pytest.raises(faults.UnknownFaultSiteError):
        faults.parse_plan("bogus.site:raise")


def test_metric_kind_conflict_rejected():
    from keystone_tpu.obs.metrics import MetricKindError, MetricsRegistry

    r = MetricsRegistry()
    r.inc("a.b", site="x")
    with pytest.raises(MetricKindError) as ei:
        r.set_gauge("a.b", 1.0)
    assert "counter" in str(ei.value) and "gauge" in str(ei.value)
    with pytest.raises(MetricKindError):
        r.observe("a.b", 0.5)
    # same kind, any labels: fine; reset clears the kind registry
    r.inc("a.b", site="y")
    r.reset()
    r.set_gauge("a.b", 1.0)
    assert r.gauge_value("a.b") == 1.0


def test_metric_kind_gauge_family_is_one_kind():
    from keystone_tpu.obs.metrics import MetricsRegistry

    r = MetricsRegistry()
    r.set_gauge("g.x", 1.0, key="a")
    r.gauge_max("g.x", 5.0, key="a")  # watermark and set share the kind
    r.remove_gauge("g.x", key="a")
    assert r.gauge_value("g.x", key="a") is None
