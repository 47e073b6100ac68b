"""Compiled-HLO sharding-semantics gate (round-3 review missing-2).

The numerics gates (dryrun mesh-sweep parity, multihost tests) cannot
distinguish a correctly sharded program from one that silently fell back
to full replication — on parity shapes both produce identical numbers.
This gate pins the SCALING claim itself, on the 8-device CPU mesh, by
inspecting the SPMD-partitioned executables of the four hot programs
(the reference's per-partition-gemm + treeReduce semantics, SURVEY.md
§3.2: collectives carry *small* Gramians/gradients/moments, never the
feature matrix):

  - ``models/block_ls.py § _bcd_fit``          (dense BCD hot loop)
  - ``models/block_ls.py § _oc_block_step``    (out-of-core BCD step)
  - ``models/lbfgs.py § _lbfgs_sparse_least_squares`` (sparse L-BFGS)
  - ``models/gmm.py § _gmm_fit``               (GMM fit: seeding + EM)

Assertions per program:

  1. every row-dimensioned input is sharded 1/n_data over 'data'
     (per-device shard shape from the compiled input shardings);
  2. at least one all-reduce exists (the treeReduce analogue);
  3. NO collective's output is O(n): every all-reduce/all-gather/
     reduce-scatter/all-to-all result has fewer elements than the
     global row count — test shapes are chosen so every legitimate
     collective payload (Gramian bs², weights bs·k, moments K·d) is
     far below n, while a gathered feature/residual operand is far
     above it.

The gate is proven live by mutation (`test_gate_detects_dropped_
constraints`): re-jitting the same program with ``constrain`` degraded
to full replication must trip the gate.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.parallel.mesh import DATA_AXIS

# collective HLO opcodes whose payload size we police.  collective-permute
# is included: a point-to-point reshard of the feature operand is just as
# much a scaling bug as a gather of it.  The opcode must be followed by
# '(' (instruction position) — operand references are %names ('%all-
# reduce.12)') and never match.
_OP_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"\b(?:f|s|u|bf|pred|c)\d*\[([\d,]*)\]")


def _collective_lines(hlo_text):
    """(line, result_elems) for every collective instruction.

    HLO instructions read ``%name = <result-shape(s)> opcode(operands)``;
    the result shape — plain ``f32[16,4]{0,1}`` or a tuple
    ``(f32[16,16]{1,0}, f32[16,2]{0,1})`` — sits BETWEEN '=' and the
    opcode.  Parsing is self-checked by the caller: a collective line on
    which no shape parses is an error, not a silent skip."""
    out = []
    for ln in hlo_text.splitlines():
        m = _OP_RE.search(ln)
        if not m:
            continue
        eq = ln.find("=")
        if eq < 0 or eq > m.start():
            continue  # not an instruction definition
        shapes_txt = ln[eq + 1 : m.start()]
        elems = []
        for sm in _SHAPE_RE.finditer(shapes_txt):
            dims = sm.group(1)
            elems.append(
                int(np.prod([int(d) for d in dims.split(",")]))
                if dims
                else 1
            )
        assert elems, (
            "collective line with no parseable result shape — the gate's "
            f"HLO parser needs updating:\n{ln.strip()[:300]}"
        )
        out.append((ln, elems))
    return out


def _assert_gate(compiled, args, n_global, label):
    """The three assertions above, against one compiled executable."""
    txt = compiled.as_text()
    coll = _collective_lines(txt)

    # (2) the treeReduce analogue must exist
    assert any(
        "all-reduce" in ln or "reduce-scatter" in ln for ln, _ in coll
    ), f"{label}: no all-reduce in compiled HLO — program is not aggregating over 'data'"

    # (3) no O(n) collective payloads
    for ln, elems_list in coll:
        for elems in elems_list:
            assert elems < n_global, (
                f"{label}: collective with {elems} >= n={n_global} result "
                f"elements — a feature/residual-sized operand is crossing "
                f"the interconnect:\n{ln.strip()[:300]}"
            )

    # (1) row-dimensioned inputs are sharded 1/n_data on 'data'
    from keystone_tpu.parallel import mesh as _mesh

    mesh = _mesh.current_mesh()
    dsize = mesh.shape[DATA_AXIS]
    leaves = jax.tree_util.tree_leaves(args)
    shardings = jax.tree_util.tree_leaves(compiled.input_shardings[0])
    assert len(leaves) == len(shardings), (
        f"{label}: {len(leaves)} arg leaves vs {len(shardings)} compiled "
        "input shardings — pass the program's FULL runtime argument list"
    )
    checked = 0
    for leaf, sh in zip(leaves, shardings):
        shape = np.shape(leaf)
        if not shape or n_global not in shape:
            continue
        ax = shape.index(n_global)
        shard = sh.shard_shape(shape)
        assert shard[ax] == n_global // dsize, (
            f"{label}: row-dimensioned input {shape} has per-device shard "
            f"{shard} — axis {ax} is not 1/{dsize} over 'data' (silent "
            f"replication fallback)"
        )
        checked += 1
    assert checked > 0, f"{label}: no row-dimensioned input found to check"
    return txt


# test shapes: n >> every legitimate collective payload (bs², bs·k, K·d,
# d·k) so assertion (3) has wide separation in both directions
_N = 512


def test_bcd_fit_stays_sharded(mesh):
    from keystone_tpu.models.block_ls import _bcd_fit

    rng = np.random.default_rng(0)
    nb, bs, k = 2, 16, 4
    # the matrix as it arrives (PR 32: the program blocks and centres it
    # itself), the second block four columns short
    x = jnp.asarray(rng.normal(size=(_N, nb * bs - 4)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(_N, k)).astype(np.float32))
    compiled = _bcd_fit.lower(x, y, _N, 1e-3, 2, bs, True).compile()
    _assert_gate(compiled, (x, y, _N, 1e-3), _N, "_bcd_fit")


def test_oc_block_step_stays_sharded(mesh):
    from keystone_tpu.models.block_ls import _oc_block_step

    rng = np.random.default_rng(1)
    bs, k = 16, 4
    a_raw = jnp.asarray(rng.normal(size=(_N, bs)).astype(np.float32))
    xm_b = jnp.zeros((bs,), jnp.float32)
    yc = jnp.asarray(rng.normal(size=(_N, k)).astype(np.float32))
    sa = jnp.ones((_N,), jnp.float32)
    row_ok = jnp.ones((_N,), jnp.float32)
    p = jnp.zeros((_N, k), jnp.float32)
    wb = jnp.zeros((bs, k), jnp.float32)
    args = (a_raw, xm_b, yc, sa, row_ok, p, wb, jnp.float32(1e-2))
    compiled = _oc_block_step.lower(*args).compile()
    _assert_gate(compiled, args, _N, "_oc_block_step")


def test_sparse_lbfgs_stays_sharded(mesh):
    from keystone_tpu.models.lbfgs import _lbfgs_sparse_least_squares

    rng = np.random.default_rng(2)
    nnz, d, k = 8, 64, 4
    bidx = (jnp.asarray(rng.integers(0, d, size=(_N, nnz)).astype(np.int32)),)
    bvals = (jnp.asarray(rng.normal(size=(_N, nnz)).astype(np.float32)),)
    by = (jnp.asarray(rng.normal(size=(_N, k)).astype(np.float32)),)
    compiled = _lbfgs_sparse_least_squares.lower(
        bidx, bvals, by, jnp.float32(_N), d, 1e-3, 3, 4, False
    ).compile()
    _assert_gate(
        compiled,
        (bidx, bvals, by, jnp.float32(_N), 1e-3),
        _N,
        "_lbfgs_sparse_least_squares",
    )


def test_gmm_em_stays_sharded(mesh):
    # gate _gmm_fit, the jitted program actually executed: the inner
    # _em_steps relies on _gmm_fit's constrain for its sharding (compiled
    # standalone with replicated args it is legitimately unsharded)
    from keystone_tpu.models.gmm import _gmm_fit

    rng = np.random.default_rng(3)
    K, d = 8, 16
    x = jnp.asarray(rng.normal(size=(_N, d)).astype(np.float32))
    row_ok = jnp.ones((_N,), jnp.float32)
    compiled = _gmm_fit.lower(
        x, jnp.float32(_N), row_ok, K, 2, 1e-4, 0, 2
    ).compile()
    _assert_gate(
        compiled,
        (x, jnp.float32(_N), row_ok, 1e-4, 0),
        _N,
        "_gmm_fit",
    )


def test_gate_detects_dropped_constraints(mesh, monkeypatch):
    """Mutation proof: the SAME program re-jitted with ``constrain``
    degraded to full replication must TRIP the gate — otherwise the gate
    could not protect against a dropped with_sharding_constraint."""
    import keystone_tpu.models.block_ls as bls

    def replicate(x, *spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())
        )

    monkeypatch.setattr(bls, "constrain", replicate)
    # a NEW function identity wrapping the unjitted body: jax's jaxpr
    # cache is keyed on the underlying callable, so re-jitting
    # __wrapped__ directly would silently reuse the UNMUTATED trace
    # when the clean test compiled the same shapes first
    mutated = jax.jit(
        lambda x, y, n, lam, num_iter, block_size, fit_intercept: bls._bcd_fit.__wrapped__(
            x, y, n, lam, num_iter, block_size, fit_intercept
        ),
        static_argnames=("num_iter", "block_size", "fit_intercept"),
    )
    rng = np.random.default_rng(0)
    nb, bs, k = 2, 16, 4
    x = jnp.asarray(rng.normal(size=(_N, nb * bs)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(_N, k)).astype(np.float32))
    compiled = mutated.lower(x, y, _N, 1e-3, 2, bs, True).compile()
    with pytest.raises(AssertionError, match="all-reduce|replication"):
        _assert_gate(compiled, (x, y, _N, 1e-3), _N, "_bcd_fit[mutated]")


def test_shared_traced_param_apply_stays_sharded(mesh):
    """r5: the class-shared traced-parameter apply programs (scoring
    path — Transformer.traced_attrs) must keep the batch axis sharded
    and must introduce NO collectives: parameters ride as (replicated)
    arguments now, and a silent replication fallback or an inserted
    gather here would materialize the full feature matrix per device."""
    import importlib

    from keystone_tpu.models.pca import PCATransformer
    from keystone_tpu.parallel.mesh import shard_batch

    T = importlib.import_module("keystone_tpu.workflow.transformer")
    rng = np.random.default_rng(5)
    d, k = 16, 4
    comp = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
    p = PCATransformer(comp, None)
    x = shard_batch(rng.normal(size=(_N, d)).astype(np.float32))
    # drive through the production path so the SHARED wrapper compiles
    out = p._apply_batch_jitted(x, None)
    assert out.shape == (_N, k)
    keys = [
        kk
        for kk in T._SHARED_APPLY_CACHE
        if kk[0][1] is PCATransformer and callable(T._SHARED_APPLY_CACHE[kk])
    ]
    assert keys, "shared apply did not compile"
    # lower the same wrapper at the same signature and gate the HLO
    fn = T._SHARED_APPLY_CACHE[keys[-1]]
    params = {"components": comp, "mean": None}
    compiled = fn.lower(params, x, None).compile()
    txt = compiled.as_text()
    assert not _collective_lines(txt), (
        "shared apply introduced a collective — the per-row map must "
        "stay local to each shard"
    )
    leaves = jax.tree_util.tree_leaves((params, x))
    shardings = jax.tree_util.tree_leaves(compiled.input_shardings[0])
    assert len(leaves) == len(shardings)
    from keystone_tpu.parallel import mesh as _mesh

    dsize = _mesh.current_mesh().shape[DATA_AXIS]
    for leaf, sh in zip(leaves, shardings):
        shape = np.shape(leaf)
        if shape and _N in shape:
            ax = shape.index(_N)
            assert sh.shard_shape(shape)[ax] == _N // dsize, (
                f"batch input {shape} not sharded 1/{dsize} over 'data'"
            )
