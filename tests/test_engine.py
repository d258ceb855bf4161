"""Functional engine API: pytree EngineState + ExecutionConfig + scan.

Covers the acceptance criteria of the engine redesign:
  * ``engine.all_modes`` is ONE jitted ``lax.scan`` program (trace count
    stays 1 across calls; jaxpr contains a scan; dispatch count is 1 per
    full rotation instead of nmodes);
  * ``EngineState`` round-trips through ``jax.tree_util.tree_flatten``;
  * xla vs pallas-interpret parity for nmodes 3..6 (the paper's >4-mode
    claim previously had no test above 4 modes);
  * the deprecated ``MTTKRPExecutor`` shim matches ``mttkrp_ref`` on all
    modes for nmodes 3..6, works from any start mode, and ``reset()``
    restores mode 0 (regression for the removed mode-0 assertion).
"""
import contextlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import (MTTKRPExecutor, build_flycoo, cp_als,
                        cp_als_reference, init_factors, mttkrp_ref)
from repro.core.cpd import _als_fold
from repro.engine import EngineState, ExecutionConfig

DIMS_BY_NMODES = {
    3: (23, 17, 11),
    4: (13, 11, 7, 9),
    5: (9, 8, 7, 6, 5),
    6: (7, 6, 5, 4, 3, 8),
}


def _tensor(seed, dims, nnz, **kw):
    rng = np.random.default_rng(seed)
    idx = np.unique(np.stack([rng.integers(0, d, nnz) for d in dims], 1)
                    .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    return idx, val, build_flycoo(idx, val, dims, **kw)


def _refs(idx, val, factors, dims):
    return [mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors, d,
                       dims[d]) for d in range(len(dims))]


# --------------------------------------------------------------------------
# Backend parity across mode counts (incl. the paper's >4-mode claim).
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas", "pallas_fused", "ref"])
@pytest.mark.parametrize("nmodes", [3, 4, 5, 6])
def test_all_modes_backend_parity(backend, nmodes):
    dims = DIMS_BY_NMODES[nmodes]
    idx, val, t = _tensor(nmodes, dims, 700, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(1), dims, 8))
    state = engine.init(t, ExecutionConfig(backend=backend, interpret=True))
    refs = _refs(idx, val, factors, dims)
    for _ in range(2):  # second sweep exercises remapped layouts
        outs, state = engine.all_modes(state, factors)
        for d in range(nmodes):
            np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4,
                                       atol=2e-4)


@pytest.mark.parametrize("chunk", [1000, 4096])
def test_xla_backend_chunked_reduction(monkeypatch, chunk):
    """Layouts over ``XLA_CHUNK_SLOTS`` are reduced in a loop of chunks
    (the last one shifted back, its overlap masked): same result as the
    oracle, including the remapped second rotation."""
    from repro.engine import backends

    dims = DIMS_BY_NMODES[4]
    idx, val, t = _tensor(11, dims, 6000, rows_pp=4, block_p=16)
    factors = tuple(init_factors(jax.random.PRNGKey(2), dims, 8))
    monkeypatch.setattr(backends, "XLA_CHUNK_SLOTS", chunk)
    monkeypatch.setattr(engine.api, "_JIT_CACHE", {})
    state = engine.init(t, ExecutionConfig(backend="xla"))
    assert state.statics[0].padded_nnz > chunk
    assert state.statics[0].padded_nnz % chunk     # a shifted last chunk
    refs = _refs(idx, val, factors, dims)
    for _ in range(2):
        outs, state = engine.all_modes(state, factors)
        for d in range(len(dims)):
            np.testing.assert_allclose(outs[d], refs[d], rtol=2e-5,
                                       atol=2e-5)


@pytest.mark.parametrize("nmodes", [3, 4, 5, 6])
def test_pallas_fused_any_start_and_step(nmodes):
    """The fused EC+remap pipeline (on the rotating layout) works from any
    resident mode, both as the scanned rotation and stepped one dispatch
    at a time."""
    dims = DIMS_BY_NMODES[nmodes]
    idx, val, t = _tensor(nmodes + 20, dims, 600, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(5), dims, 8))
    refs = _refs(idx, val, factors, dims)
    cfg = ExecutionConfig(backend="pallas_fused", interpret=True)
    for start in (0, nmodes - 1):
        state = engine.init(t, cfg, start_mode=start, _rotating=True)
        outs, state = engine.all_modes(state, factors)
        assert state.mode == start
        for d in range(nmodes):
            np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4,
                                       atol=2e-4)
    state = engine.init(t, cfg, start_mode=1, _rotating=True)
    for i in range(nmodes):
        out, state = engine.mttkrp(state, factors)
        np.testing.assert_allclose(out, refs[(1 + i) % nmodes], rtol=2e-4,
                                   atol=2e-4)


def _poison_pads(state, value):
    """``state`` with ``value`` in every pad slot's val: the pinned pads of
    every mode, or the pads of the resident rotating layout."""
    if state.pinned is not None:
        return state.replace(pinned=tuple(
            p._replace(val=jnp.where(p.lrow < 0, value, p.val))
            for p in state.pinned))
    return state.replace(
        val=jnp.where(state.alpha[:, state.mode] < 0, value, state.val))


@pytest.mark.parametrize("backend,rotating", [
    pytest.param(b, False, id=b) for b in
    ("xla", "pallas", "pallas_fused", "ref")] + [
    pytest.param("pallas_fused", True, id="pallas_fused-rotating")])
def test_pad_slots_cannot_pollute_row_zero(backend, rotating):
    """Pad slots (lrow == -1) are dumped into segment 0 by the XLA
    segment-sum paths and carry in-bounds idx = 0 — so their contribution
    must be masked structurally, not by relying on pad val == 0. Forcing
    every pad val to a nonzero value must leave ALL outputs (in particular
    the user row that relabels to row 0) bit-identical to the oracle."""
    dims = DIMS_BY_NMODES[4]
    idx, val, t = _tensor(8, dims, 500, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(7), dims, 8))
    refs = _refs(idx, val, factors, dims)
    state = engine.init(t, ExecutionConfig(backend=backend, interpret=True),
                        _rotating=rotating)
    assert state.layout == ("pinned" if backend == "pallas_fused"
                            and not rotating else "rotating")
    poisoned = _poison_pads(state, 7.25)
    outs, _ = engine.all_modes(poisoned, factors)
    for d in range(4):
        np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("nmodes", [3, 4, 5, 6])
def test_single_mode_step_and_any_start(nmodes):
    """Stepping through modes one dispatch at a time matches the oracle,
    and a rotation may start anywhere (no mode-0 restriction)."""
    dims = DIMS_BY_NMODES[nmodes]
    idx, val, t = _tensor(nmodes + 10, dims, 500, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(2), dims, 4))
    refs = _refs(idx, val, factors, dims)

    state = engine.init(t)
    for d in range(nmodes):
        out, state = engine.mttkrp(state, factors)
        np.testing.assert_allclose(out, refs[d], rtol=2e-4, atol=2e-4)
    assert state.mode == 0

    start = nmodes - 1
    state = engine.init(t, start_mode=start)
    outs, state = engine.all_modes(state, factors)
    assert state.mode == start
    for d in range(nmodes):
        np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4, atol=2e-4)


def test_mttkrp_rejects_nonresident_mode():
    dims = DIMS_BY_NMODES[3]
    _, _, t = _tensor(0, dims, 300, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(0), dims, 4))
    state = engine.init(t)
    with pytest.raises(ValueError, match="mode-0 layout"):
        engine.mttkrp(state, factors, mode=2)


# --------------------------------------------------------------------------
# Scan program: one trace, one dispatch per rotation, scan in the jaxpr.
# --------------------------------------------------------------------------
def test_all_modes_is_single_scanned_dispatch():
    dims = DIMS_BY_NMODES[4]
    idx, val, t = _tensor(1, dims, 600, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(3), dims, 8))
    state = engine.init(t)

    engine.reset_counters()
    for _ in range(3):
        outs, state = engine.all_modes(state, factors)
    # one traced program, reused; one dispatch per full rotation — the
    # old executor issued nmodes dispatches per rotation.
    assert engine.TRACE_COUNTS["all_modes"] == 1
    assert engine.DISPATCH_COUNTS["all_modes"] == 3

    jaxpr = str(engine.scan_jaxpr(state, factors))
    assert "scan" in jaxpr, "all_modes must lower to a lax.scan program"


# --------------------------------------------------------------------------
# Zero-HBM-intermediate acceptance: the fused scan step materializes no
# (S_d, N-1, R) gathered buffer (the unfused pallas backend does).
# --------------------------------------------------------------------------
def _scan_hlo(t, backend, factors):
    from repro.engine.api import _build_scan, _scan_args

    state = engine.init(t, ExecutionConfig(backend=backend, interpret=True,
                                           donate=False))
    fn = _build_scan(state, None)
    return state, jax.jit(fn).lower(
        *_scan_args(state, factors, None)).as_text()


def test_fused_scan_has_no_gathered_intermediate():
    dims = DIMS_BY_NMODES[4]
    rank = 8
    _, _, t = _tensor(6, dims, 600, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(9), dims, rank))
    nm1 = len(dims) - 1

    state, fused_txt = _scan_hlo(t, "pallas_fused", factors)
    # the pre-gathered kernel operand is lane-dense (N-1, R, S)
    gathered_types = [f"tensor<{nm1}x{rank}x{s.padded_nnz}xf32>"
                      for s in state.statics]
    for ty in gathered_types:
        assert ty not in fused_txt, \
            f"pallas_fused scan step materializes a gathered buffer {ty}"

    # ... while the unfused pallas baseline does stage it through HBM.
    _, base_txt = _scan_hlo(t, "pallas", factors)
    assert any(ty in base_txt for ty in gathered_types), \
        "baseline should show the (N-1, R, S) gathered intermediate"


def test_fuse_remap_knob_and_vmem_budget():
    """fuse_remap=False forces the XLA scatter path on a rotating layout
    (bit-parity with the fused one); vmem_budget_bytes sizes the
    vmem-policy row tiles."""
    dims = DIMS_BY_NMODES[3]
    idx, val, t = _tensor(12, dims, 400, rows_pp=4, block_p=8)
    factors = tuple(init_factors(jax.random.PRNGKey(3), dims, 8))
    outs_f, _ = engine.all_modes(
        engine.init(t, ExecutionConfig(backend="pallas_fused",
                                       interpret=True), _rotating=True),
        factors)
    outs_u, _ = engine.all_modes(
        engine.init(t, ExecutionConfig(backend="pallas_fused",
                                       interpret=True, fuse_remap=False),
                    _rotating=True),
        factors)
    for a, b in zip(outs_f, outs_u):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    # VMEM budget -> rows_pp -> kappa: 64 KiB at R=32 (4 B) halves to 256
    # rows; explicit rows_pp still wins; no budget = library default.
    budget = ExecutionConfig(vmem_budget_bytes=64 * 1024)
    assert budget.resolve_rows_pp() == 256
    assert budget.kappa_for(1000) == 4  # ceil(1000 / 256)
    assert ExecutionConfig(vmem_budget_bytes=64 * 1024,
                           rows_pp=100).resolve_rows_pp() == 100
    assert ExecutionConfig().resolve_rows_pp() is None
    with pytest.raises(ValueError, match="vmem_budget_bytes"):
        ExecutionConfig(vmem_budget_bytes=0)


# --------------------------------------------------------------------------
# Compact block schedule: Zipf parity, bitwise vs rect, padded-slot wins.
# --------------------------------------------------------------------------
def _zipf_tensor(seed, dims, nnz, schedule, a=1.5, **kw):
    from repro.core import datasets

    ts = datasets.TensorSpec(name="zipf", dims=dims, nnz=nnz, zipf_a=a)
    idx, val = datasets.synthesize(ts, seed=seed)
    return idx, val, build_flycoo(idx, val, dims, schedule=schedule, **kw)


@pytest.mark.parametrize("backend", ["xla", "pallas", "pallas_fused", "ref"])
@pytest.mark.parametrize("nmodes", [3, 4, 5, 6])
def test_compact_schedule_zipf_parity(backend, nmodes):
    """Acceptance: on skewed (Zipf) tensors the compact schedule matches
    the COO oracle for every backend across nmodes 3-6, any start mode,
    inside the scanned rotation — and is BITWISE identical to the rect
    baseline (same partitions, same per-partition element order; the pad
    blocks it drops contribute exact zeros)."""
    dims = DIMS_BY_NMODES[nmodes]
    idx, val, t = _zipf_tensor(nmodes, dims, 900, "compact", rows_pp=4,
                               block_p=8)
    _, _, t_rect = _zipf_tensor(nmodes, dims, 900, "rect", rows_pp=4,
                                block_p=8)
    assert sum(p.padded_nnz for p in t.plans) <= \
        sum(p.padded_nnz for p in t_rect.plans)
    factors = tuple(init_factors(jax.random.PRNGKey(2), dims, 8))
    refs = _refs(idx, val, factors, dims)
    start = nmodes - 1
    cfg = ExecutionConfig(backend=backend, interpret=True)
    state = engine.init(t, cfg, start_mode=start)
    state_r = engine.init(t_rect, cfg, start_mode=start)
    for _ in range(2):  # second sweep exercises remapped compact layouts
        outs, state = engine.all_modes(state, factors)
        outs_r, state_r = engine.all_modes(state_r, factors)
        for d in range(nmodes):
            np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4,
                                       atol=2e-4)
            np.testing.assert_array_equal(np.asarray(outs[d]),
                                          np.asarray(outs_r[d]))


def test_compact_reduces_padded_slots_on_skew():
    """On a skewed tensor the compact layout drops most pad blocks; the
    engine's uniform carrier S_max shrinks with it."""
    dims = (96, 64, 48)
    _, _, t = _zipf_tensor(7, dims, 2500, "compact", rows_pp=8, block_p=8)
    _, _, t_rect = _zipf_tensor(7, dims, 2500, "rect", rows_pp=8, block_p=8)
    compact_s = sum(p.padded_nnz for p in t.plans)
    rect_s = sum(p.padded_nnz for p in t_rect.plans)
    assert compact_s * 2 <= rect_s, (compact_s, rect_s)
    assert engine.init(t).smax < engine.init(t_rect).smax


def test_schedule_knob_plumbs_from_raw_coo():
    """ExecutionConfig.schedule governs plans built from raw COO input."""
    dims = (19, 13, 7)
    rng = np.random.default_rng(5)
    idx = np.unique(np.stack([rng.integers(0, d, 300) for d in dims], 1)
                    .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    for sched in ("compact", "rect"):
        state = engine.init((idx, val, dims),
                            ExecutionConfig(schedule=sched, block_p=8))
        assert all(s.schedule == sched for s in state.statics)
    with pytest.raises(ValueError, match="schedule"):
        ExecutionConfig(schedule="bogus")


# --------------------------------------------------------------------------
# Pytree contract.
# --------------------------------------------------------------------------
def test_engine_state_pytree_roundtrip():
    dims = DIMS_BY_NMODES[4]
    idx, val, t = _tensor(2, dims, 400, rows_pp=4, block_p=8)
    state = engine.init(t, ExecutionConfig(backend="xla"))

    leaves, treedef = jax.tree_util.tree_flatten(state)
    assert all(isinstance(x, jax.Array) for x in leaves)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt, EngineState)
    assert rebuilt.aux_key() == state.aux_key()
    for a, b in zip(leaves, jax.tree_util.tree_leaves(rebuilt)):
        np.testing.assert_array_equal(a, b)

    # states pass transparently through jax transformations
    doubled = jax.tree_util.tree_map(lambda x: x * 2, state)
    np.testing.assert_allclose(doubled.val, state.val * 2)
    assert doubled.statics == state.statics


def test_execution_config_static_and_validated():
    assert hash(ExecutionConfig()) == hash(ExecutionConfig())
    assert ExecutionConfig(backend="pallas") != ExecutionConfig()
    with pytest.raises(ValueError, match="kappa_policy"):
        ExecutionConfig(kappa_policy="bogus")
    with pytest.raises(ValueError, match="requires kappa"):
        ExecutionConfig(kappa_policy="fixed")
    with pytest.raises(KeyError, match="unknown engine backend"):
        engine.get_backend("cuda")


def test_kappa_for_rounds_to_device_multiples():
    """One kappa policy for single- and multi-device plans: divisible by
    n_dev, never exceeding the row count unless the mode has fewer rows
    than devices, honoring fixed/vmem policies."""
    cfg = ExecutionConfig(rows_pp=8)
    from repro.core.partition import choose_kappa
    assert cfg.kappa_for(40) == choose_kappa(40, 8)
    for dim in (40, 30, 20, 9):
        for n_dev in (2, 4):
            k = cfg.kappa_for(dim, n_dev)
            assert k % n_dev == 0
            assert n_dev <= k <= dim
    # fixed policy: round the explicit kappa up to the device multiple
    fixed = ExecutionConfig(kappa_policy="fixed", kappa=3)
    assert fixed.kappa_for(100) == 3
    assert fixed.kappa_for(100, 4) == 4
    assert fixed.kappa_for(100, 2) == 4
    # fewer rows than devices: one partition per device, surplus empty
    assert ExecutionConfig().kappa_for(3, 4) == 4
    assert ExecutionConfig().kappa_for(2, 4) == 4


def test_init_from_raw_coo_uses_config_policy():
    dims = (19, 13, 7)
    rng = np.random.default_rng(5)
    idx = np.unique(np.stack([rng.integers(0, d, 300) for d in dims], 1)
                    .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    cfg = ExecutionConfig(kappa_policy="fixed", kappa=2, block_p=8)
    state = engine.init((idx, val, dims), cfg)
    assert all(s.kappa == 2 for s in state.statics)
    factors = tuple(init_factors(jax.random.PRNGKey(0), dims, 4))
    outs, _ = engine.all_modes(state, factors)
    for d in range(3):
        ref = mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors, d,
                         dims[d])
        np.testing.assert_allclose(outs[d], ref, rtol=2e-4, atol=2e-4)


def test_backend_registry_is_extensible():
    name = "_test_zeros"
    try:
        @engine.register_backend(name)
        def _zeros(layout, factors, mode, *, plan, config):
            r = factors[0].shape[1]
            return jnp.zeros((plan.relabeled_rows, r), jnp.float32)

        dims = DIMS_BY_NMODES[3]
        _, _, t = _tensor(4, dims, 200, rows_pp=4, block_p=8)
        factors = tuple(init_factors(jax.random.PRNGKey(0), dims, 4))
        state = engine.init(t, ExecutionConfig(backend=name))
        outs, _ = engine.all_modes(state, factors)
        for o in outs:
            np.testing.assert_array_equal(np.asarray(o), 0.0)
    finally:
        engine.BACKENDS.pop(name, None)


# --------------------------------------------------------------------------
# Deprecated shim: oracle parity 3..6 modes, partial rotation + reset.
# --------------------------------------------------------------------------
@pytest.mark.parametrize("nmodes", [3, 4, 5, 6])
def test_deprecated_shim_matches_oracle(nmodes):
    dims = DIMS_BY_NMODES[nmodes]
    idx, val, t = _tensor(nmodes, dims, 700, rows_pp=4, block_p=8)
    factors = init_factors(jax.random.PRNGKey(1), dims, 8)
    with pytest.deprecated_call():
        exe = MTTKRPExecutor(t)
    outs = exe.all_modes(factors)
    refs = _refs(idx, val, factors, dims)
    for d in range(nmodes):
        np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4, atol=2e-4)


def test_shim_partial_rotation_reset_regression():
    """Step a partial rotation, reset, and match the oracle — the old
    executor hard-asserted ``current_mode == 0`` in all_modes."""
    dims = DIMS_BY_NMODES[4]
    idx, val, t = _tensor(9, dims, 600, rows_pp=4, block_p=8)
    factors = init_factors(jax.random.PRNGKey(4), dims, 8)
    refs = _refs(idx, val, factors, dims)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        exe = MTTKRPExecutor(t)
    np.testing.assert_allclose(exe.step(factors), refs[0], rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(exe.step(factors), refs[1], rtol=2e-4,
                               atol=2e-4)
    assert exe.current_mode == 2

    outs = exe.all_modes(factors)  # mid-rotation: previously an assert
    for d in range(4):
        np.testing.assert_allclose(outs[d], refs[d], rtol=2e-4, atol=2e-4)
    assert exe.current_mode == 2

    exe.reset()
    assert exe.current_mode == 0
    np.testing.assert_allclose(exe.step(factors), refs[0], rtol=2e-4,
                               atol=2e-4)


# --------------------------------------------------------------------------
# CPD on the scanned engine.
# --------------------------------------------------------------------------
def test_cp_als_with_config_matches_reference():
    dims = (24, 18, 12)
    idx, val, t = _tensor(11, dims, 800, rows_pp=8, block_p=16)
    res = cp_als(t, rank=6, iters=4,
                 config=ExecutionConfig(backend="xla"))
    ref = cp_als_reference(idx, val, dims, 6, iters=4)
    assert res.fits == pytest.approx(ref.fits, abs=2e-3)
    with pytest.raises(ValueError, match="not both"):
        cp_als(t, rank=4, iters=1, config=ExecutionConfig(),
               backend="pallas")


# --------------------------------------------------------------------------
# What the sweep program tells about itself: named scopes per mode,
# op_scopes, and the EC kernels' row-copy gauge.
# --------------------------------------------------------------------------
_METADATA = re.compile(r", metadata=\{[^}]*\}")
# the module's source-location tables, each up to its blank line
_SOURCE_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
    re.M | re.S)


def _without_metadata(text: str) -> str:
    return _METADATA.sub("", _SOURCE_TABLES.sub("", text))


def _sweep(backend, seed=21, rotating=False, **cfg):
    dims = DIMS_BY_NMODES[4]
    _, _, t = _tensor(seed, dims, 700, rows_pp=4, block_p=8)
    state = engine.init(t, ExecutionConfig(backend=backend, interpret=True,
                                           donate=False, **cfg),
                        _rotating=rotating)
    factors = tuple(init_factors(jax.random.PRNGKey(4), dims, 8))
    return t, state, factors, jnp.ones((8,), jnp.float32)


def test_sweep_scopes_in_compiled_hlo():
    _, state, factors, lam = _sweep("xla")
    text = engine.scan_hlo(state, factors, fold=_als_fold, carry=lam)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for d in range(state.nmodes):
        for scope in ("ec", "remap", "fold"):
            assert any(f"/mode{d}/{scope}/" in n for n in names), (d, scope)


@pytest.mark.parametrize("backend,fuse_remap,rotating", [
    pytest.param("xla", True, False, id="xla-True"),
    pytest.param("pallas_fused", False, False, id="pallas_fused-False"),
    pytest.param("pallas_fused", True, False, id="pallas_fused-True"),
    pytest.param("pallas_fused", True, True, id="pallas_fused-True-rotating"),
])
def test_sweep_scopes_change_only_metadata(monkeypatch, backend, fuse_remap,
                                           rotating):
    """Without the named scopes the optimized program is the same, apart
    from metadata: instruction for instruction, names included (pinned
    and rotating layouts)."""
    _, state, factors, lam = _sweep(backend, fuse_remap=fuse_remap,
                                    rotating=rotating)
    args = engine.api._scan_args(state, factors, lam)

    def compiled():
        fn = jax.jit(engine.api._build_scan(state, _als_fold))
        return fn.lower(*args).compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "/mode1/ec/" in scoped and "/mode1/" not in plain
    assert "FileLocations" in scoped and "FileLocations" not in \
        _without_metadata(scoped)
    assert _without_metadata(scoped) == _without_metadata(plain)


def test_op_scopes_map_the_slot_scatters_to_remap(monkeypatch):
    monkeypatch.setattr(engine.api, "_JIT_CACHE", {})
    monkeypatch.setattr(engine.api, "_SCAN_ARGS", {})
    monkeypatch.setattr(engine.api, "_OP_SCOPES", {})
    assert engine.api.op_scopes() == {}
    _, state, factors, lam = _sweep("xla")
    engine.all_modes(state, factors, fold=_als_fold, carry=lam)
    scopes = engine.api.op_scopes()
    # the shapes recorded at the first call compile the program it ran
    text = engine.scan_hlo(state, factors, fold=_als_fold, carry=lam)
    (key,) = engine.api._SCAN_ARGS
    assert engine.api._compiled_text(engine.api._JIT_CACHE[key],
                                     engine.api._SCAN_ARGS[key]) == text
    # every fusion around a scatter: the (S, 2N+1) slot-record moves are
    # each mode's remap, the f32 row sums its EC
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"^(%\S+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S)}
    scatter_comps = {c for c, body in comps.items() if " scatter(" in body}
    heads = re.findall(
        r"^\s+(?:ROOT )?(%\S+ = \S+) fusion\(.*calls=(%[^,\s]+)", text, re.M)
    records = [h for h, c in heads if c in scatter_comps
               and re.match(r"%\S+ = s32\[\d+,9\]", h)]
    sums = [h for h, c in heads if c in scatter_comps
            and re.match(r"%\S+ = f32\[", h)]
    assert len(records) == state.nmodes and sums
    assert sorted((scopes[h].mode, scopes[h].scope) for h in records) == \
        [(d, "remap") for d in range(state.nmodes)]
    assert {scopes[h].scope for h in sums} == {"ec"}
    assert {s.scope for s in scopes.values()} == {"ec", "remap", "fold"}


@pytest.mark.parametrize("dedup", [True, False])
def test_engine_row_copies_equal_the_nuniq_sums(dedup):
    t, state, _, _ = _sweep("pallas_fused", seed=22, dedup=dedup)
    for d in range(t.nmodes):
        nuniq = np.asarray(state.sched[d].nuniq)
        assert engine.api.ROW_COPIES[d] == int(nuniq.sum()) > 0
        if dedup:
            assert engine.api.ROW_COPIES[d] == int(t.dedup_tables(d)[2].sum())
            assert t._dedup_cache[("row_copies", d)] == int(nuniq.sum())
        else:
            p = t.plans[d]
            assert engine.api.ROW_COPIES[d] == \
                p.nblocks * p.block_p * (t.nmodes - 1)
    # a backend that stages no rows through dedup tables issues none
    engine.init(t, ExecutionConfig(backend="xla"))
    assert [engine.api.ROW_COPIES[d] for d in range(t.nmodes)] == \
        [0] * t.nmodes

