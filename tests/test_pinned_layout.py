"""The pinned layout: where the EC backend reads only ``val`` and ``lrow``
of the layout (``pallas_fused`` under the compact schedule),
``engine.init`` pins each mode's pair on the device and no step remaps.

  * pinned and rotating states give the same outputs, factors, ``lam``
    and fits bit for bit (3-6 modes, start mode 0 and N-1, dedup on and
    off), and stepping a pinned state matches the oracle;
  * the pinned sweep program has no slot-record scatter and no S-long
    relabel lookup, and the ``engine_remap_slots`` gauge says so;
  * the pinned state holds fewer bytes than the rotating one.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import build_flycoo, cp_als, init_factors, mttkrp_ref
from repro.core.cpd import _als_fold
from repro.engine import ExecutionConfig

DIMS_BY_NMODES = {
    3: (23, 17, 11),
    4: (13, 11, 7, 9),
    5: (9, 8, 7, 6, 5),
    6: (7, 6, 5, 4, 3, 8),
}
RANK = 8


def _tensor(seed, dims, nnz=600):
    rng = np.random.default_rng(seed)
    idx = np.unique(np.stack([rng.integers(0, d, nnz) for d in dims], 1)
                    .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    return idx, val, build_flycoo(idx, val, dims, rows_pp=4, block_p=8)


def _cfg(dedup=True, **kw):
    return ExecutionConfig(backend="pallas_fused", interpret=True,
                           fuse_remap=False, dedup=dedup, **kw)


def _assert_bitwise(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


@pytest.mark.parametrize("start,dedup", [("first", True), ("last", False)])
@pytest.mark.parametrize("nmodes", [3, 4, 5, 6])
def test_pinned_matches_rotating_bitwise(monkeypatch, nmodes, start, dedup):
    """The pinned state hands the kernel the val and lrow the rotating one
    computes, in the same slot order: the same ALS sweep outputs, factors
    and lam bit for bit. From mode 0 that is also three ``cp_als`` sweeps
    (fits included); from mode N-1, stepping one dispatch at a time
    matches the oracle."""
    dims = DIMS_BY_NMODES[nmodes]
    idx, val, t = _tensor(nmodes + 30, dims)
    m0 = 0 if start == "first" else nmodes - 1
    cfg = _cfg(dedup)
    pinned = engine.init(t, cfg, start_mode=m0)
    rotating = engine.init(t, cfg, start_mode=m0, _rotating=True)
    assert (pinned.layout, rotating.layout) == ("pinned", "rotating")
    assert pinned.val is None and pinned.idx is None and pinned.alpha is None
    factors = tuple(init_factors(jax.random.PRNGKey(6), dims, RANK))
    lam = jnp.ones((RANK,), jnp.float32)
    fp, fr = factors, factors
    for _ in range(2):   # the second sweep reads a remapped rotating layout
        outs_p, nxt, fp, lam_p = engine.all_modes(pinned, fp,
                                                  fold=_als_fold, carry=lam)
        outs_r, rotating, fr, lam_r = engine.all_modes(
            rotating, fr, fold=_als_fold, carry=lam)
        assert nxt is pinned and rotating.mode == m0
        _assert_bitwise(outs_p + list(fp) + [lam_p],
                        outs_r + list(fr) + [lam_r])

    if m0 == 0:
        # cp_als runs the program above; its rotating run asks init for
        # the rotating layout
        res_p = cp_als(t, rank=RANK, iters=3, config=cfg)
        init = engine.init
        monkeypatch.setattr(engine, "init", lambda *a, **kw: init(
            *a, **{**kw, "_rotating": True}))
        res_r = cp_als(t, rank=RANK, iters=3, config=cfg)
        _assert_bitwise(res_p.factors + [res_p.lam],
                        res_r.factors + [res_r.lam])
        assert res_p.fits == res_r.fits and len(res_p.fits) == 3
        return
    refs = [mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors, d,
                       dims[d]) for d in range(nmodes)]
    for i in range(nmodes):
        out, pinned = engine.mttkrp(pinned, factors)
        assert pinned.mode == (m0 + i + 1) % nmodes
        np.testing.assert_allclose(out, refs[(m0 + i) % nmodes], rtol=2e-4,
                                   atol=2e-4)
    with pytest.raises(ValueError, match=f"mode-{m0} layout"):
        engine.mttkrp(pinned, factors, mode=(m0 + 1) % nmodes)


def _leading_dims(text: str, op: str) -> set:
    """First result dimension of every ``op`` instruction of an HLO text."""
    return {int(m.group(1)) for m in re.finditer(
        rf"^\s+(?:ROOT )?%\S+ = \w+\[(\d+)[\],][^=]* {op}\(", text, re.M)}


def test_pinned_program_has_no_remap(monkeypatch):
    """The pinned sweep program has no scatter, no (S, 2N+1) slot record
    and no S-long relabel lookup, and its scopes are ec and fold alone.
    The remap gauge reads 0 per mode there, and S_max on rotating states:
    xla's, pallas_fused's asked for, and the rect schedule's."""
    monkeypatch.setattr(engine.api, "_JIT_CACHE", {})
    monkeypatch.setattr(engine.api, "_SCAN_ARGS", {})
    monkeypatch.setattr(engine.api, "_OP_SCOPES", {})
    dims = DIMS_BY_NMODES[4]
    n = len(dims)
    _, _, t = _tensor(21, dims)
    factors = tuple(init_factors(jax.random.PRNGKey(4), dims, RANK))
    lam = jnp.ones((RANK,), jnp.float32)
    cfg = _cfg(donate=False)
    state = engine.init(t, cfg)
    slots = {s.padded_nnz for s in state.statics} | {state.smax}
    assert state.layout == "pinned"
    assert [engine.api.REMAP_SLOTS[d] for d in range(n)] == [0] * n
    text = engine.scan_hlo(state, factors, fold=_als_fold, carry=lam)
    assert " scatter(" not in text
    assert not re.search(rf"s32\[\d+,{2 * n + 1}\]", text)
    assert not _leading_dims(text, "gather") & slots
    engine.all_modes(state, factors, fold=_als_fold, carry=lam)
    assert {s.scope for s in engine.api.op_scopes().values()} == \
        {"ec", "fold"}

    xla = engine.init(t, ExecutionConfig(backend="xla", donate=False))
    text = engine.scan_hlo(xla, factors, fold=_als_fold, carry=lam)
    assert " scatter(" in text
    assert re.search(rf"s32\[{xla.smax},{2 * n + 1}\]", text)
    assert _leading_dims(text, "gather") & slots
    rect = build_flycoo(t.indices, t.values, dims, rows_pp=4, block_p=8,
                        schedule="rect")
    for make in (lambda: engine.init(t, ExecutionConfig(backend="xla")),
                 lambda: engine.init(t, cfg, _rotating=True),
                 lambda: engine.init(rect, cfg)):
        st = make()
        assert st.layout == "rotating"
        assert [engine.api.REMAP_SLOTS[d] for d in range(n)] == \
            [st.smax] * n


@pytest.mark.parametrize("nmodes", [3, 6])
def test_pinned_state_holds_fewer_bytes(nmodes):
    """Pinned: 8 B a slot and mode, against (4 + 8N) B for each of the
    rotating layout's S_max slots, beside the same schedule and relabel
    tables; ``resident_bytes`` counts the layout the state holds."""
    from repro.engine.stream import resident_bytes

    dims = DIMS_BY_NMODES[nmodes]
    _, _, t = _tensor(nmodes + 50, dims)
    cfg = _cfg()
    pinned = engine.init(t, cfg)
    rotating = engine.init(t, cfg, _rotating=True)

    def nbytes(tree):
        return sum(x.nbytes for x in jax.tree.leaves(tree))

    tables = nbytes((pinned.sched, pinned.relabel))
    assert tables == nbytes((rotating.sched, rotating.relabel))
    s = [p.padded_nnz for p in t.plans]
    assert nbytes(pinned) == 8 * sum(s) + tables
    assert nbytes(rotating) == (4 + 8 * nmodes) * max(s) + tables
    assert nbytes(pinned) < nbytes(rotating)
    beside = (sum(dims) + max(dims)) * RANK * 4    # factors + one output
    assert resident_bytes(t, cfg, RANK) == nbytes(pinned) + beside
    xla = engine.init(t, ExecutionConfig(backend="xla"))
    assert resident_bytes(t, xla.config, RANK) == nbytes(xla) + beside


def test_shard_state_asks_for_the_rotating_layout():
    """A pinned state holds no alpha tables to re-lay over a mesh."""
    _, _, t = _tensor(3, DIMS_BY_NMODES[3])
    with pytest.raises(ValueError, match="_rotating=True"):
        engine.dist.shard_state(engine.init(t, _cfg()),
                                jax.sharding.Mesh(jax.devices()[:1],
                                                  ("data",)))
