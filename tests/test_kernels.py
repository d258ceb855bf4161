"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("kappa,rows_pp,blocks_pp,p", [
    (2, 8, 1, 8), (4, 16, 3, 16), (8, 4, 2, 32), (3, 128, 2, 128),
])
@pytest.mark.parametrize("nm1,r", [(2, 8), (3, 32), (4, 16), (2, 128)])
def test_mttkrp_fused_shapes(kappa, rows_pp, blocks_pp, p, nm1, r):
    rng = np.random.default_rng(kappa * 1000 + nm1)
    s = kappa * blocks_pp * p
    g = rng.standard_normal((s, nm1, r)).astype(np.float32)
    val = rng.standard_normal(s).astype(np.float32)
    lrow = rng.integers(-1, rows_pp, s).astype(np.int32)
    val[lrow < 0] = 0.0
    args = (jnp.asarray(g), jnp.asarray(val), jnp.asarray(lrow))
    kw = dict(kappa=kappa, rows_pp=rows_pp, blocks_pp=blocks_pp, block_p=p)
    # the kernel takes the lane-dense (N-1, R, S) layout of the operand
    out = ops.mttkrp_fused(jnp.transpose(args[0], (1, 2, 0)), *args[1:],
                           **kw, interpret=True)
    exp = ref.mttkrp_fused_ref(*args, **kw)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)


def _gather_case(seed, kappa, rows_pp, blocks_pp, p, nm1, r):
    """Random fused-gather kernel inputs + the composed oracle target."""
    rng = np.random.default_rng(seed)
    s = kappa * blocks_pp * p
    dims_in = [int(rng.integers(8, 40)) for _ in range(nm1)]
    facs = tuple(jnp.asarray(rng.standard_normal((d, r)).astype(np.float32))
                 for d in dims_in)
    lidx = np.stack([rng.integers(0, d, s) for d in dims_in]).astype(np.int32)
    val = rng.standard_normal(s).astype(np.float32)
    lrow = rng.integers(-1, rows_pp, s).astype(np.int32)
    val[lrow < 0] = 0.0
    gathered = jnp.stack([facs[w][lidx[w]] for w in range(nm1)], axis=1)
    exp = ref.mttkrp_fused_ref(gathered, jnp.asarray(val), jnp.asarray(lrow),
                               kappa=kappa, rows_pp=rows_pp,
                               blocks_pp=blocks_pp, block_p=p)
    return facs, jnp.asarray(lidx), jnp.asarray(val), jnp.asarray(lrow), exp


@pytest.mark.parametrize("kappa,rows_pp,blocks_pp,p", [
    (2, 8, 1, 8), (4, 16, 3, 16), (3, 4, 2, 32),
])
@pytest.mark.parametrize("nm1,r", [(2, 8), (3, 32), (5, 16)])
def test_mttkrp_fused_gather_shapes(kappa, rows_pp, blocks_pp, p, nm1, r):
    """In-kernel gather == XLA gather + baseline kernel oracle."""
    facs, lidx, val, lrow, exp = _gather_case(
        kappa * 100 + nm1, kappa, rows_pp, blocks_pp, p, nm1, r)
    out = ops.mttkrp_fused_gather(val, lrow, lidx, facs, kappa=kappa,
                                  rows_pp=rows_pp, blocks_pp=blocks_pp,
                                  block_p=p, interpret=True)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kappa,rows_pp,blocks_pp,p,nm1,r", [
    (2, 8, 1, 8, 2, 8), (3, 4, 2, 16, 3, 32),
])
def test_mttkrp_fused_remap_scatters_next_layout(kappa, rows_pp, blocks_pp,
                                                 p, nm1, r):
    """The remap variant returns the EC result AND the mode-(d+1) layout
    (val/idx/alpha scattered to alpha[:, next]; empty slots = pad pattern),
    matching the XLA scatter the scan step used to issue."""
    facs, lidx, val, lrow, exp = _gather_case(
        7 * kappa + p, kappa, rows_pp, blocks_pp, p, nm1, r)
    rng = np.random.default_rng(p + nm1)
    s = val.shape[0]
    n = nm1 + 1
    smax = s + 24
    alive = np.asarray(lrow) >= 0
    idx = rng.integers(0, 50, (s, n)).astype(np.int32)
    alpha = np.full((s, n), -1, np.int32)
    alpha[alive] = rng.integers(0, smax, (int(alive.sum()), n))
    alpha[alive, 1] = rng.permutation(smax)[: int(alive.sum())]
    dst = alpha[:, 1]

    out, nval, nidx, nalpha = ops.mttkrp_fused_remap(
        val, jnp.asarray(idx), jnp.asarray(alpha), lrow, lidx, facs,
        kappa=kappa, rows_pp=rows_pp, blocks_pp=blocks_pp, block_p=p,
        smax=smax, next_mode=1, interpret=True)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)

    eval_ = np.zeros(smax, np.float32)
    eidx = np.zeros((smax, n), np.int32)
    ealpha = np.full((smax, n), -1, np.int32)
    eval_[dst[alive]] = np.asarray(val)[alive]
    eidx[dst[alive]] = idx[alive]
    ealpha[dst[alive]] = alpha[alive]
    np.testing.assert_allclose(np.asarray(nval), eval_)
    np.testing.assert_array_equal(np.asarray(nidx), eidx)
    np.testing.assert_array_equal(np.asarray(nalpha), ealpha)


# --------------------------------------------------------------------------
# Compact (descriptor-driven) kernels with in-block row dedup.
# --------------------------------------------------------------------------
def _compact_case(seed, kappa, part_blocks, p, nm1, r, hot_rows=4):
    """Random compact-schedule inputs: a descriptor with the given per-
    partition block counts, Zipf-ish factor rows (few hot rows so blocks
    dedup), dedup tables from the shared host-side builder, and the
    composed descriptor-aware oracle."""
    from repro.core.flycoo import _ROW_SENTINEL, dedup_tables_from_rows

    rng = np.random.default_rng(seed)
    assert len(part_blocks) == kappa
    nblocks = sum(part_blocks)
    s = nblocks * p
    bpart = np.repeat(np.arange(kappa), part_blocks).astype(np.int32)
    rows_pp = 8
    dims_in = [int(rng.integers(8, 40)) for _ in range(nm1)]
    facs = tuple(jnp.asarray(rng.standard_normal((d, r)).astype(np.float32))
                 for d in dims_in)
    # skewed row choices: sample from a few hot rows most of the time
    lidx = np.stack([
        np.where(rng.random(s) < 0.7,
                 rng.integers(0, min(hot_rows, d), s),
                 rng.integers(0, d, s))
        for d in dims_in]).astype(np.int64)
    lrow = rng.integers(-1, rows_pp, s).astype(np.int32)
    val = rng.standard_normal(s).astype(np.float32)
    val[lrow < 0] = 0.0
    uidx, upos, nuniq = [], [], []
    for w in range(nm1):
        rows = np.where(lrow < 0, _ROW_SENTINEL, lidx[w])
        u, pos, nun = dedup_tables_from_rows(rows, nblocks, p)
        uidx.append(u)
        upos.append(pos)
        nuniq.append(nun)
    uidx, upos, nuniq = (np.stack(uidx), np.stack(upos, axis=1),
                         np.stack(nuniq))
    gathered = jnp.stack([facs[w][lidx[w]] for w in range(nm1)], axis=1)
    exp = ref.mttkrp_fused_compact_ref(
        gathered, jnp.asarray(val), jnp.asarray(lrow), jnp.asarray(bpart),
        kappa=kappa, rows_pp=rows_pp, block_p=p)
    return dict(facs=facs, bpart=jnp.asarray(bpart),
                uidx=jnp.asarray(uidx), upos=jnp.asarray(upos),
                nuniq=jnp.asarray(nuniq), gathered=gathered,
                val=jnp.asarray(val), lrow=jnp.asarray(lrow), exp=exp,
                kappa=kappa, rows_pp=rows_pp, nblocks=nblocks, p=p,
                nm1=nm1, nuniq_np=nuniq, lidx=lidx)


@pytest.mark.parametrize("kappa,part_blocks,p", [
    (2, (3, 1), 8), (4, (1, 4, 2, 1), 16), (3, (2, 1, 5), 32),
])
@pytest.mark.parametrize("nm1,r", [(2, 8), (3, 32), (5, 16)])
def test_mttkrp_fused_compact_shapes(kappa, part_blocks, p, nm1, r):
    """Descriptor-driven 1-D grid == descriptor-aware oracle on skewed,
    deliberately unbalanced per-partition block counts."""
    c = _compact_case(kappa * 10 + p, kappa, part_blocks, p, nm1, r)
    out = ops.mttkrp_fused_compact(
        jnp.transpose(c["gathered"], (1, 2, 0)), c["val"], c["lrow"], c["bpart"], kappa=c["kappa"],
        rows_pp=c["rows_pp"], nblocks=c["nblocks"], block_p=c["p"],
        interpret=True)
    np.testing.assert_allclose(out, c["exp"], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kappa,part_blocks,p", [
    (2, (3, 1), 8), (4, (1, 4, 2, 1), 16), (3, (2, 1, 5), 32),
])
@pytest.mark.parametrize("nm1,r", [(2, 8), (3, 32), (5, 16)])
def test_mttkrp_fused_gather_compact_dedup(kappa, part_blocks, p, nm1, r):
    """In-kernel dedup gather (U <= P row DMAs + one-hot stage select)
    == XLA gather + oracle; the dedup tables actually dedup (hot rows)."""
    c = _compact_case(kappa * 7 + nm1, kappa, part_blocks, p, nm1, r)
    assert int(c["nuniq_np"].sum()) < c["nblocks"] * c["p"] * c["nm1"]
    out = ops.mttkrp_fused_gather_compact(
        c["val"], c["lrow"], c["upos"], c["bpart"], c["uidx"], c["nuniq"],
        c["facs"], kappa=c["kappa"], rows_pp=c["rows_pp"],
        nblocks=c["nblocks"], block_p=c["p"], interpret=True)
    np.testing.assert_allclose(out, c["exp"], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kappa,part_blocks,p,nm1,r", [
    (2, (3, 1), 8, 2, 8), (3, (2, 1, 3), 16, 3, 32),
])
def test_mttkrp_fused_remap_compact_scatters_next_layout(kappa, part_blocks,
                                                         p, nm1, r):
    """The compact remap variant returns the EC result AND the mode-(d+1)
    layout, matching the XLA scatter the scan step would issue."""
    c = _compact_case(13 * kappa + p, kappa, part_blocks, p, nm1, r)
    rng = np.random.default_rng(p + nm1)
    s = c["nblocks"] * c["p"]
    n = nm1 + 1
    smax = s + 24
    lrow = np.asarray(c["lrow"])
    alive = lrow >= 0
    idx = rng.integers(0, 50, (s, n)).astype(np.int32)
    alpha = np.full((s, n), -1, np.int32)
    alpha[alive] = rng.integers(0, smax, (int(alive.sum()), n))
    alpha[alive, 1] = rng.permutation(smax)[: int(alive.sum())]
    dst = alpha[:, 1]

    out, nval, nidx, nalpha = ops.mttkrp_fused_remap_compact(
        c["val"], jnp.asarray(idx), jnp.asarray(alpha), c["lrow"],
        c["upos"], c["bpart"], c["uidx"], c["nuniq"], c["facs"],
        kappa=c["kappa"], rows_pp=c["rows_pp"], nblocks=c["nblocks"],
        block_p=c["p"], smax=smax, next_mode=1, interpret=True)
    np.testing.assert_allclose(out, c["exp"], rtol=1e-4, atol=1e-4)

    eval_ = np.zeros(smax, np.float32)
    eidx = np.zeros((smax, n), np.int32)
    ealpha = np.full((smax, n), -1, np.int32)
    eval_[dst[alive]] = np.asarray(c["val"])[alive]
    eidx[dst[alive]] = idx[alive]
    ealpha[dst[alive]] = alpha[alive]
    np.testing.assert_allclose(np.asarray(nval), eval_)
    np.testing.assert_array_equal(np.asarray(nidx), eidx)
    np.testing.assert_array_equal(np.asarray(nalpha), ealpha)


@pytest.mark.parametrize("b,t,d,chunk", [
    (1, 32, 8, 8), (2, 64, 16, 16), (3, 128, 32, 32), (2, 64, 128, 64),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_lru_scan_shapes(b, t, d, chunk, dtype):
    rng = np.random.default_rng(b * t)
    a = rng.uniform(0.3, 0.999, (b, t, d)).astype(dtype)
    x = rng.standard_normal((b, t, d)).astype(dtype)
    out = ops.lru_scan(jnp.asarray(a), jnp.asarray(x), chunk=chunk,
                       interpret=True)
    exp = ref.lru_scan_ref(jnp.asarray(a), jnp.asarray(x))
    np.testing.assert_allclose(out, exp, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bh,t,k,v,chunk", [
    (2, 16, 8, 8, 8), (4, 32, 16, 32, 16), (1, 64, 64, 64, 16),
])
def test_wkv6_shapes(bh, t, k, v, chunk):
    rng = np.random.default_rng(bh + t)
    r = rng.standard_normal((bh, t, k)).astype(np.float32)
    kk = rng.standard_normal((bh, t, k)).astype(np.float32)
    w = rng.uniform(0.5, 0.999, (bh, t, k)).astype(np.float32)
    vv = rng.standard_normal((bh, t, v)).astype(np.float32)
    u = rng.standard_normal((bh, k)).astype(np.float32)
    args = tuple(map(jnp.asarray, (r, kk, w, vv, u)))
    out = ops.wkv6(*args, chunk=chunk, interpret=True)
    exp = ref.wkv6_ref(*args)
    np.testing.assert_allclose(out, exp, rtol=1e-3, atol=1e-3)


def test_mttkrp_kernel_matches_model_chunking():
    """Kernel path through the full executor (integration-level)."""
    from repro.core import MTTKRPExecutor, build_flycoo, init_factors, \
        mttkrp_ref
    rng = np.random.default_rng(0)
    dims = (33, 21, 17)
    idx = np.unique(np.stack([rng.integers(0, d, 700) for d in dims], 1)
                    .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    t = build_flycoo(idx, val, dims, rows_pp=8, block_p=16)
    factors = init_factors(jax.random.PRNGKey(0), dims, 8)
    outs = MTTKRPExecutor(t, backend="pallas", interpret=True).all_modes(
        factors)
    for d in range(3):
        expd = mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors, d,
                          dims[d])
        np.testing.assert_allclose(outs[d], expd, rtol=1e-4, atol=1e-4)


def test_wkv6_kernel_matches_model_timemix():
    """Pallas wkv6 == the model's chunked time_mix core recurrence."""
    from repro.models.rwkv import time_mix, init_rwkv_block
    from repro import configs
    # equivalence is exercised indirectly: both against the scan oracle
    rng = np.random.default_rng(1)
    bh, t, k = 3, 32, 8
    r = rng.standard_normal((bh, t, k)).astype(np.float32)
    kk = rng.standard_normal((bh, t, k)).astype(np.float32)
    w = rng.uniform(0.8, 0.999, (bh, t, k)).astype(np.float32)
    vv = rng.standard_normal((bh, t, k)).astype(np.float32)
    u = rng.standard_normal((bh, k)).astype(np.float32)
    args = tuple(map(jnp.asarray, (r, kk, w, vv, u)))
    out = ops.wkv6(*args, chunk=8, interpret=True)
    exp = ref.wkv6_ref(*args)
    np.testing.assert_allclose(out, exp, rtol=1e-3, atol=1e-3)
