"""Fused spMTTKRP elementwise-computation Pallas TPU kernels.

This is the TPU adaptation of the paper's thread-block kernel (Alg. 2/4):

  * the grid walks nonzero blocks with the *output row tile resident in
    VMEM* — the paper's "intermediate values never visit global memory"
    (its challenge (2)) becomes "the (P, R) Hadamard partials live in VREGs
    and the (rows_pp, R) accumulator lives in VMEM for the whole partition".
  * the scatter-add that GPUs do with intra-block atomics becomes a one-hot
    MXU contraction: out_tile += (val * onehot(lrow))^T @ partials, a dense
    (rows_pp x P) @ (P x R) matmul — the TPU-idiomatic segment reduction.
  * ownership (paper Observation 2): partition j's elements touch only rows
    [j*rows_pp, (j+1)*rows_pp), so no cross-block reduction exists: the
    accumulator is zeroed on a partition's first block and DMA'd to its
    HBM row range on the partition's last block.

Pad slots carry lrow = -1; the one-hot comparison yields an all-zero column
for them, so they contribute nothing even when a pad val is nonzero.

Every kernel walks a 1-D grid over blocks. Block ``b``'s partition and
whether it opens or closes that partition come from a per-block metadata
table kept in HBM (``ANY``) and loaded into SMEM ``_GROUP`` blocks at a
time, so the SMEM footprint is independent of the slot count ``S`` and of
the block count — a scalar-prefetched ``(nblocks,)`` descriptor or
``(N-1, S)`` index table does not fit SMEM at published tensor sizes.
The two block schedules differ only in that table:

  *rect*      every partition padded to ``blocks_pp`` blocks (partition of
              block b is ``b // blocks_pp``) — kept as the baseline.
  *compact*   only the real blocks, with the host plan's ``bpart``
              block->partition descriptor (paper challenge (3)).

Pipelines (x2 schedules):

  ``mttkrp_fused[_compact]``        take a pre-gathered ``(N-1, R, S)``
                                    operand that XLA materializes in HBM —
                                    the comparison baseline (engine backend
                                    ``pallas``).
  ``mttkrp_fused_gather[_compact]`` zero-HBM-intermediate pipeline (engine
                                    backend ``pallas_fused``): factor
                                    matrices stay in ``ANY``/HBM and each
                                    grid step DMAs the needed rows into a
                                    double-buffered VMEM stage (block b+1's
                                    gather in flight while block b
                                    computes). The compact variant adds
                                    *in-block factor-row dedup*: the plan
                                    pre-sorts each block's factor-row list
                                    into ``U <= P`` unique rows (``uidx`` /
                                    ``nuniq``) so the kernel issues ``U``
                                    row DMAs instead of ``P`` — Zipf-heavy
                                    tensors re-fetch hot rows many times
                                    per block otherwise — and the EC body
                                    gathers its Hadamard operands through
                                    the per-slot stage positions ``upos``
                                    with a one-hot MXU select (no dynamic
                                    VMEM gather needed). A block's row
                                    list is DMA'd into SMEM one block
                                    ahead of its row copies.
  ``mttkrp_fused_remap[_compact]``  same pass, plus the Alg. 3 dynamic
                                    remap: the kernel scatters each alive
                                    slot's (val, idx, alpha) row to its
                                    ``alpha[:, next]`` destination in
                                    VMEM-resident next-layout buffers,
                                    replacing three full-``S_max`` XLA
                                    scatters per scan step. Those buffers
                                    bound ``S_max``
                                    (:func:`fused_remap_vmem_bytes`).

Layout at the kernel boundary: per-slot vectors are passed lane-dense as
``(nblocks, P)`` rows (an ``(S, 1)`` operand would be padded to 128 lanes
in HBM), factor matrices are zero-padded to a multiple of 128 lanes so a
single-row DMA is tile-aligned at any rank R, and the output is sliced
back to ``(kappa * rows_pp, R)``. All MXU contractions run at
``Precision.HIGHEST`` (f32), so one-hot selects are exact.

Block shape knobs mirror the paper's R x P thread block (Fig. 4): P is the
number of nonzeros entering per step (paper picks P=32 for 1024-thread
blocks; we default P=128 = one lane tile), R is the rank.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128        # TPU lane width: factor/output minor dims pad to this
_SUBLANE = 8       # f32 sublane tile: row tiles and row blocks pad to this
_GROUP = 128       # blocks whose metadata one SMEM load carries
_HIGHEST = lax.Precision.HIGHEST

# Metadata fields per block; gather kernels append one row-count per input
# factor (``nuniq``).
_PART, _FIRST, _LAST, _COUNT0 = 0, 1, 2, 3

#: VMEM of one TPU v5e TensorCore. The fused remap's next layout stays
#: resident there for the whole grid, so it bounds that kernel's S_max.
VMEM_CAPACITY_BYTES = 128 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def fused_remap_vmem_bytes(smax: int, nmodes: int, rank: int, rows_pp: int,
                           block_p: int) -> int:
    """VMEM the fused remap needs: its resident next layout (``(smax, 1)``
    f32 and two ``(smax, N)`` int32 buffers, each padded to (8, 128)
    tiles, about 1.5 KiB per slot) plus the double-buffered row stage and
    the row-tile accumulator."""
    rp = _round_up(rank, _LANE)
    resident = 3 * _round_up(smax, _SUBLANE) * _round_up(nmodes, _LANE) * 4
    stage = 2 * (nmodes - 1) * block_p * rp * 4
    acc = _round_up(rows_pp, _SUBLANE) * rp * 4
    return resident + stage + acc


def check_fused_remap_fits(smax: int, nmodes: int, rank: int, rows_pp: int,
                           block_p: int) -> None:
    """Raise ``ValueError`` when the fused remap cannot hold its next
    layout in VMEM (``S_max`` above roughly 87k slots on a v5e)."""
    need = fused_remap_vmem_bytes(smax, nmodes, rank, rows_pp, block_p)
    if need > VMEM_CAPACITY_BYTES:
        raise ValueError(
            f"fused remap needs {need} bytes of VMEM for S_max={smax} "
            f"(next layout resident at ~1.5 KiB per slot), over the "
            f"{VMEM_CAPACITY_BYTES}-byte VMEM of one TPU v5e core; build "
            "the engine with ExecutionConfig(fuse_remap=False)")


def _blocked(x, nblocks: int, block_p: int):
    """Per-slot ``(S,)`` or ``(k, S)`` array -> lane-dense ``(nblocks, P)``
    or ``(k, nb8, P)`` rows: block b is row b. A ``(k, S)`` table is first
    padded to a whole sublane tile of blocks, which keeps the reshape a
    relabeling of its (8, 128) tiles; otherwise XLA:TPU emits a real
    relayout that takes minutes to compile at ~22M slots."""
    if x.ndim == 1:
        return x.reshape(nblocks, block_p)
    pad = (_round_up(nblocks, _SUBLANE) - nblocks) * block_p
    x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(x.shape[0], -1, block_p)


def _block_meta(bpart, counts, nblocks: int):
    """Per-block metadata ``[part, first, last, *counts]`` as an
    ``(ngroups * M, _GROUP)`` int32 table: group g's M rows hold the fields
    of blocks ``[g*_GROUP, (g+1)*_GROUP)``, one block per lane."""
    bpart = bpart.astype(jnp.int32)
    edge = jnp.full((1,), -1, jnp.int32)
    prev = jnp.concatenate([edge, bpart[:-1]])
    nxt = jnp.concatenate([bpart[1:], edge])
    fields = [bpart, (bpart != prev).astype(jnp.int32),
              (bpart != nxt).astype(jnp.int32)]
    if counts is not None:
        fields += list(counts.astype(jnp.int32))
    meta = jnp.stack(fields)                          # (M, nblocks)
    m = meta.shape[0]
    ngroups = -(-nblocks // _GROUP)
    meta = jnp.pad(meta, ((0, 0), (0, ngroups * _GROUP - nblocks)))
    return meta.reshape(m, ngroups, _GROUP).transpose(1, 0, 2).reshape(
        ngroups * m, _GROUP)


def _rect_bpart(nblocks: int, blocks_pp: int):
    return jnp.arange(nblocks, dtype=jnp.int32) // blocks_pp


def _pad_lanes(f, width: int):
    return jnp.pad(f.astype(jnp.float32), ((0, 0), (0, width - f.shape[1])))


def _unpad_out(out, kappa: int, rows_pp: int, rows_pad: int, r: int):
    out = out.reshape(kappa, rows_pad, out.shape[-1])[:, :rows_pp, :r]
    return out.reshape(kappa * rows_pp, r)


# --------------------------------------------------------------------------
# Kernel body shared by every pipeline.
# --------------------------------------------------------------------------
def _remap_init_and_scatter(b, val_ref, idx_ref, alpha_ref, nval_ref,
                            nidx_ref, nalpha_ref, *, block_p: int,
                            next_mode: int):
    """Alg. 3 in-kernel: initialize the resident next-layout buffers on the
    first grid step, then scatter every alive slot to its
    ``alpha[:, next_mode]`` destination (conflict-free by construction —
    destinations are a permutation of the alive slots; pads carry -1)."""

    @pl.when(b == 0)
    def _init_next_layout():
        nval_ref[...] = jnp.zeros_like(nval_ref)
        nidx_ref[...] = jnp.zeros_like(nidx_ref)
        nalpha_ref[...] = jnp.full_like(nalpha_ref, -1)

    def scatter(i, _):
        d = alpha_ref[i, next_mode]

        @pl.when(d >= 0)
        def _move():
            nval_ref[pl.ds(d, 1), :] = val_ref[pl.ds(i, 1), :]
            nidx_ref[pl.ds(d, 1), :] = idx_ref[pl.ds(i, 1), :]
            nalpha_ref[pl.ds(d, 1), :] = alpha_ref[pl.ds(i, 1), :]
        return 0

    lax.fori_loop(0, block_p, scatter, 0)


def _kernel(meta_hbm, val_ref, lrow_ref, *refs, kind: str, nm1: int, r: int,
            rows_pad: int, block_p: int, nblocks: int, nfields: int,
            next_mode: int | None):
    """One grid step = one block of ``block_p`` slots.

    ``kind`` selects how the step obtains its per-slot factor rows:
    ``"pregathered"`` reads them from a blocked ``(N-1, R, P)`` operand;
    ``"gather"`` DMAs one row per slot from the ``ANY`` factors;
    ``"dedup"`` DMAs each block's ``U <= P`` unique rows and routes slots
    to them through ``upos``. ``next_mode`` adds the Alg. 3 remap scatter.
    """
    with_remap = next_mode is not None
    refs = list(refs)
    take = lambda k: [refs.pop(0) for _ in range(k)]  # noqa: E731
    if kind == "pregathered":
        (g_ref,) = take(1)
    else:
        (uidx_hbm,) = take(1)
        (upos_ref,) = take(1) if kind == "dedup" else (None,)
        if with_remap:
            valc_ref, idx_ref, alpha_ref = take(3)
        facs = take(nm1)
    (out_hbm,) = take(1)
    if with_remap:
        nval_ref, nidx_ref, nalpha_ref = take(3)
    meta_sm, acc_ref, sem = take(3)
    if kind != "pregathered":
        uidx_sm, stage, row_sems, uidx_sems = take(4)

    b = pl.program_id(0)
    k = b % _SUBLANE                           # row of block b in its tile

    def sync(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem.at[0])
        cp.start()
        cp.wait()

    def load_meta(g):
        sync(meta_hbm.at[pl.ds(g * nfields, nfields)], meta_sm.at[g % 2])

    def meta(blk, field):
        return meta_sm[(blk // _GROUP) % 2, field, blk % _GROUP]

    # Group g's metadata is resident from the step before its first block
    # (the row copies of block b+1 are issued during step b).
    @pl.when(b == 0)
    def _first_group():
        load_meta(0)

    @pl.when(jnp.logical_and((b + 1) % _GROUP == 0, b + 1 < nblocks))
    def _next_group():
        load_meta((b + 1) // _GROUP)

    if kind == "pregathered":
        g = g_ref[...]                         # (N-1, R, P)
        parts = [g[w] for w in range(nm1)]
    else:
        def fetch_uidx(blk, start: bool):
            # block blk's row list, one (1, P) row per input factor
            for w in range(nm1):
                cp = pltpu.make_async_copy(
                    uidx_hbm.at[w, pl.ds(blk, 1)],
                    uidx_sm.at[blk % 2, pl.ds(w, 1)],
                    uidx_sems.at[blk % 2])
                (cp.start if start else cp.wait)()

        def rows(blk, start: bool):
            # One (1, R) row copy per listed row; starts and waits pair up
            # through the per-stage-slot semaphore (all copies same size).
            sl = blk % 2
            for w, f in enumerate(facs):
                def body(u, c, w=w, f=f):
                    row = uidx_sm[sl, w, u] if start else 0
                    cp = pltpu.make_async_copy(
                        f.at[pl.ds(row, 1)], stage.at[sl, w, pl.ds(u, 1)],
                        row_sems.at[sl])
                    (cp.start if start else cp.wait)()
                    return c

                lax.fori_loop(0, meta(blk, _COUNT0 + w), body, 0)

        @pl.when(b == 0)
        def _prologue():                   # block 0 has nobody to hide under
            # The dedup select reads the WHOLE staged block (rows >= U
            # weighted 0): zero it once so no step multiplies
            # uninitialized VMEM (0 * garbage need not be 0).
            stage[...] = jnp.zeros_like(stage)
            fetch_uidx(0, start=True)
            fetch_uidx(0, start=False)
            rows(0, start=True)
            if nblocks > 1:
                fetch_uidx(1, start=True)

        @pl.when(b + 1 < nblocks)
        def _prefetch_next():              # overlap: issue b+1, compute b
            fetch_uidx(b + 1, start=False)
            rows(b + 1, start=True)

            @pl.when(b + 2 < nblocks)
            def _row_list_after():
                fetch_uidx(b + 2, start=True)

        rows(b, start=False)
        g = stage[b % 2]                       # (N-1, P, Rp) staged rows
        if kind == "dedup":
            pos = upos_ref[:, pl.ds(k, 1), :]              # (N-1, 1, P)
            parts = []
            for w in range(nm1):
                # slot i's operand row = staged[pos[i]]: a (P x P) one-hot
                # select matmul (MXU-friendly; dynamic VMEM gathers are not)
                sel_t = (lax.broadcasted_iota(jnp.int32, (block_p, block_p),
                                              0) == pos[w]).astype(jnp.float32)
                parts.append(lax.dot_general(
                    sel_t, g[w], (((0,), (0,)), ((), ())),
                    precision=_HIGHEST, preferred_element_type=jnp.float32))
        else:
            parts = [g[w] for w in range(nm1)]

    ell = parts[0]
    for part in parts[1:]:                     # Hadamard across input modes
        ell = ell * part                       # (Alg. 2 lines 11-13)
    val = val_ref[pl.ds(k, 1), :]              # (1, P)
    lrow = lrow_ref[pl.ds(k, 1), :]            # (1, P) local output rows
    # Scatter-add as a val-weighted one-hot MXU matmul (no atomics on TPU).
    onehot = jnp.where(
        lax.broadcasted_iota(jnp.int32, (rows_pad, block_p), 0) == lrow,
        val, 0.0)                              # (rows_pad, P); -1 rows vanish
    contract = ((1,), (1,)) if kind == "pregathered" else ((1,), (0,))
    contrib = lax.dot_general(onehot, ell, (contract, ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)

    @pl.when(meta(b, _FIRST) == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if kind == "pregathered":
        acc_ref[:, :r] += contrib
    else:
        acc_ref[...] += contrib

    @pl.when(meta(b, _LAST) == 1)
    def _flush():
        row0 = pl.multiple_of(meta(b, _PART) * rows_pad, _SUBLANE)
        sync(acc_ref, out_hbm.at[pl.ds(row0, rows_pad)])

    if with_remap:
        _remap_init_and_scatter(b, valc_ref, idx_ref, alpha_ref, nval_ref,
                                nidx_ref, nalpha_ref, block_p=block_p,
                                next_mode=next_mode)


def _ec_call(kind: str, val, lrow, bpart, *, operands, operand_specs,
             nm1: int, r: int, kappa: int, rows_pp: int, nblocks: int,
             block_p: int, counts=None, remap=None, interpret: bool):
    """Assemble and run one pallas_call of :func:`_kernel`.

    ``operands``/``operand_specs`` are the pipeline-specific inputs after
    ``(meta, val, lrow)``; ``remap`` is ``(smax, n, next_mode)`` or None.
    Returns the padded ``(kappa * rows_pad, Rp)`` output (plus the next
    layout when remapping)."""
    s = nblocks * block_p
    assert val.shape == (s,) and lrow.shape == (s,), (val.shape, s)
    assert bpart.shape == (nblocks,), (bpart.shape, nblocks)
    rows_pad = _round_up(rows_pp, _SUBLANE)
    rp = _round_up(r, _LANE)
    meta = _block_meta(bpart, counts, nblocks)
    nfields = meta.shape[0] // -(-nblocks // _GROUP)

    # per-slot rows move _SUBLANE blocks at a time (all of them if fewer)
    row_blk = pl.BlockSpec((min(_SUBLANE, nblocks), block_p),
                           lambda b: (b // _SUBLANE, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = [jax.ShapeDtypeStruct((kappa * rows_pad, rp), jnp.float32)]
    out_specs = [any_spec]
    scratch = [pltpu.SMEM((2, nfields, _GROUP), jnp.int32),
               pltpu.VMEM((rows_pad, rp), jnp.float32),
               pltpu.SemaphoreType.DMA((1,))]
    if kind != "pregathered":
        scratch += [pltpu.SMEM((2, nm1, block_p), jnp.int32),
                    pltpu.VMEM((2, nm1, block_p, rp), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,))]
    next_mode = None
    if remap is not None:
        smax, n, next_mode = remap
        out_shape += [jax.ShapeDtypeStruct((smax, 1), jnp.float32),
                      jax.ShapeDtypeStruct((smax, n), jnp.int32),
                      jax.ShapeDtypeStruct((smax, n), jnp.int32)]
        out_specs += [pl.BlockSpec((smax, 1), lambda b: (0, 0)),
                      pl.BlockSpec((smax, n), lambda b: (0, 0)),
                      pl.BlockSpec((smax, n), lambda b: (0, 0))]

    return pl.pallas_call(
        functools.partial(_kernel, kind=kind, nm1=nm1, r=r,
                          rows_pad=rows_pad, block_p=block_p,
                          nblocks=nblocks, nfields=nfields,
                          next_mode=next_mode),
        grid=(nblocks,),
        in_specs=[any_spec, row_blk, row_blk] + list(operand_specs),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(meta, _blocked(val.astype(jnp.float32), nblocks, block_p),
      _blocked(lrow.astype(jnp.int32), nblocks, block_p), *operands)


def _pregathered(gathered, val, lrow, bpart, *, kappa, rows_pp, nblocks,
                 block_p, interpret):
    nm1, r, s = gathered.shape
    assert s == nblocks * block_p, (s, nblocks, block_p)
    spec = pl.BlockSpec((nm1, r, block_p), lambda b: (0, 0, b))
    (out,) = _ec_call("pregathered", val, lrow, bpart,
                      operands=[gathered.astype(jnp.float32)],
                      operand_specs=[spec], nm1=nm1, r=r, kappa=kappa,
                      rows_pp=rows_pp, nblocks=nblocks, block_p=block_p,
                      interpret=interpret)
    return _unpad_out(out, kappa, rows_pp, _round_up(rows_pp, _SUBLANE), r)


def _gather(val, lrow, bpart, uidx, nuniq, upos, factors, *, kappa, rows_pp,
            nblocks, block_p, remap=None, idx=None, alpha=None,
            interpret):
    """Shared wrapper of the in-kernel-gather pipelines. ``upos=None``
    stages slot i's row at stage row i (one copy per slot)."""
    s = nblocks * block_p
    nm1 = len(factors)
    r = factors[0].shape[1]
    rp = _round_up(r, _LANE)
    assert uidx.shape == (nm1, s), (uidx.shape, nm1, s)
    assert nuniq.shape == (nm1, nblocks), (nuniq.shape, nm1, nblocks)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    operands = [_blocked(uidx.astype(jnp.int32), nblocks, block_p)]
    specs = [any_spec]
    if upos is not None:
        assert upos.shape == (s, nm1), (upos.shape, s, nm1)
        operands.append(_blocked(upos.astype(jnp.int32).T, nblocks,
                                 block_p))
        specs.append(pl.BlockSpec((nm1, _SUBLANE, block_p),
                                  lambda b: (0, b // _SUBLANE, 0)))
    if remap is not None:
        n = idx.shape[1]
        operands += [val.reshape(s, 1).astype(jnp.float32),
                     idx.astype(jnp.int32), alpha.astype(jnp.int32)]
        specs += [pl.BlockSpec((block_p, 1), lambda b: (b, 0)),
                  pl.BlockSpec((block_p, n), lambda b: (b, 0)),
                  pl.BlockSpec((block_p, n), lambda b: (b, 0))]
    operands += [_pad_lanes(f, rp) for f in factors]
    specs += [any_spec] * nm1
    res = _ec_call("dedup" if upos is not None else "gather", val, lrow,
                   bpart, operands=operands, operand_specs=specs, nm1=nm1,
                   r=r, kappa=kappa, rows_pp=rows_pp, nblocks=nblocks,
                   block_p=block_p, counts=nuniq, remap=remap,
                   interpret=interpret)
    out = _unpad_out(res[0], kappa, rows_pp, _round_up(rows_pp, _SUBLANE),
                     r)
    if remap is None:
        return out
    return out, res[1][:, 0], res[2], res[3]


def _full_counts(nm1: int, nblocks: int, block_p: int):
    return jnp.full((nm1, nblocks), block_p, jnp.int32)


# --------------------------------------------------------------------------
# Public kernels.
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("kappa", "rows_pp", "blocks_pp", "block_p", "interpret"),
)
def mttkrp_fused(
    gathered: jax.Array,   # (N-1, R, S) gathered input-factor rows
    val: jax.Array,        # (S,) nonzero values (0 in pads)
    lrow: jax.Array,       # (S,) local output rows (-1 in pads)
    *,
    kappa: int,
    rows_pp: int,
    blocks_pp: int,
    block_p: int,
    interpret: bool = False,
) -> jax.Array:
    """Rect-schedule EC baseline; returns out_rel (kappa*rows_pp, R) in
    relabeled row space."""
    nblocks = kappa * blocks_pp
    return _pregathered(gathered, val, lrow, _rect_bpart(nblocks, blocks_pp),
                        kappa=kappa, rows_pp=rows_pp, nblocks=nblocks,
                        block_p=block_p, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kappa", "rows_pp", "nblocks", "block_p", "interpret"),
)
def mttkrp_fused_compact(
    gathered: jax.Array,   # (N-1, R, S) gathered input-factor rows
    val: jax.Array,        # (S,) nonzero values (0 in pads)
    lrow: jax.Array,       # (S,) local output rows (-1 in pads)
    bpart: jax.Array,      # (nblocks,) block -> partition descriptor
    *,
    kappa: int,
    rows_pp: int,
    nblocks: int,
    block_p: int,
    interpret: bool = False,
) -> jax.Array:
    """Compact-schedule EC baseline: a 1-D grid over real blocks only, each
    block's partition read from the descriptor."""
    return _pregathered(gathered, val, lrow, bpart, kappa=kappa,
                        rows_pp=rows_pp, nblocks=nblocks, block_p=block_p,
                        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kappa", "rows_pp", "blocks_pp", "block_p", "interpret"),
)
def mttkrp_fused_gather(
    val: jax.Array,        # (S,) nonzero values (0 in pads)
    lrow: jax.Array,       # (S,) local output rows (-1 in pads)
    lidx: jax.Array,       # (N-1, S) input-factor row per slot
    factors: tuple,        # N-1 arrays (I_w, R), kept in ANY/HBM
    *,
    kappa: int,
    rows_pp: int,
    blocks_pp: int,
    block_p: int,
    interpret: bool = False,
) -> jax.Array:
    """EC with the factor gather fused into the kernel grid; returns
    out_rel (kappa*rows_pp, R) without materializing (S, N-1, R) in HBM."""
    nblocks = kappa * blocks_pp
    return _gather(val, lrow, _rect_bpart(nblocks, blocks_pp), lidx,
                   _full_counts(len(factors), nblocks, block_p), None,
                   factors, kappa=kappa, rows_pp=rows_pp, nblocks=nblocks,
                   block_p=block_p, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kappa", "rows_pp", "nblocks", "block_p", "interpret"),
)
def mttkrp_fused_gather_compact(
    val: jax.Array,        # (S,) nonzero values (0 in pads)
    lrow: jax.Array,       # (S,) local output rows (-1 in pads)
    upos: jax.Array,       # (S, N-1) per-slot stage position (0 in pads)
    bpart: jax.Array,      # (nblocks,) block -> partition
    uidx: jax.Array,       # (N-1, S) per-block unique rows
    nuniq: jax.Array,      # (N-1, nblocks) unique counts
    factors: tuple,        # N-1 arrays (I_w, R), kept in ANY/HBM
    *,
    kappa: int,
    rows_pp: int,
    nblocks: int,
    block_p: int,
    interpret: bool = False,
) -> jax.Array:
    """Compact-schedule fused gather with in-block row dedup; returns
    out_rel (kappa*rows_pp, R)."""
    return _gather(val, lrow, bpart, uidx, nuniq, upos, factors,
                   kappa=kappa, rows_pp=rows_pp, nblocks=nblocks,
                   block_p=block_p, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kappa", "rows_pp", "blocks_pp", "block_p", "smax",
                     "next_mode", "interpret"),
)
def mttkrp_fused_remap(
    val: jax.Array,        # (S,) nonzero values (0 in pads)
    idx: jax.Array,        # (S, N) original indices
    alpha: jax.Array,      # (S, N) per-mode slot table (-1 in pads)
    lrow: jax.Array,       # (S,) local output rows (-1 in pads)
    lidx: jax.Array,       # (N-1, S) input-factor row per slot
    factors: tuple,        # N-1 arrays (I_w, R), kept in ANY/HBM
    *,
    kappa: int,
    rows_pp: int,
    blocks_pp: int,
    block_p: int,
    smax: int,
    next_mode: int,
    interpret: bool = False,
):
    """Fused EC + Alg. 3 remap: one Pallas pass returning
    ``(out_rel, nval, nidx, nalpha)`` with the next layout scattered
    in-kernel to the ``alpha[:, next_mode]`` destinations (no separate
    full-``S_max`` XLA scatters, no separate destination stream)."""
    n = idx.shape[1]
    nblocks = kappa * blocks_pp
    assert val.shape[0] <= smax and 0 <= next_mode < n
    return _gather(val, lrow, _rect_bpart(nblocks, blocks_pp), lidx,
                   _full_counts(len(factors), nblocks, block_p), None,
                   factors, kappa=kappa, rows_pp=rows_pp, nblocks=nblocks,
                   block_p=block_p, remap=(smax, n, next_mode), idx=idx,
                   alpha=alpha, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kappa", "rows_pp", "nblocks", "block_p", "smax",
                     "next_mode", "interpret"),
)
def mttkrp_fused_remap_compact(
    val: jax.Array,        # (S,) nonzero values (0 in pads)
    idx: jax.Array,        # (S, N) original indices
    alpha: jax.Array,      # (S, N) per-mode slot table (-1 in pads)
    lrow: jax.Array,       # (S,) local output rows (-1 in pads)
    upos: jax.Array,       # (S, N-1) per-slot stage position (0 in pads)
    bpart: jax.Array,      # (nblocks,) block -> partition
    uidx: jax.Array,       # (N-1, S) per-block unique rows
    nuniq: jax.Array,      # (N-1, nblocks) unique counts
    factors: tuple,        # N-1 arrays (I_w, R), kept in ANY/HBM
    *,
    kappa: int,
    rows_pp: int,
    nblocks: int,
    block_p: int,
    smax: int,
    next_mode: int,
    interpret: bool = False,
):
    """Compact-schedule fused EC + Alg. 3 remap with in-block row dedup;
    one Pallas pass returning ``(out_rel, nval, nidx, nalpha)``."""
    n = idx.shape[1]
    assert val.shape[0] <= smax and 0 <= next_mode < n
    return _gather(val, lrow, bpart, uidx, nuniq, upos, factors,
                   kappa=kappa, rows_pp=rows_pp, nblocks=nblocks,
                   block_p=block_p, remap=(smax, n, next_mode), idx=idx,
                   alpha=alpha, interpret=interpret)
