"""Backend registry for the spMTTKRP elementwise computation (Alg. 2/4).

Replaces the old string-typed ``backend=`` kwarg plumbing: a backend is a
named entry in ``BACKENDS`` selected by ``ExecutionConfig.backend``. Every
backend implements the same contract,

    ec(layout, factors, mode, plan=ModeStatic, config=ExecutionConfig)
        -> out_rel  (plan.relabeled_rows, R) f32

where ``layout`` holds the mode-``mode`` kernel layout slices
(``val (S_d,)``, ``idx (S_d, N)``, ``lrow (S_d,)``, and — when the caller
has it resident, as the engine scan does — ``alpha (S_d, N)``) and the
result lives in relabeled row space (caller un-relabels with the mode's
relabel table). Under the ``compact`` block schedule (``plan.schedule ==
"compact"``) the layout additionally carries the per-mode schedule tables
from ``EngineState.sched``: the ``bpart (nblocks,)`` block->partition
descriptor (required — slot->partition is no longer a fixed stride) and
the in-block dedup tables ``uidx``/``upos``/``nuniq`` consumed by the
fused Pallas pipelines. The same contract serves the single-device scan
(``engine.api``) and the per-device shards under ``shard_map``
(``engine.dist``).

A backend may additionally expose a ``fused_remap`` attribute,

    fused_remap(layout, factors, mode, plan=, config=, smax=, next_mode=)
        -> (out_rel, (nval (smax,), nidx (smax, N), nalpha (smax, N)))

which performs EC *and* the Alg. 3 remap scatter in one kernel pass; the
engine's scan step delegates to it (unless ``config.fuse_remap`` is off)
instead of issuing the XLA scatter of the slot records.

A backend that reads nothing of the layout but ``val`` and ``lrow`` under
the compact schedule declares ``pinned_layout = True``: ``engine.init``
then pins each mode's ``(val, lrow)`` on the device (:func:`pins_layout`)
and no step remaps.

Registered backends:
  ============  =========================================================
  xla           fused segment-sum over the relabeled row space (default);
                segment ids come from the block->partition descriptor
                under the compact schedule, a fixed stride under rect
  pallas        one-hot-MXU Pallas kernel fed by an XLA-materialized
                ``(N-1, R, S)`` HBM gather — the fusion comparison
                baseline (interpret off-TPU). Compact schedule: the 1-D
                descriptor-driven grid (``mttkrp_fused_compact``)
  pallas_fused  zero-HBM-intermediate Pallas pipeline: factor rows are
                gathered *inside* the kernel grid (per-block row lists
                DMA'd into SMEM + double-buffered ANY->VMEM row DMA) and,
                on a rotating layout, the Alg. 3 remap scatter is emitted
                by the same pass via ``fused_remap``. Compact schedule: the
                gather is *dedup-aware* — each block DMAs only its
                ``U <= P`` unique factor rows (plan-sorted
                ``uidx``/``nuniq``) and the EC body routes slots through
                ``upos`` with a one-hot MXU stage select; it reads only
                ``val`` and ``lrow`` of the layout (``pinned_layout``)
  ref           unfused oracle-shaped path: materialize the (S, R)
                Hadamard partials, then segment-sum — the baseline the
                paper's fusion argument (Fig. 7) is measured against
  ============  =========================================================

Every backend serves both block schedules (``plan.schedule``): the
``compact`` grid walks only real blocks (a ``(nblocks,)`` descriptor
names each block's partition), ``rect`` is the padded baseline.
"""
from __future__ import annotations

from typing import Callable, Protocol

import jax
import jax.numpy as jnp
from jax import lax

from .config import ExecutionConfig
from .state import ModeStatic


class ECBackend(Protocol):
    def __call__(self, layout: dict, factors: tuple, mode: int, *,
                 plan: ModeStatic, config: ExecutionConfig) -> jax.Array: ...


BACKENDS: dict[str, ECBackend] = {}

#: Slots per step of the ``xla`` backend's gather-multiply-reduce loop. It
#: bounds the live ``(chunk, R)`` partials, which TPU pads to 128 lanes:
#: 512 MiB per step, where all ~22M slots of a published-size tensor at
#: once would not fit one chip's HBM.
XLA_CHUNK_SLOTS = 1 << 20


def register_backend(name: str) -> Callable[[ECBackend], ECBackend]:
    """Decorator: add an elementwise-computation backend to the registry."""

    def deco(fn: ECBackend) -> ECBackend:
        BACKENDS[name] = fn
        return fn

    return deco


def get_backend(config_or_name: ExecutionConfig | str) -> ECBackend:
    name = (config_or_name.backend
            if isinstance(config_or_name, ExecutionConfig)
            else config_or_name)
    try:
        return BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown engine backend {name!r}; registered: "
            f"{sorted(BACKENDS)}") from None


def pins_layout(config: ExecutionConfig, statics) -> bool:
    """Whether ``engine.init`` pins each mode's ``(val, lrow)``: the
    configured backend declares ``pinned_layout`` and every mode runs the
    compact schedule."""
    return (getattr(get_backend(config), "pinned_layout", False)
            and all(s.schedule == "compact" for s in statics))


# --------------------------------------------------------------------------
# Shared pieces.
# --------------------------------------------------------------------------
def compute_lrow(idx_d, row_relabel_d, rows_pp: int, alive):
    """Local row ids in the owning partition (relabel table lookup)."""
    rel = jnp.take(row_relabel_d, idx_d, axis=0, mode="fill", fill_value=0)
    return jnp.where(alive, rel % rows_pp, -1)


def pack_slots(val, idx, alpha):
    """The Alg. 3 slot record: ``(S, 2N+1)`` int32 holding ``idx``,
    ``alpha`` and the bits of the f32 ``val``, so a remap moves a slot with
    one scatter. (A one-value-per-index scatter over ~22M slots also takes
    XLA:TPU ~25 s to compile; a 2-D record ~2 s.)"""
    return jnp.concatenate(
        [idx, alpha, lax.bitcast_convert_type(val, jnp.int32)[:, None]],
        axis=1)


def empty_slots(size: int, n: int):
    """``size`` pad records: val 0, idx 0, alpha -1."""
    return jnp.concatenate([jnp.zeros((size, n), jnp.int32),
                            jnp.full((size, n), -1, jnp.int32),
                            jnp.zeros((size, 1), jnp.int32)], axis=1)


def unpack_slots(rec):
    """``(val, idx, alpha)`` of a slot record."""
    n = (rec.shape[1] - 1) // 2
    return (lax.bitcast_convert_type(rec[:, 2 * n], jnp.float32),
            rec[:, :n], rec[:, n:2 * n])


def scatter_slots(dst, rec, base):
    """``base.at[dst].set(rec)`` along the slot axis, destinations unique and
    out-of-range ones dropped: the Alg. 3 move of slot records."""
    return base.at[dst].set(rec, mode="drop", unique_indices=True)


def _gather_partials(layout, factors, mode: int, accum_dtype):
    """ell(r) = val * prod_{w != d} Y_w[c_w, r]  (Alg. 2 lines 7-13).

    Pad slots are masked via ``lrow == -1`` rather than relying on their
    ``val`` being zero: pads carry in-bounds ``idx = 0``, so an unmasked
    product would dump ``val * prod Y_w[0]`` into segment 0 (the Pallas
    kernels get this for free from the one-hot comparison).
    """
    val, idx = layout["val"], layout["idx"]
    partials = val[:, None].astype(accum_dtype)
    for w, f in enumerate(factors):
        if w == mode:
            continue
        partials = partials * jnp.take(f, idx[:, w], axis=0, mode="fill",
                                       fill_value=0.0).astype(accum_dtype)
    return jnp.where((layout["lrow"] >= 0)[:, None], partials, 0)


def _segment_ids(layout, plan: ModeStatic):
    """Global relabeled row per slot; pads (lrow == -1) -> dump row 0.

    The owning partition is a fixed slot stride under the ``rect``
    schedule; under ``compact`` it is the block->partition descriptor
    lookup (the layout must carry ``bpart``)."""
    slot = jnp.arange(layout["val"].shape[0], dtype=jnp.int32)
    if plan.schedule == "compact":
        if layout.get("bpart") is None:
            raise KeyError(
                "compact-schedule layout needs the 'bpart' block->partition "
                "descriptor (see EngineState.sched)")
        part = jnp.take(layout["bpart"], slot // plan.block_p, axis=0)
    else:
        part = slot // (plan.blocks_pp * plan.block_p)
    lrow = layout["lrow"]
    return jnp.where(lrow < 0, 0, part * plan.rows_pp + lrow)


# --------------------------------------------------------------------------
# Backends.
# --------------------------------------------------------------------------
@register_backend("xla")
def ec_xla(layout, factors, mode: int, *, plan: ModeStatic,
           config: ExecutionConfig) -> jax.Array:
    """Fused XLA path: gather-multiply feeding segment-sum directly, so the
    (S, R) partials never round-trip HBM as a named intermediate. The slots
    are reduced in chunks of at most ``XLA_CHUNK_SLOTS`` in a loop."""
    dtype = config.accum_dtype()
    gid = _segment_ids(layout, plan)
    s = layout["val"].shape[0]
    c = min(XLA_CHUNK_SLOTS, s)

    def body(i, acc):
        # the last chunk is shifted back to end at S; slots an earlier
        # chunk already summed are masked out as pads
        start = jnp.minimum(i * c, s - c)
        chunk = {k: lax.dynamic_slice_in_dim(layout[k], start, c)
                 for k in ("val", "idx", "lrow")}
        fresh = start + jnp.arange(c) >= i * c
        chunk["lrow"] = jnp.where(fresh, chunk["lrow"], -1)
        partials = _gather_partials(chunk, factors, mode, dtype)
        return acc + jax.ops.segment_sum(
            partials, lax.dynamic_slice_in_dim(gid, start, c),
            num_segments=plan.relabeled_rows)

    r = factors[0].shape[1]
    return lax.fori_loop(0, -(-s // c), body,
                         jnp.zeros((plan.relabeled_rows, r), dtype))


@register_backend("ref")
def ec_ref(layout, factors, mode: int, *, plan: ModeStatic,
           config: ExecutionConfig) -> jax.Array:
    """Unfused baseline: materialize partials, then reduce (paper Fig. 7's
    comparison point; also the oracle for backend parity tests)."""
    partials = _gather_partials(layout, factors, mode, config.accum_dtype())
    partials = jnp.asarray(partials)  # named intermediate, kept live
    gid = _segment_ids(layout, plan)
    return jax.ops.segment_sum(partials, gid,
                               num_segments=plan.relabeled_rows)


@register_backend("pallas")
def ec_pallas(layout, factors, mode: int, *, plan: ModeStatic,
              config: ExecutionConfig) -> jax.Array:
    """Fused Pallas TPU kernel (one-hot MXU segment reduction in VMEM)."""
    from repro.kernels import ops as kops

    gathered = jnp.stack(
        [jnp.take(f, layout["idx"][:, w], axis=0, mode="fill",
                  fill_value=0.0).T
         for w, f in enumerate(factors) if w != mode])  # (N-1, R, S)
    if plan.schedule == "compact":
        return kops.mttkrp_fused_compact(
            gathered,
            layout["val"],
            layout["lrow"],
            layout["bpart"],
            kappa=plan.kappa,
            rows_pp=plan.rows_pp,
            nblocks=plan.nblocks,
            block_p=plan.block_p,
            interpret=config.resolve_interpret(),
        )
    return kops.mttkrp_fused(
        gathered,
        layout["val"],
        layout["lrow"],
        kappa=plan.kappa,
        rows_pp=plan.rows_pp,
        blocks_pp=plan.blocks_pp,
        block_p=plan.block_p,
        interpret=config.resolve_interpret(),
    )


def _fused_lidx(layout, nmodes: int, mode: int):
    """(N-1, S) row table: per slot, the row of each *input*
    factor to gather (pads hold in-bounds 0 — killed later by the one-hot
    / dst < 0, so the garbage gather is harmless)."""
    idx = layout["idx"]
    return jnp.stack([idx[:, w] for w in range(nmodes) if w != mode]
                     ).astype(jnp.int32)


@register_backend("pallas_fused")
def ec_pallas_fused(layout, factors, mode: int, *, plan: ModeStatic,
                    config: ExecutionConfig) -> jax.Array:
    """Zero-HBM-intermediate Pallas pipeline: the factor-row gather happens
    inside the kernel grid (per-block row lists, double-buffered
    ANY->VMEM row DMA), so no ``(N-1, R, S)`` intermediate is ever
    materialized. Under the compact schedule the gather is dedup-aware:
    each block DMAs only its unique factor rows. This entry is the
    plain-EC contract used under ``shard_map`` and on pinned states; the
    single-device scan step of a rotating state upgrades to
    ``fused_remap`` below."""
    from repro.kernels import ops as kops

    inputs = tuple(f for w, f in enumerate(factors) if w != mode)
    if plan.schedule == "compact":
        return kops.mttkrp_fused_gather_compact(
            layout["val"],
            layout["lrow"],
            layout["upos"],
            layout["bpart"],
            layout["uidx"],
            layout["nuniq"],
            inputs,
            kappa=plan.kappa,
            rows_pp=plan.rows_pp,
            nblocks=plan.nblocks,
            block_p=plan.block_p,
            interpret=config.resolve_interpret(),
        )
    return kops.mttkrp_fused_gather(
        layout["val"],
        layout["lrow"],
        _fused_lidx(layout, len(factors), mode),
        inputs,
        kappa=plan.kappa,
        rows_pp=plan.rows_pp,
        blocks_pp=plan.blocks_pp,
        block_p=plan.block_p,
        interpret=config.resolve_interpret(),
    )


def _pallas_fused_remap(layout, factors, mode: int, *, plan: ModeStatic,
                        config: ExecutionConfig, smax: int, next_mode: int):
    """EC + Alg. 3 remap in ONE Pallas pass (see module docstring). The
    remap destinations are ``alpha[:, next_mode]`` verbatim: alive slots
    hold their next-layout slot, pads hold -1 and are skipped in-kernel.
    A compiled (non-interpret) kernel keeps the next layout in VMEM, so a
    plan whose ``S_max`` does not fit raises ``ValueError`` here rather
    than falling back to the XLA scatter."""
    from repro.kernels import ops as kops
    from repro.kernels.mttkrp_kernel import check_fused_remap_fits

    if not config.resolve_interpret():
        check_fused_remap_fits(smax, len(factors), factors[0].shape[1],
                               plan.rows_pp, plan.block_p)
    inputs = tuple(f for w, f in enumerate(factors) if w != mode)
    if plan.schedule == "compact":
        out_rel, nval, nidx, nalpha = kops.mttkrp_fused_remap_compact(
            layout["val"],
            layout["idx"],
            layout["alpha"],
            layout["lrow"],
            layout["upos"],
            layout["bpart"],
            layout["uidx"],
            layout["nuniq"],
            inputs,
            kappa=plan.kappa,
            rows_pp=plan.rows_pp,
            nblocks=plan.nblocks,
            block_p=plan.block_p,
            smax=smax,
            next_mode=next_mode,
            interpret=config.resolve_interpret(),
        )
        return out_rel, (nval, nidx, nalpha)
    out_rel, nval, nidx, nalpha = kops.mttkrp_fused_remap(
        layout["val"],
        layout["idx"],
        layout["alpha"],
        layout["lrow"],
        _fused_lidx(layout, len(factors), mode),
        inputs,
        kappa=plan.kappa,
        rows_pp=plan.rows_pp,
        blocks_pp=plan.blocks_pp,
        block_p=plan.block_p,
        smax=smax,
        next_mode=next_mode,
        interpret=config.resolve_interpret(),
    )
    return out_rel, (nval, nidx, nalpha)


ec_pallas_fused.fused_remap = _pallas_fused_remap
# engine.init builds the per-mode dedup tables (EngineState.sched) only
# for backends that declare they consume them.
ec_pallas_fused.needs_dedup = True
# Under the compact schedule the kernel reads only val and lrow of the
# layout (the rect one reads idx, through _fused_lidx).
ec_pallas_fused.pinned_layout = True


__all__ = ["BACKENDS", "register_backend", "get_backend", "pins_layout",
           "compute_lrow", "pack_slots", "empty_slots", "unpack_slots",
           "scatter_slots", "ec_xla", "ec_ref", "ec_pallas", "ec_pallas_fused"]
