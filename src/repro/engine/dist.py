"""Distributed spMTTKRP engine: sharded ``EngineState`` under ``shard_map``.

Cluster-scope version of the paper's Observation 2 on top of the functional
engine (:mod:`repro.engine.api`): partitions — and hence the output rows
they own — are dealt to devices along the mesh's ``data`` axis, so the
elementwise computation needs NO cross-device reduction; each device
segment-sums into rows it exclusively owns. The rank dimension may
optionally shard over ``model`` (MTTKRP is embarrassingly parallel over
rank).

The dynamic remap (Alg. 3) becomes a *static* cross-device permutation:
which element moves from which device to which is fixed by the FLYCOO
plans, so the exchange is precomputed host-side into an
:class:`ExchangeSchedule` and executed as a ``collective_permute``
round-robin — hop ``h`` sends a bounded buffer from every device ``k`` to
device ``(k + h) % n_dev`` — instead of the baseline ``all_gather`` of the
full element list (kept as ``DistConfig(exchange="all_gather")`` for
comparison). AMPED (arXiv:2507.15121) and load-balanced spMTTKRP
(arXiv:1904.03329) both identify this exchange, not the compute, as the
multi-GPU bottleneck.

Sharded layout numbering
------------------------
A :class:`DistState` stores the layout in *device-major* slot numbering:
device ``k`` owns global slots ``[k * S_loc, (k+1) * S_loc)`` where
``S_loc = max_d S_d_loc``, and within a device the mode-``d`` layout
occupies the first ``S_d_loc`` local slots — its ``kappa_d / n_dev``
contiguous partitions' blocks, laid out by the mode's block schedule.
Under the ``rect`` schedule ``S_d_loc = S_d / n_dev`` exactly; under
``compact`` each device's real block count differs (partitions are
nnz-balanced, not block-identical), so ``S_d_loc`` is the max device's
block count and shorter devices carry trailing all-pad blocks (dead
slots, descriptor repeating the last real partition). This requires every
mode's ``kappa`` to be a multiple of ``n_dev`` — build tensors with
:func:`repro.core.distributed.build_sharded_flycoo` or pick partition
counts via :meth:`ExecutionConfig.kappa_for`.

Public surface:

  DistConfig                            frozen mesh-axis/exchange policy
  shard_state(state, mesh[, dist])      EngineState -> DistState (host, once)
  dist_mttkrp(dstate, factors)          one mode + exchange, one dispatch
  dist_all_modes(dstate, factors)       whole rotation: ONE jitted lax.scan
                                        inside shard_map (fold hook as in
                                        ``engine.all_modes`` -> distributed
                                        CPD-ALS sweeps are single programs)
  schedule_for_plans / exchange_bytes   host-side schedule + traffic model
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.metrics import gauge as _obs_gauge
from repro.obs.trace import span
from repro.resilience import chaos as _chaos
from repro.sharding import ShardingCtx

from .api import _JIT_CACHE, DISPATCH_COUNTS, TRACE_COUNTS, FoldFn
from .backends import (compute_lrow, empty_slots, get_backend, pack_slots,
                       scatter_slots, unpack_slots)
from .config import ExecutionConfig
from .state import EngineState, ModeSched, ModeStatic


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


EXCHANGES = ("permute", "all_gather")


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Static distribution policy (hashable; part of the jit cache key).

    Attributes:
      data_axis: mesh axis partitions/rows/slots shard over.
      model_axis: optional mesh axis the factor rank dim shards over
        (incompatible with a ``fold`` hook — grams need the full rank).
      exchange: remap exchange strategy — ``"permute"`` runs the
        precomputed collective_permute schedule, ``"all_gather"`` the
        baseline full-element-list gather + scatter-slice.
      pad_hop: per-hop buffer slot counts round up to this multiple.
    """

    data_axis: str = "data"
    model_axis: str | None = None
    exchange: str = "permute"
    pad_hop: int = 8

    def __post_init__(self):
        if self.exchange not in EXCHANGES:
            raise ValueError(
                f"exchange {self.exchange!r} not in {EXCHANGES}")
        if self.pad_hop < 1:
            raise ValueError("pad_hop must be >= 1")


# --------------------------------------------------------------------------
# Static exchange schedule (host-side, derived from the FLYCOO plans).
# --------------------------------------------------------------------------
class ExchangeSchedule(NamedTuple):
    """Per-(mode -> next mode) transition, per round-robin hop, the padded
    slot capacity of the send buffer. ``hops[d][h-1]`` bounds how many
    elements any device sends to its ``+h``-neighbour while remapping the
    mode-``d`` layout into mode ``d+1``. Static truth derived from the
    plans — the traced exchange cannot overflow it."""

    n_dev: int
    hops: tuple[tuple[int, ...], ...]

    def permute_slots(self, d: int) -> int:
        """Total send-buffer slots one device uses for transition ``d``."""
        return sum(self.hops[d])


def row_bytes(nmodes: int) -> int:
    """Wire bytes per element row: val f32 + idx i32*N + alpha i32*N."""
    return 4 * (1 + 2 * nmodes)


def _schedule_from_devs(devs_by_mode: Sequence[np.ndarray], n_dev: int,
                        pad_hop: int) -> ExchangeSchedule:
    """Build the schedule from each element's owning device in every mode."""
    n = len(devs_by_mode)
    hops = []
    for d in range(n):
        src, dst = devs_by_mode[d], devs_by_mode[(d + 1) % n]
        counts = np.bincount(src * n_dev + dst,
                             minlength=n_dev * n_dev).reshape(n_dev, n_dev)
        per_hop = []
        for h in range(1, n_dev):
            cap = int(max(counts[k, (k + h) % n_dev] for k in range(n_dev)))
            if cap:
                cap = ((cap + pad_hop - 1) // pad_hop) * pad_hop
            per_hop.append(cap)
        hops.append(tuple(per_hop))
    return ExchangeSchedule(n_dev=n_dev, hops=tuple(hops))


def element_devices(plan, n_dev: int) -> np.ndarray:
    """(nnz,) owning device per element for a ``ModePlan`` sharded over
    ``n_dev`` devices: device ``k`` owns partitions
    ``[k*kappa/n_dev, (k+1)*kappa/n_dev)``. Schedule-agnostic — the
    partition comes from the block->partition descriptor, which under
    ``rect`` degenerates to the fixed slot stride."""
    if plan.kappa % n_dev != 0:
        raise ValueError(
            f"mode-{plan.mode} kappa {plan.kappa} not divisible by "
            f"n_dev {n_dev}; build with kappa_for / build_sharded_flycoo")
    part = plan.block_part[plan.slot_of_elem // plan.block_p]
    return (part // (plan.kappa // n_dev)).astype(np.int64)


def schedule_for_plans(plans, n_dev: int,
                       pad_hop: int = 8) -> ExchangeSchedule:
    """Exchange schedule for a tensor's ``ModePlan`` list (host-only; needs
    no devices — used by benchmarks to model traffic at any scale)."""
    return _schedule_from_devs([element_devices(p, n_dev) for p in plans],
                               n_dev, pad_hop)


# --------------------------------------------------------------------------
# Device-major block geometry (host-side, schedule-aware).
# --------------------------------------------------------------------------
def _block_geometry(static: ModeStatic, bpart: np.ndarray, n_dev: int):
    """Per-mode block geometry under device-major sharding.

    Returns ``(kappa_loc, blocks_per_dev, dev_first_block, nblocks_loc)``:
    device ``k`` owns partitions ``[k*kappa_loc, (k+1)*kappa_loc)`` whose
    blocks are contiguous (partition-major layout) starting at global
    block ``dev_first_block[k]``; every device's local layout is padded to
    ``nblocks_loc = max blocks_per_dev`` blocks.
    """
    kappa_loc = static.kappa // n_dev
    part_blocks = np.bincount(bpart, minlength=static.kappa)
    blocks_per_dev = part_blocks.reshape(n_dev, kappa_loc).sum(axis=1)
    dev_first_block = np.concatenate([[0], np.cumsum(blocks_per_dev)])[:-1]
    return kappa_loc, blocks_per_dev, dev_first_block, int(
        blocks_per_dev.max())


def _local_static(static: ModeStatic, nblocks_loc: int,
                  n_dev: int) -> ModeStatic:
    """The per-device ``ModeStatic`` (kappa_loc partitions, padded-uniform
    local block count)."""
    return ModeStatic(kappa=static.kappa // n_dev, rows_pp=static.rows_pp,
                      blocks_pp=static.blocks_pp, block_p=static.block_p,
                      dim=static.dim, nblocks=nblocks_loc,
                      schedule=static.schedule)


def _local_sched(ms: ModeSched, static: ModeStatic, geom,
                 n_dev: int) -> ModeSched:
    """Device-major re-layout of one mode's schedule tables: each device's
    block run is sliced out and padded to the uniform local block count.
    Pad blocks repeat the last real partition id (so the descriptor stays
    nondecreasing and never re-triggers a tile init) and carry zeroed
    dedup tables (``nuniq = 0`` -> the kernel issues no DMAs for them)."""
    kappa_loc, blocks_per_dev, dev_first_block, nblocks_loc = geom
    p = static.block_p
    sloc = nblocks_loc * p
    bp = np.asarray(ms.bpart)
    lbp = np.empty((n_dev, nblocks_loc), dtype=np.int32)
    for k in range(n_dev):
        nb = int(blocks_per_dev[k])
        seg = bp[dev_first_block[k]:dev_first_block[k] + nb] - k * kappa_loc
        lbp[k, :nb] = seg
        lbp[k, nb:] = seg[-1] if nb else kappa_loc - 1
    out = {"bpart": jnp.asarray(lbp.reshape(-1))}
    if ms.uidx is not None:
        nm1 = ms.uidx.shape[0]
        uidx = np.asarray(ms.uidx)
        upos = np.asarray(ms.upos)
        nuniq = np.asarray(ms.nuniq)
        luidx = np.zeros((nm1, n_dev * sloc), dtype=np.int32)
        lupos = np.zeros((n_dev * sloc, nm1), dtype=np.int32)
        lnuniq = np.zeros((nm1, n_dev * nblocks_loc), dtype=np.int32)
        for k in range(n_dev):
            nb = int(blocks_per_dev[k])
            g0 = int(dev_first_block[k])
            luidx[:, k * sloc:k * sloc + nb * p] = \
                uidx[:, g0 * p:(g0 + nb) * p]
            lupos[k * sloc:k * sloc + nb * p] = upos[g0 * p:(g0 + nb) * p]
            lnuniq[:, k * nblocks_loc:k * nblocks_loc + nb] = \
                nuniq[:, g0:g0 + nb]
        out.update(uidx=jnp.asarray(luidx), upos=jnp.asarray(lupos),
                   nuniq=jnp.asarray(lnuniq))
    return ModeSched(**out)


def exchange_bytes(schedule: ExchangeSchedule, nmodes: int,
                   slocs: Sequence[int]) -> list[dict]:
    """Per-device wire traffic of one full rotation, per mode transition:
    the collective_permute schedule vs the all_gather baseline. ``slocs``
    is the per-mode local padded slot count ``S_d / n_dev`` — the baseline
    gathers each remote device's mode-``d`` element list, so transition
    ``d`` ships ``(n_dev - 1) * slocs[d]`` rows per device."""
    rb = row_bytes(nmodes)
    out = []
    for d in range(len(schedule.hops)):
        out.append({
            "mode": d,
            "permute_bytes": schedule.permute_slots(d) * rb,
            "all_gather_bytes": (schedule.n_dev - 1) * slocs[d] * rb,
        })
    return out


# --------------------------------------------------------------------------
# DistState: the sharded EngineState.
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DistState:
    """Immutable sharded engine state (device-major slot numbering).

    Array leaves mirror :class:`~repro.engine.state.EngineState` but hold
    *global* arrays placed over the mesh: ``val (n_dev*S_loc,)``,
    ``idx/alpha (n_dev*S_loc, N)`` sharded along the ``data`` axis, the
    replicated per-mode ``relabel`` tables, and the per-mode ``sched``
    block-schedule tables in device-major layout (sharded so every device
    holds its local descriptor/dedup slices). ``alpha`` entries are in the
    device-major dist numbering (see module docstring), so remap
    destinations encode both target device and target local slot.
    ``lstatics`` holds each mode's *per-device* plan constants
    (``kappa/n_dev`` partitions, padded-uniform local block count).
    """

    val: jax.Array
    idx: jax.Array
    alpha: jax.Array
    relabel: tuple[jax.Array, ...]
    sched: tuple[ModeSched, ...]
    mode: int
    dims: tuple[int, ...]
    statics: tuple[ModeStatic, ...]
    lstatics: tuple[ModeStatic, ...]
    config: ExecutionConfig
    dist: DistConfig
    n_dev: int
    schedule: ExchangeSchedule
    mesh: Mesh

    # ------------------------------------------------------------ derived
    @property
    def nmodes(self) -> int:
        return len(self.dims)

    @property
    def slocs(self) -> tuple[int, ...]:
        """Per-mode local padded slot counts ``S_d_loc``."""
        return tuple(s.padded_nnz for s in self.lstatics)

    @property
    def smax_loc(self) -> int:
        """Per-device slot count (max over per-mode local padded sizes)."""
        return max(self.slocs)

    @property
    def imax(self) -> int:
        return max(self.dims)

    def aux_key(self):
        return (self.mode, self.dims, self.statics, self.lstatics,
                self.config, self.dist, self.n_dev, self.schedule,
                self.mesh)

    def replace(self, **kw) -> "DistState":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------- pytree
    def tree_flatten(self):
        children = (self.val, self.idx, self.alpha, self.relabel,
                    self.sched)
        # aux IS the jit-cache key: one definition, no drift between what
        # forces a retrace and what keys the _JIT_CACHE programs.
        return children, self.aux_key()

    @classmethod
    def tree_unflatten(cls, aux, children):
        val, idx, alpha, relabel, sched = children
        (mode, dims, statics, lstatics, config, dist, n_dev, schedule,
         mesh) = aux
        return cls(val=val, idx=idx, alpha=alpha, relabel=tuple(relabel),
                   sched=tuple(sched), mode=mode, dims=dims,
                   statics=statics, lstatics=lstatics, config=config,
                   dist=dist, n_dev=n_dev, schedule=schedule, mesh=mesh)


# --------------------------------------------------------------------------
# shard_state: place an EngineState over the mesh.
# --------------------------------------------------------------------------
def shard_state(state: EngineState, mesh: Mesh | ShardingCtx,
                dist: DistConfig | None = None) -> DistState:
    """Shard a single-device :class:`EngineState` over ``mesh``'s data axis.

    ``mesh`` may be a raw :class:`jax.sharding.Mesh` or a
    :class:`repro.sharding.ShardingCtx` — with a ctx (and no explicit
    ``dist``) the data/model axes follow the ctx's dp/tp convention.

    Renumbers every mode layout into device-major slots, precomputes the
    collective_permute :class:`ExchangeSchedule` from the alpha tables, and
    ``device_put``s the arrays with the matching ``NamedSharding``s.
    Requires every mode's ``kappa`` to be a multiple of the data-axis size
    (see :meth:`ExecutionConfig.kappa_for`), and the rotating layout
    (``engine.init(..., _rotating=True)``): a pinned state holds no alpha
    tables to renumber.
    """
    if state.pinned is not None:
        raise ValueError(
            "shard_state re-lays the rotating (val, idx, alpha) layout; "
            "build the state with engine.init(..., _rotating=True)")
    if isinstance(mesh, ShardingCtx):
        ctx, mesh = mesh, mesh.mesh
        if dist is None:
            dist = DistConfig(data_axis=ctx.data_axis,
                              model_axis=ctx.tp_axis)
    dist = dist or DistConfig()
    if dist.data_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {dist.data_axis!r}: "
                         f"{mesh.axis_names}")
    n_dev = mesh.shape[dist.data_axis]
    for s in state.statics:
        if s.kappa % n_dev != 0:
            raise ValueError(
                f"kappa {s.kappa} not divisible by n_dev {n_dev}; build "
                "the tensor with ExecutionConfig.kappa_for(dim, n_dev) "
                "(e.g. via core.distributed.build_sharded_flycoo)")

    n, m0 = state.nmodes, state.mode
    with span("dist.shard_state", n_dev=int(n_dev), nmodes=n):
        statics = state.statics
        with span("dist.renumber"):
            geoms = [_block_geometry(statics[d],
                                     np.asarray(state.sched[d].bpart),
                                     n_dev) for d in range(n)]
            lstatics = tuple(_local_static(statics[d], geoms[d][3], n_dev)
                             for d in range(n))
            slocs = [ls.padded_nnz for ls in lstatics]
            smax_loc = max(slocs)
            total = n_dev * smax_loc

            alpha = np.asarray(state.alpha)
            alive = alpha[:, m0] >= 0
            slots = alpha[alive].astype(np.int64)   # (nnz, n) per-mode slots
            # device-major renumbering: each device's contiguous block run
            # starts at local slot 0 ->
            # dslot = dev * smax_loc + (slot - first slot of dev)
            dslots = np.empty_like(slots)
            devs = np.empty_like(slots)
            for d in range(n):
                _, blocks_per_dev, dev_first_block, _ = geoms[d]
                p = statics[d].block_p
                dev_of_block = np.repeat(np.arange(n_dev), blocks_per_dev)
                dev = dev_of_block[slots[:, d] // p]
                dslots[:, d] = (dev * smax_loc + slots[:, d]
                                - dev_first_block[dev] * p)
                devs[:, d] = dev
        with span("dist.exchange_schedule"):
            schedule = _schedule_from_devs([devs[:, d] for d in range(n)],
                                           n_dev, dist.pad_hop)
            wire = _obs_gauge("dist_exchange_bytes",
                             "permute wire bytes per mode transition")
            for hop in exchange_bytes(schedule, n, slocs):
                wire.set(f"mode{hop['mode']}", hop["permute_bytes"])

        pos = dslots[:, m0]
        val = np.zeros(total, dtype=np.float32)
        idx = np.zeros((total, n), dtype=np.int32)
        nalpha = np.full((total, n), -1, dtype=np.int32)
        val[pos] = np.asarray(state.val)[alive]
        idx[pos] = np.asarray(state.idx)[alive]
        nalpha[pos] = dslots.astype(np.int32)

        da = dist.data_axis
        sh1 = NamedSharding(mesh, P(da))
        sh2 = NamedSharding(mesh, P(da, None))
        rep = NamedSharding(mesh, P())
        with span("dist.device_place"):
            sched = tuple(
                _place_sched(_local_sched(state.sched[d], statics[d],
                                          geoms[d], n_dev), mesh, da)
                for d in range(n))
            return DistState(
                val=jax.device_put(jnp.asarray(val), sh1),
                idx=jax.device_put(jnp.asarray(idx), sh2),
                alpha=jax.device_put(jnp.asarray(nalpha), sh2),
                relabel=tuple(jax.device_put(r, rep)
                              for r in state.relabel),
                sched=sched,
                mode=m0, dims=state.dims, statics=statics,
                lstatics=lstatics, config=state.config, dist=dist,
                n_dev=n_dev, schedule=schedule, mesh=mesh)


def _sched_pspecs(ms: ModeSched, da: str) -> ModeSched:
    """Partition specs matching one mode's device-major schedule tables."""
    return ModeSched(
        bpart=P(da),
        uidx=None if ms.uidx is None else P(None, da),
        upos=None if ms.upos is None else P(da, None),
        nuniq=None if ms.nuniq is None else P(None, da))


def _place_sched(ms: ModeSched, mesh: Mesh, da: str) -> ModeSched:
    specs = _sched_pspecs(ms, da)
    return ModeSched(*(None if x is None
                       else jax.device_put(x, NamedSharding(mesh, s))
                       for x, s in zip(ms, specs)))


# --------------------------------------------------------------------------
# Per-device exchange kernels (run inside shard_map).
# --------------------------------------------------------------------------
def _exchange_permute(v, ix, al, alive, *, nxt, hops, smax_loc, n_dev, da,
                      nmodes):
    """Static round-robin: hop ``h`` ships a bounded buffer to the ``+h``
    neighbour via collective_permute; local moves scatter directly."""
    me = lax.axis_index(da)
    dstg = al[:, nxt]                       # global dist slot (-1 dead)
    dst_dev = dstg // smax_loc              # floor div: dead -> -1
    mine = alive & (dst_dev == me)
    dst = jnp.where(mine, dstg % smax_loc, smax_loc)
    rec = pack_slots(v, ix, al)
    nrec = scatter_slots(dst, rec, empty_slots(smax_loc, nmodes))

    for h in range(1, n_dev):
        cap = hops[h - 1]
        if cap == 0:    # statically empty hop: no collective at all
            continue
        sel = alive & (dst_dev == (me + h) % n_dev)
        # pack outgoing elements densely (distinct slots; the rest are
        # dropped); schedule guarantees fit <= cap
        bpos = jnp.where(sel, jnp.cumsum(sel) - 1, cap)
        buf = scatter_slots(bpos, rec, empty_slots(cap, nmodes))
        perm = [(k, (k + h) % n_dev) for k in range(n_dev)]
        rbuf = lax.ppermute(buf, da, perm)
        rdst = rbuf[:, nmodes + nxt]        # arrivals all target me
        rloc = jnp.where(rdst >= 0, rdst % smax_loc, smax_loc)
        nrec = scatter_slots(rloc, rbuf, nrec)
    return unpack_slots(nrec)


def _exchange_all_gather(v, ix, al, alive, *, d, nxt, smax_loc, n_dev, da,
                         nmodes):
    """Baseline (pre-engine ``DistributedMTTKRP``): gather the FULL element
    list on every device, scatter into the whole next layout, keep the
    local slice. O(n_dev * nnz) wire traffic per transition."""
    del alive
    total = n_dev * smax_loc
    rec = lax.all_gather(pack_slots(v, ix, al), da, tiled=True)
    ag = rec[:, nmodes:2 * nmodes]
    dst = jnp.where(ag[:, d] >= 0, ag[:, nxt], total)
    nrec = scatter_slots(dst, rec, empty_slots(total, nmodes))
    me = lax.axis_index(da)
    return unpack_slots(lax.dynamic_slice_in_dim(
        nrec, me * smax_loc, smax_loc, axis=0))


# --------------------------------------------------------------------------
# One mode on one device: local EC + output gather + remap exchange.
# --------------------------------------------------------------------------
def _dist_mode_branch(d: int, *, statics: Sequence[ModeStatic],
                      lstatics: Sequence[ModeStatic], n_dev: int,
                      smax_loc: int, schedule: ExchangeSchedule,
                      config: ExecutionConfig, dist: DistConfig,
                      fold: FoldFn | None, pad_out_to: int | None):
    """Traced per-device step for (static) mode ``d``; same contract as the
    single-device ``engine.api._mode_branch`` but over local shards."""
    s = statics[d]
    n = len(statics)
    nxt = (d + 1) % n
    lplan = lstatics[d]
    sloc = lplan.padded_nnz
    backend = get_backend(config)
    da = dist.data_axis

    def step(layout3, relabels, sched, factors, carry):
        val, idx, alpha = layout3           # local (smax_loc, ...) shards
        v, ix, al = val[:sloc], idx[:sloc], alpha[:sloc]
        alive = al[:, d] >= 0
        # EC over owned partitions only (Obs. 2: rows owned exclusively,
        # so the segment-sum needs no cross-device reduction). Backends see
        # the exact same contract as the single-device scan; fusing
        # backends (``pallas_fused``) run their plain-EC entry here — the
        # remap is the cross-device exchange below, not a local scatter —
        # so the in-kernel gather fusion (incl. the compact schedule's
        # in-block dedup) still applies per shard.
        lrow = compute_lrow(ix[:, d], relabels[d], s.rows_pp, alive)
        out_rel_loc = backend({"val": v, "idx": ix, "alpha": al,
                               "lrow": lrow, **sched[d]._asdict()},
                              tuple(factors), d, plan=lplan, config=config)
        # Devices own contiguous relabeled-row ranges (kappa % n_dev == 0),
        # so a tiled output gather IS the global relabeled result. This is
        # rows x R — small — not the element list.
        out_rel = lax.all_gather(out_rel_loc, da, tiled=True)
        out = jnp.take(out_rel, relabels[d], axis=0)
        if fold is not None:
            factors, carry = fold(d, out, factors, carry)
        if pad_out_to is not None:
            out = jnp.pad(out, ((0, pad_out_to - s.dim), (0, 0)))

        if dist.exchange == "permute":
            nl = _exchange_permute(v, ix, al, alive, nxt=nxt,
                                   hops=schedule.hops[d],
                                   smax_loc=smax_loc, n_dev=n_dev, da=da,
                                   nmodes=n)
        else:
            nl = _exchange_all_gather(v, ix, al, alive, d=d, nxt=nxt,
                                      smax_loc=smax_loc, n_dev=n_dev,
                                      da=da, nmodes=n)
        return nl, out, factors, carry

    return step


# --------------------------------------------------------------------------
# Program builders (shard_map-wrapped; pre-jit for lowering inspection).
# --------------------------------------------------------------------------
def _specs(dstate: DistState, fold: FoldFn | None):
    da, ma = dstate.dist.data_axis, dstate.dist.model_axis
    if fold is not None and ma is not None:
        raise ValueError("fold needs the full rank on every device; use "
                         "model_axis=None when folding (e.g. CPD-ALS)")
    layout_specs = (P(da), P(da, None), P(da, None))
    fac_spec = P(None, ma) if ma else P(None, None)
    sched_specs = tuple(_sched_pspecs(ms, da) for ms in dstate.sched)
    in_specs = (layout_specs, P(), sched_specs, fac_spec, P())
    return layout_specs, fac_spec, in_specs


def _build_dist_scan(dstate: DistState, fold: FoldFn | None):
    """The whole mode rotation as one ``lax.scan`` on every device, wrapped
    in shard_map. Captures only static aux, never the caller's arrays."""
    n, m0, imax = dstate.nmodes, dstate.mode, dstate.imax
    dims, smax_loc = dstate.dims, dstate.smax_loc
    seq = tuple((m0 + i) % n for i in range(n))
    branches = [
        _dist_mode_branch(d, statics=dstate.statics,
                          lstatics=dstate.lstatics, n_dev=dstate.n_dev,
                          smax_loc=smax_loc, schedule=dstate.schedule,
                          config=dstate.config, dist=dstate.dist,
                          fold=fold, pad_out_to=imax)
        for d in range(n)
    ]
    layout_specs, fac_spec, in_specs = _specs(dstate, fold)

    def local_run(layout3, relabels, sched, factors, carry):
        TRACE_COUNTS["dist_all_modes"] += 1  # trace-time side effect

        def body(sc, mode_t):
            layout3, factors, carry = sc
            nl, out, factors, carry = lax.switch(
                mode_t,
                [lambda l3, f, c, b=b: b(l3, relabels, sched, f, c)
                 for b in branches],
                layout3, factors, carry)
            return (nl, factors, carry), out

        (layout3, factors, carry), outs = lax.scan(
            body, (layout3, factors, carry),
            jnp.asarray(seq, dtype=jnp.int32))
        by_mode = tuple(outs[seq.index(d)][: dims[d]] for d in range(n))
        return layout3, by_mode, factors, carry

    out_specs = (layout_specs, fac_spec, fac_spec, P())
    return shard_map(local_run, dstate.mesh, in_specs, out_specs)


def _build_dist_step(dstate: DistState):
    """Single-mode program: EC + exchange for the resident mode only."""
    d = dstate.mode
    step = _dist_mode_branch(d, statics=dstate.statics,
                             lstatics=dstate.lstatics, n_dev=dstate.n_dev,
                             smax_loc=dstate.smax_loc,
                             schedule=dstate.schedule, config=dstate.config,
                             dist=dstate.dist, fold=None, pad_out_to=None)
    layout_specs, fac_spec, in_specs = _specs(dstate, None)

    def local_run(layout3, relabels, sched, factors, carry):
        TRACE_COUNTS["dist_mttkrp"] += 1  # trace-time side effect
        nl, out, _, _ = step(layout3, relabels, sched, factors, carry)
        return nl, out

    return shard_map(local_run, dstate.mesh, in_specs,
                     (layout_specs, fac_spec))


# --------------------------------------------------------------------------
# Public execution API.
# --------------------------------------------------------------------------
def _place_operands(dstate: DistState, factors, carry, fold):
    """Commit factors and carry to the shardings the program hands them
    back with, so the first sweep (host-initialized factors) and every
    later one (mesh-resident outputs) share one trace."""
    _, fac_spec, _ = _specs(dstate, fold)
    fac_sh = NamedSharding(dstate.mesh, fac_spec)
    rep = NamedSharding(dstate.mesh, P())
    factors = tuple(jax.device_put(f, fac_sh) for f in factors)
    carry = jax.tree.map(lambda x: jax.device_put(x, rep), carry)
    return factors, carry


def _gate_dispatch(dstate: DistState, policy, what: str):
    """Run the chaos hook for one dist dispatch, retrying *transient*
    failures with the same policy-driven backoff stream uploads use.
    Non-transient faults (exchange, device loss, compile) propagate to
    the caller's ladder. Yields nothing; returns after the gate passes.
    """
    attempt = 0
    while True:
        _c = _chaos.active()
        if _c is None:
            return
        try:
            _c.on_dist_dispatch(dstate.config.backend,
                                exchange=dstate.dist.exchange,
                                n_dev=int(dstate.n_dev), attempt=attempt)
            return
        except Exception as exc:
            from repro.resilience.ladder import (backoff_delay, classify,
                                                 record_retry)
            if policy is None or classify(exc) != "transient" \
                    or attempt >= policy.max_retries:
                raise
            record_retry("dist.dispatch", attempt,
                         backoff_delay(policy, attempt,
                                       token=(what, dstate.mode)),
                         kind="dist")
            attempt += 1


def dist_mttkrp(dstate: DistState, factors: Sequence[jax.Array], *,
                policy=None):
    """MTTKRP for the resident mode + cross-device remap exchange; returns
    ``(out, next_dstate)`` with ``out`` of shape ``(dims[mode], R)``."""
    key = ("dist_mttkrp", dstate.aux_key())
    fn = _JIT_CACHE.get(key)
    if fn is None:
        donate = (0,) if dstate.config.resolve_donate() else ()
        fn = _JIT_CACHE[key] = jax.jit(_build_dist_step(dstate),
                                       donate_argnums=donate)
    _gate_dispatch(dstate, policy, "dist_mttkrp")
    factors, _ = _place_operands(dstate, factors, None, None)
    DISPATCH_COUNTS["dist_mttkrp"] += 1
    with span("engine.dispatch", kind="dist_mttkrp", mode=dstate.mode,
              n_dev=int(dstate.n_dev)):
        (nval, nidx, nalpha), out = fn(
            (dstate.val, dstate.idx, dstate.alpha), dstate.relabel,
            dstate.sched, factors, None)
    nxt = (dstate.mode + 1) % dstate.nmodes
    return out, dstate.replace(val=nval, idx=nidx, alpha=nalpha, mode=nxt)


def dist_all_modes(dstate: DistState, factors: Sequence[jax.Array], *,
                   fold: FoldFn | None = None, carry=None, policy=None):
    """Distributed spMTTKRP along all modes: ONE jitted ``lax.scan`` under
    ``shard_map``, starting from any resident mode, with the sharded layout
    as (donation-ready) carry. Same contract as ``engine.all_modes``:
    without ``fold`` returns ``(outs, next_dstate)``; with ``fold`` (a
    stable module-level callable) returns
    ``(outs, next_dstate, factors, carry)`` — which is how distributed
    CPD-ALS sweeps stay single traced programs. ``policy`` (a
    ``LadderPolicy``) retries transient dispatch failures in place; other
    fault kinds propagate to the caller's ladder rungs."""
    key = ("dist_all_modes", dstate.aux_key(), fold)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        donate = (0,) if dstate.config.resolve_donate() else ()
        fn = _JIT_CACHE[key] = jax.jit(_build_dist_scan(dstate, fold),
                                       donate_argnums=donate)
    _gate_dispatch(dstate, policy, "dist_all_modes")
    factors, carry = _place_operands(dstate, factors, carry, fold)
    DISPATCH_COUNTS["dist_all_modes"] += 1
    with span("engine.dispatch", kind="dist_all_modes",
              start_mode=dstate.mode, n_dev=int(dstate.n_dev)):
        layout3, outs, out_factors, out_carry = fn(
            (dstate.val, dstate.idx, dstate.alpha), dstate.relabel,
            dstate.sched, factors, carry)
    nval, nidx, nalpha = layout3
    next_state = dstate.replace(val=nval, idx=nidx, alpha=nalpha)
    if fold is None:
        return list(outs), next_state
    return list(outs), next_state, list(out_factors), out_carry


def surviving_mesh(mesh: Mesh, lost: int, kappas: Sequence[int],
                   data_axis: str = "data") -> Mesh:
    """The largest viable 1-D data mesh after ``lost`` devices die.

    Simulated/elastic device loss drops the highest-ordinal devices; the
    survivor count is then rounded *down* to the largest ``n`` that
    divides every mode's partition count (``build_sharded_flycoo`` sizes
    kappa as a multiple of the original device count, so halving always
    works). Raises when nothing viable remains — losing the whole mesh is
    not a rung, it is an outage.
    """
    devices = list(np.asarray(mesh.devices).reshape(-1))
    alive = devices[:len(devices) - int(lost)]
    n = len(alive)
    while n >= 1 and any(int(k) % n for k in kappas):
        n -= 1
    if n < 1:
        raise RuntimeError(
            f"no viable mesh after losing {lost} of {len(devices)} "
            f"device(s) (kappas {tuple(int(k) for k in kappas)})")
    return Mesh(np.asarray(alive[:n]), (data_axis,))


def lowered_text(dstate: DistState, factors: Sequence[jax.Array], *,
                 fold: FoldFn | None = None, carry=None) -> str:
    """StableHLO of the dist_all_modes program (acceptance: the permute
    exchange lowers to collective_permute with no element-list all_gather)."""
    fn = _build_dist_scan(dstate, fold)
    return jax.jit(fn).lower(
        (dstate.val, dstate.idx, dstate.alpha), dstate.relabel,
        dstate.sched, tuple(factors), carry).as_text()


__all__ = ["DistConfig", "DistState", "ExchangeSchedule", "shard_state",
           "dist_mttkrp", "dist_all_modes", "schedule_for_plans",
           "element_devices", "exchange_bytes", "row_bytes", "lowered_text",
           "surviving_mesh", "EXCHANGES"]
