"""Out-of-core streaming spMTTKRP engine: tensors larger than device memory.

The resident engine (:mod:`repro.engine.api`) keeps the whole FLYCOO
element list on device. This subsystem keeps it on the HOST and streams
partition-aligned *chunks* of each mode's block schedule through the
device, double-buffered: while chunk ``k`` runs the elementwise
computation, chunk ``k+1`` is already uploading (async ``jax.device_put``
onto a ring of ``config.stream_ring`` buffers). It is the same
double-buffered-DMA idiom the fused Pallas kernel uses for factor rows,
one level up the memory hierarchy (AMPED, arXiv:2507.15121; out-of-memory
MTTKRP, arXiv:2201.12523).

Why chunking preserves bitwise equality
---------------------------------------
Chunks are *whole partitions* (:func:`repro.core.partition.
chunk_schedule`): every output row is owned by exactly one partition
(paper Observation 2), and a partition's slots are a contiguous run of the
partition-major layout, so each chunk's elementwise computation touches a
disjoint, contiguous relabeled-row range ``[part_start[c]*rows_pp,
part_start[c+1]*rows_pp)`` and sees its slots in exactly the order the
resident engine does. Per-chunk results therefore concatenate
bitwise-exactly into the resident result — no accumulation across chunks,
no reassociation. The unchanged backend contract serves every chunk
(``xla | ref | pallas | pallas_fused``); chunks are padded to one uniform
``(chunk_kappa, chunk_blocks)`` shape so each mode compiles ONE program
(pad blocks repeat the last real partition and carry all-pad slots, the
``engine.dist`` device-padding pattern). Short chunks' row overhang is
handled by an ascending ``dynamic_update_slice`` into an over-allocated
accumulator: each later chunk overwrites its predecessor's overhang, and
the final slice keeps exactly ``kappa * rows_pp`` rows.

The Alg. 3 remap is the streaming analogue of ``engine.dist``'s exchange:
each chunk emits its next-mode *fragment* (the chunk's alive elements
scattered through ``alpha[:, d+1]``) which is reassembled host-side into
the next rotation's layout while the device crunches the next chunk — the
device never holds more than the chunk ring, the factor matrices, and the
output accumulator.

Public surface:

  StreamPlan / plan_stream(tensor, config)   per-mode chunk schedules sized
                                             to ``device_budget_bytes``
  StreamState / stream_init(tensor, config)  host layout + device chunk ring
  stream_mttkrp(state, factors)              one mode, chunked + prefetched
  stream_all_modes(state, factors)           full rotation (fold hook as in
                                             ``engine.all_modes``)
  cp_als_stream(tensor, rank, ...)           out-of-core CPD-ALS
  resident_bytes / resolve_chunk_slots /     the budget model ``factory.
  stream_transfer_model                      make_engine`` and ``engine.
                                             autotune`` price streaming with
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.partition import (ChunkSchedule, chunk_bpart,
                                  chunk_schedule)
from repro.obs.metrics import counter as _obs_counter
from repro.obs.metrics import gauge as _obs_gauge
from repro.obs.probe import device_peak_bytes
from repro.obs.trace import span
from repro.resilience import chaos as _chaos
from repro.resilience import guard as _guard
from repro.resilience.ladder import (backoff_delay, classify, next_backend,
                                     record_degradation, record_retry,
                                     resolve_policy)
from repro.resilience.snapshot import as_store, fingerprint

from .api import _JIT_CACHE, DISPATCH_COUNTS, TRACE_COUNTS, _as_flycoo
from .backends import get_backend, pins_layout
from .config import ExecutionConfig
from .dist import row_bytes
from .state import ModeStatic, mode_static_from_plan

#: Chunk size (kernel slots) when neither ``chunk_nnz`` nor
#: ``device_budget_bytes`` is configured.
DEFAULT_CHUNK_SLOTS = 1 << 20


# --------------------------------------------------------------------------
# Budget model (host-side, plan-free where possible).
# --------------------------------------------------------------------------
def _wants_tables(config: ExecutionConfig, schedule: str) -> bool:
    """Whether streamed chunks must carry the in-block dedup tables — the
    exact condition ``engine.api._mode_sched`` uses for residency."""
    return (schedule == "compact"
            and getattr(get_backend(config), "needs_dedup", False))


def bytes_per_slot(nmodes: int, tables: bool) -> int:
    """Device bytes one streamed kernel slot costs: val f32 + idx i32*N +
    lrow i32, plus the dedup tables (uidx + upos, i32*(N-1) each) when the
    backend consumes them, plus 4 bytes slack covering the per-block
    descriptor/nuniq amortization — kept conservative so ring sizing from
    a budget never lands over it."""
    b = 4 * (2 + nmodes) + 4
    if tables:
        b += 8 * (nmodes - 1)
    return b


def chunk_device_bytes(cs: ChunkSchedule, nmodes: int, tables: bool) -> int:
    """Exact device bytes of one uploaded (uniformly padded) chunk."""
    s, nb = cs.chunk_slots, cs.chunk_blocks
    b = s * 4 * (2 + nmodes) + nb * 4
    if tables:
        b += s * 8 * (nmodes - 1) + nb * 4 * (nmodes - 1)
    return b


def stream_fixed_bytes(dims: Sequence[int], config: ExecutionConfig,
                       rank: int | None = None,
                       statics: Sequence[ModeStatic] | None = None) -> int:
    """Device bytes the streaming engine holds *besides* the chunk ring:
    full factor matrices, the relabel tables, the over-allocated output
    accumulator (bounded by ``2 * rmax * R``), and one mode output."""
    rank = rank or config.rank_hint
    if statics is not None:
        rmax = max(s.relabeled_rows for s in statics)
    else:
        rmax = 0
        for dim in dims:
            kappa = config.kappa_for(int(dim))
            rmax = max(rmax, kappa * math.ceil(int(dim) / kappa))
    acc = 2 * rmax * rank * 4
    factors = sum(int(d) for d in dims) * rank * 4
    out = max(int(d) for d in dims) * rank * 4
    relabel = sum(int(d) for d in dims) * 4
    return acc + factors + out + relabel


def resolve_chunk_slots(config: ExecutionConfig, dims: Sequence[int], *,
                        tables: bool = False,
                        statics: Sequence[ModeStatic] | None = None) -> int:
    """Target kernel slots per streamed chunk — the ONE sizing rule.

    Priority: explicit ``chunk_nnz``; else derive from
    ``device_budget_bytes`` so the whole ring (``stream_ring`` uniformly
    padded chunks) plus the fixed state fits the budget; else the library
    default. Never below one kernel block — a partition larger than the
    target still forms an (oversized) chunk of its own, so streaming
    always completes; it may just exceed an impossibly small budget.
    """
    if config.chunk_nnz is not None:
        return max(config.block_p, int(config.chunk_nnz))
    if config.device_budget_bytes is None:
        return DEFAULT_CHUNK_SLOTS
    fixed = stream_fixed_bytes(dims, config, statics=statics)
    avail = config.device_budget_bytes - fixed
    slots = avail // (config.stream_ring * bytes_per_slot(len(dims), tables))
    return int(max(config.block_p, slots))


def resident_bytes(tensor, config: ExecutionConfig,
                   rank: int | None = None) -> int:
    """Device footprint of the FULL-residency engine (``engine.init``) for
    ``tensor``: the S_max-padded layout triple (or, pinned, every mode's
    ``(val, lrow)``), the per-mode schedule tables, the relabel tables, the
    factors and one rotation of outputs.
    This is the threshold ``residency="auto"`` compares
    ``device_budget_bytes`` against."""
    rank = rank or config.rank_hint
    n = tensor.nmodes
    statics = [mode_static_from_plan(p) for p in tensor.plans]
    smax = max(s.padded_nnz for s in statics)
    if pins_layout(config, statics):
        total = sum(s.padded_nnz for s in statics) * 8   # val + lrow
    else:
        total = smax * 4 * (1 + 2 * n)            # val + idx + alpha
    tables = _wants_tables(config, statics[0].schedule)
    for s in statics:
        total += s.nblocks * 4                 # bpart descriptor
        if tables:
            total += s.padded_nnz * 8 * (n - 1) + s.nblocks * 4 * (n - 1)
    total += sum(int(d) for d in tensor.dims) * 4          # relabel
    total += sum(int(d) for d in tensor.dims) * rank * 4   # factors
    total += max(int(d) for d in tensor.dims) * rank * 4   # mode output
    return total


# --------------------------------------------------------------------------
# StreamPlan: per-mode chunk schedules + chunk-local plan constants.
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Partition-aligned chunking of every mode's block schedule.

    ``chunks[d]`` slices mode ``d``'s (compact or rect) block schedule
    into chunks of at most ``target_slots`` kernel slots (whole partitions
    only); ``lstatics[d]`` is the chunk-local :class:`ModeStatic` every
    chunk of that mode runs under (uniform ``chunk_kappa`` partitions /
    ``chunk_blocks`` blocks — ONE trace per mode). ``tables`` records
    whether chunks carry the in-block dedup tables.
    """

    target_slots: int
    chunks: tuple[ChunkSchedule, ...]
    lstatics: tuple[ModeStatic, ...]
    tables: bool

    @property
    def total_chunks(self) -> int:
        return sum(cs.nchunks for cs in self.chunks)

    def mode_h2d_bytes(self, d: int, nmodes: int) -> int:
        """Uploaded bytes for one full pass over mode ``d``'s chunks."""
        cs = self.chunks[d]
        return cs.nchunks * chunk_device_bytes(cs, nmodes, self.tables)


def plan_stream(tensor, config: ExecutionConfig) -> StreamPlan:
    """Build the chunk schedules for ``tensor`` under ``config``'s budget
    (see :func:`resolve_chunk_slots` for the sizing rule)."""
    statics = tuple(mode_static_from_plan(p) for p in tensor.plans)
    tables = _wants_tables(config, statics[0].schedule)
    target = resolve_chunk_slots(config, tensor.dims, tables=tables,
                                 statics=statics)
    chunks = tuple(chunk_schedule(p, target) for p in tensor.plans)
    lstatics = tuple(
        ModeStatic(kappa=cs.chunk_kappa, rows_pp=s.rows_pp,
                   blocks_pp=s.blocks_pp, block_p=s.block_p, dim=s.dim,
                   nblocks=cs.chunk_blocks, schedule=s.schedule)
        for s, cs in zip(statics, chunks))
    return StreamPlan(target_slots=target, chunks=chunks,
                      lstatics=lstatics, tables=tables)


def _stream_plan_key(tensor, config: ExecutionConfig) -> str:
    """Structural key of a :func:`plan_stream` result: the plan geometry
    (per-mode partition/block structure) plus every config knob the chunk
    sizing reads. Two tensors with identical structure — notably the SAME
    tensor replanned under a degraded budget seen before — share a key."""
    import hashlib

    tables = _wants_tables(
        config, mode_static_from_plan(tensor.plans[0]).schedule)
    h = hashlib.sha256()
    h.update(repr((tuple(int(d) for d in tensor.dims), int(tensor.nnz),
                   config.chunk_nnz, config.device_budget_bytes,
                   config.stream_ring, config.block_p, config.rank_hint,
                   tables)).encode())
    for p in tensor.plans:
        h.update(repr((int(p.kappa), int(p.rows_pp), int(p.block_p),
                       int(p.blocks_pp), int(p.nblocks),
                       p.schedule)).encode())
        h.update(np.ascontiguousarray(p.part_nnz).tobytes())
        h.update(np.ascontiguousarray(p.block_part).tobytes())
    return h.hexdigest()


def plan_stream_cached(tensor, config: ExecutionConfig,
                       cache=None) -> StreamPlan:
    """:func:`plan_stream` through the :class:`~repro.core.plancache.
    PlanCache` structural tier — a replan under a config seen before
    (streaming re-init, resume, or a chunk-budget ladder rung replaying a
    degraded budget) is a cache hit instead of a from-scratch chunking.
    ``cache=None`` uses the process default; ``cache=False`` plans cold.
    Hits/misses land on the ``stream_replan_outcomes`` obs counter."""
    from repro.core.plancache import DEFAULT_CACHE

    if cache is None:
        cache = DEFAULT_CACHE
    elif cache is False:
        return plan_stream(tensor, config)
    return cache.get_stream_plan(
        _stream_plan_key(tensor, config),
        lambda: plan_stream(tensor, config))


def stream_transfer_model(tensor, config: ExecutionConfig) -> dict:
    """Modeled transfer traffic of one full streamed rotation: per-mode
    chunk H2D bytes (uniformly padded uploads) and remap-fragment bytes
    (``nnz`` element rows reassembled into the next layout per hop). The
    autotuner's streaming cost term and the fig11 oversubscription rows
    both read this one model."""
    plan = plan_stream(tensor, config)
    n = tensor.nmodes
    rb = row_bytes(n)
    per_mode = []
    for d in range(n):
        per_mode.append({
            "mode": d,
            "nchunks": plan.chunks[d].nchunks,
            "chunk_slots": plan.chunks[d].chunk_slots,
            "h2d_bytes": plan.mode_h2d_bytes(d, n),
            "fragment_bytes": tensor.nnz * rb,
        })
    return {
        "target_slots": plan.target_slots,
        "total_chunks": plan.total_chunks,
        "h2d_bytes": sum(m["h2d_bytes"] for m in per_mode),
        "fragment_bytes": sum(m["fragment_bytes"] for m in per_mode),
        "per_mode": per_mode,
    }


# --------------------------------------------------------------------------
# StreamState: host layout + device chunk ring.
# --------------------------------------------------------------------------
@dataclasses.dataclass
class StreamStats:
    """Mutable transfer/residency observability (shared across rotations)."""

    h2d_bytes: int = 0            # uploaded chunk bytes (host -> device)
    fragment_bytes: int = 0       # remap fragment bytes reassembled per hop
    chunks_streamed: int = 0
    modes_streamed: int = 0
    uploads: int = 0
    overlapped_uploads: int = 0   # uploads issued ahead of their compute
    upload_retries: int = 0       # transient-failure upload re-attempts
    budget_halvings: int = 0      # chunk-budget ladder rungs taken (OOM)
    backend_steps: int = 0        # backend ladder rungs taken (compile)
    peak_ring_bytes: int = 0      # max live device bytes of the chunk ring
    peak_ring_chunks: int = 0

    @property
    def transfer_bytes(self) -> int:
        return self.h2d_bytes + self.fragment_bytes

    @property
    def overlap_efficiency(self) -> float:
        """Fraction of uploads issued while earlier chunks were still in
        flight (1.0 = every upload but each mode's first was prefetched)."""
        return self.overlapped_uploads / max(self.uploads, 1)

    def as_row(self) -> dict:
        return {
            "h2d_bytes": self.h2d_bytes,
            "fragment_bytes": self.fragment_bytes,
            "transfer_bytes": self.transfer_bytes,
            "chunks_streamed": self.chunks_streamed,
            "modes_streamed": self.modes_streamed,
            "upload_retries": self.upload_retries,
            "budget_halvings": self.budget_halvings,
            "backend_steps": self.backend_steps,
            "peak_ring_bytes": self.peak_ring_bytes,
            "peak_ring_chunks": self.peak_ring_chunks,
            "overlap_efficiency": self.overlap_efficiency,
            "device_peak_bytes": device_peak_bytes(),
        }


def _mirror_stats(stats: StreamStats, before: StreamStats) -> None:
    """Mirror one mode pass's :class:`StreamStats` deltas onto the
    ``repro.obs`` metrics registry, so exported traces carry the
    count-derived transfer/overlap numbers next to the spans they are
    cross-checked against (the CI ``obs-smoke`` gate compares the two)."""
    counts = _obs_counter("stream_counts",
                          "streamed uploads / chunks / mode passes")
    counts.inc("uploads", stats.uploads - before.uploads)
    counts.inc("overlapped_uploads",
               stats.overlapped_uploads - before.overlapped_uploads)
    counts.inc("upload_retries",
               stats.upload_retries - before.upload_retries)
    counts.inc("chunks", stats.chunks_streamed - before.chunks_streamed)
    counts.inc("modes", 1)
    nbytes = _obs_counter("stream_bytes",
                          "streamed transfer bytes by direction")
    nbytes.inc("h2d", stats.h2d_bytes - before.h2d_bytes)
    nbytes.inc("fragment", stats.fragment_bytes - before.fragment_bytes)
    peaks = _obs_gauge("stream_peaks", "chunk ring high-water marks")
    peaks.max("ring_bytes", stats.peak_ring_bytes)
    peaks.max("ring_chunks", stats.peak_ring_chunks)
    dev_peak = device_peak_bytes()
    if dev_peak is not None:
        peaks.max("device_bytes", dev_peak)


@dataclasses.dataclass
class StreamState:
    """Host-resident engine state for the streaming tier.

    The FLYCOO layout of the *resident mode* lives in host numpy
    (``val (S_d,)``, ``idx/alpha (S_d, N)``, ``lrow (S_d,)`` — natural
    per-mode size, no S_max padding: nothing here rides a scan carry).
    Only the relabel tables (small, ``sum I_d`` ints) and the factor
    matrices stay device-resident; element data visits the device one
    chunk ring at a time. ``tensor`` is the canonical host copy — its
    plans drive chunk slicing and (lazily, per mode) the dedup tables.
    """

    tensor: object                      # FlycooTensor (host)
    plan: StreamPlan
    statics: tuple[ModeStatic, ...]
    val: np.ndarray                     # (S_mode,) f32 host layout
    idx: np.ndarray                     # (S_mode, N) i32
    alpha: np.ndarray                   # (S_mode, N) i32, -1 dead
    lrow: np.ndarray                    # (S_mode,) i32, -1 dead
    relabel: tuple                      # N x (I_d,) device arrays
    mode: int
    dims: tuple[int, ...]
    config: ExecutionConfig
    stats: StreamStats

    @property
    def nmodes(self) -> int:
        return len(self.dims)

    def replace(self, **kw) -> "StreamState":
        return dataclasses.replace(self, **kw)


def _host_lrow(plan, idx: np.ndarray, alpha: np.ndarray,
               d: int) -> np.ndarray:
    """Host-side ``compute_lrow``: identical integers to the device path
    (relabel lookup mod rows_pp for alive slots, -1 for pads)."""
    alive = alpha[:, d] >= 0
    rel = plan.row_relabel[idx[:, d]]
    return np.where(alive, (rel % plan.rows_pp).astype(np.int32),
                    np.int32(-1))


def stream_init(tensor, config: ExecutionConfig | None = None,
                start_mode: int = 0, *, cache=None) -> StreamState:
    """Build the host-resident streaming state for ``tensor``.

    Same input contract as ``engine.init`` (prebuilt
    :class:`~repro.core.flycoo.FlycooTensor` or raw COO triple, optionally
    through a :class:`~repro.core.plancache.PlanCache`), but the layout is
    materialized HOST-side at the start mode's natural size — the device
    never sees more than the chunk ring.
    """
    config = config or ExecutionConfig()
    with span("stream.init", start_mode=start_mode) as sp:
        tensor = _as_flycoo(tensor, config, cache=cache)
        n = tensor.nmodes
        if not 0 <= start_mode < n:
            raise ValueError(
                f"start_mode {start_mode} out of range for {n} modes")
        statics = tuple(mode_static_from_plan(p) for p in tensor.plans)
        plan = plan_stream_cached(tensor, config, cache=cache)
        sp.set("total_chunks", plan.total_chunks)
        sp.set("target_slots", plan.target_slots)

        base = tensor.plans[start_mode]
        s = base.padded_nnz
        val = np.zeros(s, dtype=np.float32)
        idx = np.zeros((s, n), dtype=np.int32)
        alpha = np.full((s, n), -1, dtype=np.int32)
        val[base.slot_of_elem] = tensor.values
        idx[base.slot_of_elem] = tensor.indices
        for d in range(n):
            alpha[base.slot_of_elem, d] = \
                tensor.plans[d].slot_of_elem.astype(np.int32)

        return StreamState(
            tensor=tensor, plan=plan, statics=statics,
            val=val, idx=idx, alpha=alpha,
            lrow=_host_lrow(base, idx, alpha, start_mode),
            relabel=tuple(jnp.asarray(p.row_relabel) for p in tensor.plans),
            mode=int(start_mode), dims=tensor.dims, config=config,
            stats=StreamStats())


# --------------------------------------------------------------------------
# Per-chunk device step (one jitted program per mode).
# --------------------------------------------------------------------------
def _step_fn(d: int, lplan: ModeStatic, config: ExecutionConfig):
    """Jitted chunk step: backend EC under the chunk-local plan, then an
    ascending full-tile ``dynamic_update_slice`` at the (traced) chunk row
    offset — one trace serves every chunk of the mode."""
    key = ("stream_ec", d, lplan, config)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        backend = get_backend(config)

        def run(acc, chunk, factors, row0):
            TRACE_COUNTS["stream_ec"] += 1  # trace-time side effect
            out_rel = backend(dict(chunk), tuple(factors), d, plan=lplan,
                              config=config)
            return lax.dynamic_update_slice(
                acc, out_rel.astype(acc.dtype), (row0, 0))

        donate = (0,) if config.resolve_donate() else ()
        fn = _JIT_CACHE[key] = jax.jit(run, donate_argnums=donate)
    return fn


def _chunk_host_arrays(state: StreamState, d: int, c: int,
                       tables) -> dict[str, np.ndarray]:
    """Slice chunk ``c`` out of the host layout, padded to the mode's
    uniform chunk shape: pad slots carry ``val=0, lrow=-1`` and zeroed
    dedup tables (``nuniq=0`` -> the fused kernel issues no DMAs), pad
    blocks repeat the last real local partition — the ``engine.dist``
    device-padding pattern, per chunk instead of per device."""
    cs = state.plan.chunks[d]
    n = state.nmodes
    p = cs.block_p
    _, _, b0, b1 = cs.bounds(c)
    s0, s1 = b0 * p, b1 * p
    m = s1 - s0
    s = cs.chunk_slots
    val = np.zeros(s, dtype=np.float32)
    val[:m] = state.val[s0:s1]
    idx = np.zeros((s, n), dtype=np.int32)
    idx[:m] = state.idx[s0:s1]
    lrow = np.full(s, -1, dtype=np.int32)
    lrow[:m] = state.lrow[s0:s1]
    chunk = {"val": val, "idx": idx, "lrow": lrow,
             "bpart": chunk_bpart(state.tensor.plans[d], cs, c)}
    if tables is not None:
        uidx, upos, nuniq = tables
        cu = np.zeros((n - 1, s), dtype=np.int32)
        cu[:, :m] = uidx[:, s0:s1]
        cp = np.zeros((s, n - 1), dtype=np.int32)
        cp[:m] = upos[s0:s1]
        cn = np.zeros((n - 1, cs.chunk_blocks), dtype=np.int32)
        cn[:, :b1 - b0] = nuniq[:, b0:b1]
        chunk.update(uidx=cu, upos=cp, nuniq=cn)
    return chunk


def _mode_tables(state: StreamState, d: int):
    """Full-mode dedup tables when the configured backend consumes them
    (lazy, memoized on the tensor), else ``None``."""
    if not state.plan.tables:
        return None
    return (state.tensor.dedup_tables(d) if state.config.dedup
            else state.tensor.trivial_dedup_tables(d))


# --------------------------------------------------------------------------
# stream_mttkrp: one mode, chunk ring + host-side remap reassembly.
# --------------------------------------------------------------------------
def _upload(host: dict, mode: int, chunk: int, policy,
            stats: StreamStats) -> dict:
    """Place one chunk's host arrays on device, with bounded
    retry-with-backoff (seeded jitter) on *transient* transfer failures
    when a ladder policy is active. Non-transient failures (OOM, compile)
    propagate to the mode-level ladder."""
    attempt = 0
    while True:
        try:
            cz = _chaos.active()
            if cz is not None:
                cz.on_upload(mode, chunk, attempt)
            return {key: jax.device_put(a) for key, a in host.items()}
        except Exception as exc:
            if (policy is None or classify(exc) != "transient"
                    or attempt >= policy.max_retries):
                raise
            stats.upload_retries += 1
            record_retry("stream.upload", attempt,
                         backoff_delay(policy, attempt,
                                       token=("upload", mode, chunk)),
                         mode=mode, chunk=chunk)
            attempt += 1


def _with_config(state: StreamState,
                 config: ExecutionConfig) -> StreamState:
    """Rebuild the chunk plan under a degraded config. Safe mid-rotation:
    a failed mode attempt mutates neither the host layout nor the factors
    (the accumulator and next-mode fragments it built are local), and the
    chunk plan is derived purely from ``tensor`` + ``config``. Goes
    through the plan-cache structural tier: a degraded replan whose
    (structure, budget) point was chunked before is a cache hit."""
    return state.replace(config=config,
                         plan=plan_stream_cached(state.tensor, config))


def stream_mttkrp(state: StreamState, factors: Sequence[jax.Array],
                  mode: int | None = None, *, policy=None):
    """MTTKRP for the resident mode, streamed chunk-by-chunk; returns
    ``(out, next_state)`` with ``out (dims[mode], R)`` bitwise-identical
    to the resident ``engine.mttkrp``. The next-mode host layout (the
    Alg. 3 remap) is reassembled fragment-by-fragment while the device
    computes.

    With a ``policy`` (:class:`repro.resilience.LadderPolicy`) the mode
    rides the degradation ladder: an OOM halves the chunk budget and
    replans (up to ``max_budget_halvings`` — per-chunk results are
    partition-aligned, so any chunking concatenates bitwise-identically);
    a compile/lowering failure steps the backend down
    ``BACKEND_LADDER`` and replans (dedup tables follow the backend).
    The degraded config rides the returned state — later modes inherit
    it. Every transition is a ``resilience_degradations`` counter + span.
    """
    halvings = steps = 0
    while True:
        try:
            return _stream_mode_once(state, factors, mode, policy)
        except Exception as exc:
            if policy is None:
                raise
            kind = classify(exc)
            if kind == "oom" and halvings < policy.max_budget_halvings:
                cur = state.plan.target_slots
                new = max(state.config.block_p, cur // 2)
                if new >= cur:
                    raise
                halvings += 1
                state.stats.budget_halvings += 1
                record_degradation("oom", cur, new,
                                   site="stream.chunk_budget",
                                   mode=state.mode)
                state = _with_config(
                    state,
                    dataclasses.replace(state.config, chunk_nnz=new))
                continue
            if kind == "compile" and steps < policy.max_backend_steps:
                nb = next_backend(state.config.backend)
                if nb is None:
                    raise
                steps += 1
                state.stats.backend_steps += 1
                record_degradation("compile", state.config.backend, nb,
                                   site="stream.backend", mode=state.mode)
                state = _with_config(
                    state,
                    dataclasses.replace(state.config, backend=nb))
                continue
            raise


def _stream_mode_once(state: StreamState, factors: Sequence[jax.Array],
                      mode: int | None, policy):
    if mode is not None and mode != state.mode:
        raise ValueError(
            f"state holds the mode-{state.mode} layout; cannot compute "
            f"mode {mode} without rotating (use stream_all_modes)")
    d = state.mode
    n = state.nmodes
    nxt = (d + 1) % n
    cs = state.plan.chunks[d]
    st = state.statics[d]
    rows_pp = st.rows_pp
    rank = factors[0].shape[1]
    config = state.config
    stats = state.stats
    step = _step_fn(d, state.plan.lstatics[d], config)
    tables = _mode_tables(state, d)
    factors = tuple(factors)

    # Over-allocated accumulator: chunk c's full (chunk_kappa * rows_pp)
    # tile lands at row part_start[c] * rows_pp; later chunks overwrite the
    # previous chunk's overhang, the final slice drops the last one's.
    acc = jnp.zeros(((st.kappa + cs.chunk_kappa) * rows_pp, rank),
                    config.accum_dtype())

    # Next-mode host layout, filled fragment-by-fragment (Alg. 3, host).
    snxt = state.statics[nxt].padded_nnz
    nval = np.zeros(snxt, dtype=np.float32)
    nidx = np.zeros((snxt, n), dtype=np.int32)
    nalpha = np.full((snxt, n), -1, dtype=np.int32)

    cz = _chaos.active()
    if cz is not None:
        cz.on_dispatch(config.backend)
    before = dataclasses.replace(stats)
    ring: dict[int, dict] = {}
    chunk_bytes = 0
    with span("stream.mode", mode=d, nchunks=cs.nchunks):
        for c in range(cs.nchunks):
            # prefetch: keep chunks [c, c + ring) resident/uploading —
            # chunk c+1's H2D overlaps chunk c's kernel (async dispatch)
            for k in range(c, min(c + config.stream_ring, cs.nchunks)):
                if k not in ring:
                    with span("stream.upload", chunk=k,
                              prefetch=k > c) as up:
                        host = _chunk_host_arrays(state, d, k, tables)
                        ring[k] = _upload(host, d, k, policy, stats)
                        nbytes = sum(a.nbytes for a in host.values())
                        up.set("bytes", nbytes)
                    if not chunk_bytes:
                        chunk_bytes = nbytes
                    stats.h2d_bytes += nbytes
                    stats.uploads += 1
                    if k > c:
                        stats.overlapped_uploads += 1
            stats.peak_ring_chunks = max(stats.peak_ring_chunks, len(ring))
            stats.peak_ring_bytes = max(stats.peak_ring_bytes,
                                        len(ring) * chunk_bytes)
            if cz is not None:
                cz.on_chunk_compute(d, c)
            dev = ring.pop(c)
            DISPATCH_COUNTS["stream_ec"] += 1
            with span("stream.compute", chunk=c):
                acc = step(acc, dev, factors,
                           np.int32(cs.part_start[c] * rows_pp))
            del dev  # ring slot freed once the dispatched step completes

            # host-side remap fragment for chunk c (real slots only) while
            # the device crunches: scatter this chunk's alive elements into
            # the next-mode layout through alpha[:, nxt]
            with span("stream.remap", chunk=c):
                _, _, b0, b1 = cs.bounds(c)
                sl = slice(b0 * cs.block_p, b1 * cs.block_p)
                av = state.alpha[sl]
                alive = av[:, d] >= 0
                dst = av[alive, nxt]
                nval[dst] = state.val[sl][alive]
                nidx[dst] = state.idx[sl][alive]
                nalpha[dst] = av[alive]
            stats.fragment_bytes += int(alive.sum()) * row_bytes(n)
            stats.chunks_streamed += 1

        out_rel = acc[: st.kappa * rows_pp]
        out = jnp.take(out_rel, state.relabel[d], axis=0)
    stats.modes_streamed += 1
    _mirror_stats(stats, before)
    nxt_plan = state.tensor.plans[nxt]
    return out, state.replace(
        val=nval, idx=nidx, alpha=nalpha,
        lrow=_host_lrow(nxt_plan, nidx, nalpha, nxt), mode=nxt)


def stream_all_modes(state: StreamState, factors: Sequence[jax.Array], *,
                     fold=None, carry=None, policy=None):
    """spMTTKRP along all N modes, streamed (one host loop — the chunk
    residency *is* the host loop, unlike the resident engine's scan).

    Same contract as ``engine.all_modes``: outputs indexed by mode from
    any start mode; without ``fold`` returns ``(outs, next_state)``, with
    ``fold`` returns ``(outs, next_state, factors, carry)`` — the hook
    runs right after each mode's output (Gauss-Seidel ALS order), on the
    device-resident factors. ``policy`` enables the per-mode degradation
    ladder (see :func:`stream_mttkrp`); a degraded config sticks for the
    rest of the rotation via the returned state."""
    n = state.nmodes
    factors = tuple(factors)
    outs: list = [None] * n
    for _ in range(n):
        d = state.mode
        out, state = stream_mttkrp(state, factors, policy=policy)
        if fold is not None:
            factors, carry = fold(d, out, factors, carry)
        outs[d] = out
    if fold is None:
        return outs, state
    return outs, state, list(factors), carry


# --------------------------------------------------------------------------
# cp_als_stream: out-of-core CPD-ALS.
# --------------------------------------------------------------------------
def cp_als_stream(tensor, rank: int, iters: int = 10, key=None,
                  config: ExecutionConfig | None = None,
                  track_fit: bool = True, *, cache=None,
                  start_mode: int = 0, ladder=None, checkpoint=None,
                  checkpoint_every: int = 1, resume: bool = False):
    """CPD-ALS with the streamed engine — same sweep semantics as
    ``core.cpd.cp_als`` (Gauss-Seidel fold after each mode, fit via the
    sparse-CPD identity), for tensors whose FLYCOO layout exceeds device
    memory. Factor matrices stay device-resident; element data streams.

    Resilience (mirrors ``cp_als``):

    * ``ladder``: ``True`` / a :class:`repro.resilience.LadderPolicy`
      enables the degradation ladder (backend rungs, chunk-budget halving
      on OOM, upload retry-with-backoff) plus the per-sweep NaN guard
      with rollback + ridge-recovery replay.
    * ``checkpoint``: a directory or :class:`repro.resilience.
      SnapshotStore`; every ``checkpoint_every`` completed sweeps the
      ``(factors, lam, fits)`` state is snapshotted atomically under the
      problem fingerprint. ``resume=True`` restores the newest intact
      snapshot *for the same problem* and replays only the remaining
      sweeps — bitwise-identical final factors vs an uninterrupted run
      (at a sweep boundary the layout has rotated back to its start
      arrangement, so factors + lam are the complete dynamic state).
    """
    # lazy: core.cpd imports repro.engine at module scope
    from repro.core.cpd import (CPDResult, _als_fold, _als_fold_recovery,
                                _fit, init_factors)

    config = config or ExecutionConfig()
    policy = resolve_policy(ladder)
    if key is None:
        key = jax.random.PRNGKey(0)
    state = stream_init(tensor, config, start_mode, cache=cache)
    n = state.nmodes
    factors = tuple(init_factors(key, state.dims, rank))
    lam = jnp.ones((rank,), jnp.float32)
    norm_x_sq = float(
        np.sum(state.tensor.values.astype(np.float64) ** 2))

    store = as_store(checkpoint)
    fits: list = []
    first = 0
    fp = None
    if store is not None:
        fp = fingerprint(state.tensor.indices, state.tensor.values,
                         state.dims, rank, config=config, key=key,
                         start_mode=start_mode, extra="stream")
        if resume:
            snap = store.latest(fp)
            if snap is not None:
                factors = tuple(jnp.asarray(f) for f in snap.factors)
                lam = jnp.asarray(snap.lam)
                fits = list(snap.fits)
                first = snap.sweep
    for i in range(first, iters):
        cz = _chaos.active()
        if cz is not None:
            cz.maybe_kill(i)
        prev = (factors, lam)
        with span("cpd.sweep", sweep=i, streamed=True) as sp:
            outs, state, factors, lam = stream_all_modes(
                state, factors, fold=_als_fold, carry=lam, policy=policy)
            if cz is not None:
                factors = tuple(cz.mangle_factors(i, factors))
            if policy is not None and not _guard.all_finite(factors, lam):
                # roll back and replay the sweep under the stronger ridge:
                # the layout is bitwise back at its start arrangement, so
                # the replay sees exactly the pre-sweep problem.
                _guard.record_recovery("nan_rollback", sweep=i,
                                       streamed=True)
                factors, lam = prev
                outs, state, factors, lam = stream_all_modes(
                    state, factors, fold=_als_fold_recovery, carry=lam,
                    policy=policy)
            if track_fit:
                fit = _fit(norm_x_sq, outs[n - 1], factors, lam)
                fits.append(fit)
                sp.set("fit", float(fit))
                _obs_gauge("cpd_fit", "latest ALS fit per tier").set(
                    "streamed", float(fit))
        if store is not None and ((i + 1) % checkpoint_every == 0
                                  or i + 1 == iters):
            store.save(fp, i + 1, [np.asarray(f) for f in factors],
                       np.asarray(lam), fits)
    return CPDResult(factors=list(factors), lam=lam, fits=fits)


__all__ = ["StreamPlan", "StreamState", "StreamStats", "plan_stream",
           "plan_stream_cached", "stream_init", "stream_mttkrp",
           "stream_all_modes",
           "cp_als_stream", "resident_bytes", "resolve_chunk_slots",
           "stream_transfer_model", "stream_fixed_bytes", "bytes_per_slot",
           "chunk_device_bytes", "DEFAULT_CHUNK_SLOTS"]
