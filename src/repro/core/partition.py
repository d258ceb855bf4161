"""Tensor partitioning scheme (paper Alg. 1) + TPU row relabeling.

Per output mode d:
  1. order mode-d vertices (output factor rows) by the number of incident
     nonzeros (hyperedge degree), descending;
  2. deal vertices cyclically over ``kappa`` partitions (paper Sec. 3.4.1
     cites Graham's 4/3; the cyclic deal is round-robin-on-sorted, whose
     provable makespan bound is mean + d_max <= 2*OPT, matching the 4/3
     regime whenever the max vertex degree is small vs. the mean load —
     the sparse-tensor common case; property-tested in tests/);
  3. every nonzero joins the partition owning its mode-d vertex, so each
     output row is owned by exactly one partition (paper Observation 2).

TPU adaptation (see DESIGN.md Sec. 2): vertices are *relabeled* so partition
``j`` owns the contiguous row range ``[j*rows_pp, (j+1)*rows_pp)``. This lets
a Pallas output BlockSpec map partition -> VMEM row tile. Relabeling permutes
rows only; the per-partition degree multiset (and hence the 4/3 bound) is
unchanged.

Block schedules
---------------
The kernel layout packs each partition's nonzeros into blocks of ``block_p``
slots. Two schedules exist (paper challenge (3): balanced block workloads):

``compact`` (default)
    Partition ``j`` gets exactly ``ceil(part_nnz[j] / P)`` blocks (min 1, so
    every output row tile is visited and zero-initialized); blocks are laid
    out partition-major and the ``(nblocks,)`` ``block_part`` descriptor
    records each block's owning partition. The Pallas grid walks only real
    work; on skewed (power-law) tensors this removes the pad blocks the
    rectangular layout spends most of its grid on.

``rect``
    Every partition is padded to the max partition's block count
    (``blocks_pp = ceil(max part_nnz / P)``); partition ``j`` owns the slot
    stride ``[j*blocks_pp*P, (j+1)*blocks_pp*P)``. Kept as the comparison
    baseline — ``block_part`` is materialized for it too, so descriptor-
    driven consumers treat both schedules uniformly.

Pad slots carry ``val = 0, lrow = -1`` in either schedule.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# Default tile knobs (DESIGN.md Sec. 2: kappa is a VMEM tiling knob on TPU,
# not a core count). rows_pp * R * 4B must fit comfortably in VMEM.
DEFAULT_ROWS_PER_PARTITION = 512
DEFAULT_BLOCK_P = 128  # nonzeros per kernel block (sublane-aligned)

SCHEDULES = ("compact", "rect")
DEFAULT_SCHEDULE = "compact"


@dataclasses.dataclass(frozen=True)
class ModePlan:
    """Host-side preprocessing output for one output mode ``d``.

    The *kernel layout* for mode d is ``nblocks`` blocks of ``block_p``
    slots (physical length ``nblocks * block_p``), laid out partition-major;
    ``block_part[b]`` is the partition owning block ``b``. Under the
    ``rect`` schedule every partition holds exactly ``blocks_pp`` blocks;
    under ``compact`` only its real ``ceil(part_nnz/P)`` blocks (min 1).
    Pad slots carry ``val = 0, lrow = -1``.
    """

    mode: int
    kappa: int                   # number of partitions
    rows_pp: int                 # relabeled rows per partition (row tile height)
    block_p: int                 # nonzeros per kernel block (paper's P)
    blocks_pp: int               # max blocks of any partition (rect grid width)
    dim: int                     # I_d
    schedule: str                # "compact" | "rect" block schedule
    nblocks: int                 # total kernel blocks in the layout
    # vertex relabeling: old row id -> relabeled row id in [0, kappa*rows_pp)
    row_relabel: np.ndarray      # (I_d,) int32
    # element -> physical slot in this mode's kernel layout (compact order)
    slot_of_elem: np.ndarray     # (nnz,) int32 (int64 iff padded_nnz >= 2^31)
    # per-partition true nonzero counts (for load-balance reporting)
    part_nnz: np.ndarray         # (kappa,) int64
    # block -> owning partition descriptor (nondecreasing, partition-major)
    block_part: np.ndarray       # (nblocks,) int32
    # max vertex degree (the d_max term of the OPT lower bound)
    max_degree: int

    @property
    def padded_nnz(self) -> int:
        return self.nblocks * self.block_p

    @property
    def relabeled_rows(self) -> int:
        return self.kappa * self.rows_pp

    @property
    def pad_block_fraction(self) -> float:
        """Fraction of kernel blocks carrying zero real nonzeros."""
        real = np.ceil(self.part_nnz / self.block_p).sum()
        return float(1.0 - real / max(self.nblocks, 1))

    def load_balance(self) -> dict:
        """Max/mean partition load; paper Sec 3.4.1 bounds max <= 4/3 OPT.

        OPT >= max(mean, max vertex degree): no schedule can beat the mean
        load, and the partition owning the hottest vertex carries at least
        its degree. ``imbalance`` is the achieved max against that lower
        bound (``imbalance_vs_mean`` keeps the mean-only ratio for
        reference — it overstates imbalance when one vertex dominates).
        """
        loads = self.part_nnz.astype(np.float64)
        mean = float(loads.mean())
        opt_lb = max(mean, float(self.max_degree))
        return {
            "max": float(loads.max()),
            "mean": mean,
            "max_degree": float(self.max_degree),
            "opt_lower_bound": opt_lb,
            "imbalance": float(loads.max() / max(opt_lb, 1e-9)),
            "imbalance_vs_mean": float(loads.max() / max(mean, 1e-9)),
        }


def choose_kappa(dim: int, rows_pp: int = DEFAULT_ROWS_PER_PARTITION) -> int:
    return max(1, math.ceil(dim / rows_pp))


def _part_dtype(kappa: int):
    """Narrowest dtype holding partition ids — the stable (radix) argsort
    over per-element partitions is the cold-plan hot spot, and radix cost
    scales with key width (uint16 sorts ~2x faster than int64)."""
    return np.uint16 if kappa <= 0xFFFF else np.int32


def _block_layout(part_nnz: np.ndarray, kappa: int, block_p: int,
                  schedule: str):
    """Block schedule: partition j owns part_blocks[j] consecutive blocks.
    Min 1 block per partition so every output row tile is visited (and
    zero-initialized) by the kernel grid even when the partition is empty.
    Returns ``(blocks_pp, block_start (kappa+1,), nblocks, block_part)``."""
    blocks_pp = max(1, math.ceil(int(part_nnz.max(initial=0)) / block_p))
    if schedule == "rect":
        part_blocks = np.full(kappa, blocks_pp, dtype=np.int64)
    else:
        part_blocks = np.maximum(1, -(-part_nnz // block_p))
    block_start = np.concatenate([[0], np.cumsum(part_blocks)])  # (kappa+1,)
    nblocks = int(block_start[-1])
    block_part = np.repeat(np.arange(kappa), part_blocks).astype(np.int32)
    return blocks_pp, block_start, nblocks, block_part


def _slots_for(indices_d: np.ndarray, part_of_vertex: np.ndarray,
               part_nnz: np.ndarray, block_start: np.ndarray,
               block_p: int) -> np.ndarray:
    """Element -> physical slot (the order-dependent half of a plan).

    Stable rank within the owning partition (sorted by partition, ranks in
    element order), then ``slot = block_start[j] * P + rank``. Value-equal
    to :func:`plan_mode_reference`'s two-gather formulation, but as one
    per-partition offset repeat + one scatter over narrow dtypes.
    """
    nnz = indices_d.shape[0]
    part_of_elem = part_of_vertex[indices_d]
    order = np.argsort(part_of_elem, kind="stable")  # radix on narrow ints
    # In partition-sorted order, slot = arange + (partition's first slot -
    # partition's first element rank); scatter back to element order.
    part_starts = np.concatenate([[0], np.cumsum(part_nnz[:-1])])
    offs = block_start[:-1] * block_p - part_starts    # (kappa,)
    padded = int(block_start[-1]) * block_p
    dtype = np.int32 if padded < 2**31 else np.int64
    slot_sorted = (np.arange(nnz, dtype=dtype)
                   + np.repeat(offs.astype(dtype), part_nnz))
    slot_of_elem = np.empty(nnz, dtype=dtype)
    slot_of_elem[order] = slot_sorted
    return slot_of_elem


def plan_mode(
    indices_d: np.ndarray,
    dim: int,
    mode: int,
    kappa: int | None = None,
    rows_pp: int | None = None,
    block_p: int = DEFAULT_BLOCK_P,
    schedule: str = DEFAULT_SCHEDULE,
    degrees: np.ndarray | None = None,
) -> ModePlan:
    """Run Alg. 1 for one mode and derive the block-scheduled kernel layout.

    Vectorized cold path: narrow (int32/uint16) sort keys and a single
    rank scatter — bitwise-identical plans to the pre-autotuner
    :func:`plan_mode_reference` (property-tested), ~2x faster on skewed
    benchmark tensors.

    Args:
      indices_d: (nnz,) mode-d index of every nonzero.
      dim: I_d.
      mode: d (bookkeeping only).
      kappa: partition count; default sized so row tiles fit VMEM. With
        more partitions than rows (a short mode sharded over more
        devices) the surplus partitions own no rows.
      rows_pp: rows per partition; derived from kappa when not given.
      schedule: ``"compact"`` emits only real blocks plus the block->
        partition descriptor; ``"rect"`` pads every partition to the max
        partition's block count (the comparison baseline).
      degrees: optional precomputed ``np.bincount(indices_d, minlength=dim)``
        — the plan cache computes per-mode degrees for its signature and
        hands them down so a cache miss never re-counts.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
    # build_flycoo hands us column views of a (nnz, N) array; the fancy
    # gathers below are ~10% faster on a contiguous copy.
    indices_d = np.ascontiguousarray(indices_d)
    if kappa is None:
        kappa = choose_kappa(dim, rows_pp or DEFAULT_ROWS_PER_PARTITION)
    rows_pp = math.ceil(dim / kappa)

    # --- Alg. 1 step 1: vertices sorted by degree (descending, stable). ---
    if degrees is None:
        degrees = np.bincount(indices_d, minlength=dim)
    neg = -degrees.astype(np.int32) if degrees.max(initial=0) < 2**31 \
        else -degrees
    vsort = np.argsort(neg, kind="stable")  # (I_d,) vertex ids

    # --- Alg. 1 step 2: cyclic deal over kappa partitions. ---
    # vertex vsort[i] -> partition i % kappa, local row i // kappa.
    rank = np.arange(dim, dtype=np.int32)
    part_of_rank = rank % kappa
    row_relabel = np.empty(dim, dtype=np.int32)
    row_relabel[vsort] = part_of_rank * rows_pp + rank // kappa
    part_of_vertex = np.empty(dim, dtype=_part_dtype(kappa))
    part_of_vertex[vsort] = part_of_rank.astype(part_of_vertex.dtype)

    # --- Alg. 1 step 3: collect hyperedges per partition; assign remap ids.
    # Partition loads come from the dealt degrees directly (column sums of
    # the rank-major deal) — no second nnz-sized bincount needed.
    dsort = degrees[vsort]
    pad = (-dim) % kappa
    if pad:
        dsort = np.concatenate([dsort, np.zeros(pad, dtype=dsort.dtype)])
    part_nnz = dsort.reshape(-1, kappa).sum(axis=0, dtype=np.int64)
    blocks_pp, block_start, nblocks, block_part = _block_layout(
        part_nnz, kappa, block_p, schedule)
    slot_of_elem = _slots_for(indices_d, part_of_vertex, part_nnz,
                              block_start, block_p)

    return ModePlan(
        mode=mode,
        kappa=int(kappa),
        rows_pp=int(rows_pp),
        block_p=int(block_p),
        blocks_pp=int(blocks_pp),
        dim=int(dim),
        schedule=schedule,
        nblocks=nblocks,
        row_relabel=row_relabel,
        slot_of_elem=slot_of_elem,
        part_nnz=part_nnz,
        block_part=block_part,
        max_degree=int(degrees.max(initial=0)),
    )


# --------------------------------------------------------------------------
# Partition-aligned chunking of a block schedule (the out-of-core tier).
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ChunkSchedule:
    """Partition-aligned slicing of one mode's block schedule into chunks.

    Chunk ``c`` owns partitions ``[part_start[c], part_start[c+1])`` whose
    blocks are contiguous in the (partition-major) kernel layout, starting
    at global block ``block_start[c]`` — so a chunk is a contiguous slot
    range ``[block_start[c]*P, block_start[c+1]*P)`` of the mode's layout.
    Because every output row is owned by exactly one partition (paper
    Observation 2), per-chunk elementwise computations touch disjoint
    output rows and concatenate bitwise-exactly into the full result.

    All chunks are padded to the uniform ``(chunk_kappa, chunk_blocks)``
    shape (max real partitions / blocks of any chunk) so the streaming
    engine compiles ONE program per mode; pad blocks repeat the last real
    local partition (descriptor stays nondecreasing) and carry all-pad
    slots.
    """

    part_start: np.ndarray      # (nchunks+1,) int64 partition boundaries
    block_start: np.ndarray     # (nchunks+1,) int64 global block boundaries
    chunk_kappa: int            # uniform (max) partitions per chunk
    chunk_blocks: int           # uniform (max) real blocks per chunk
    block_p: int

    @property
    def nchunks(self) -> int:
        return len(self.part_start) - 1

    @property
    def chunk_slots(self) -> int:
        """Uniform padded slot count of one resident chunk."""
        return self.chunk_blocks * self.block_p

    def bounds(self, c: int) -> tuple[int, int, int, int]:
        """``(p0, p1, b0, b1)`` — chunk ``c``'s partition and block range."""
        return (int(self.part_start[c]), int(self.part_start[c + 1]),
                int(self.block_start[c]), int(self.block_start[c + 1]))


def chunk_schedule(plan: ModePlan, target_slots: int) -> ChunkSchedule:
    """Greedily pack whole partitions into chunks of <= ``target_slots``
    kernel slots (min one partition per chunk, so a partition larger than
    the target still forms a — then oversized — chunk of its own).

    Works for both schedules: the per-partition block counts come from the
    ``block_part`` descriptor, which ``rect`` materializes too.
    """
    target_blocks = max(1, target_slots // plan.block_p)
    part_blocks = np.bincount(plan.block_part, minlength=plan.kappa)
    starts = [0]
    acc = 0
    for j in range(plan.kappa):
        nb = int(part_blocks[j])
        if acc and acc + nb > target_blocks:
            starts.append(j)
            acc = 0
        acc += nb
    starts.append(plan.kappa)
    part_start = np.asarray(starts, dtype=np.int64)
    cum_blocks = np.concatenate([[0], np.cumsum(part_blocks)])
    block_start = cum_blocks[part_start]
    chunk_kappa = int(np.diff(part_start).max())
    chunk_blocks = int(np.diff(block_start).max())
    return ChunkSchedule(part_start=part_start, block_start=block_start,
                         chunk_kappa=chunk_kappa, chunk_blocks=chunk_blocks,
                         block_p=plan.block_p)


def chunk_bpart(plan: ModePlan, cs: ChunkSchedule, c: int) -> np.ndarray:
    """Chunk-local block -> partition descriptor, rebased to the chunk's
    first partition and padded to the uniform ``chunk_blocks`` length (pad
    blocks repeat the last real local partition, as in the distributed
    engine's device-local descriptors)."""
    p0, _, b0, b1 = cs.bounds(c)
    seg = plan.block_part[b0:b1].astype(np.int32) - np.int32(p0)
    out = np.empty(cs.chunk_blocks, dtype=np.int32)
    out[:len(seg)] = seg
    out[len(seg):] = seg[-1]
    return out


def plan_from_structure(indices_d: np.ndarray, base: ModePlan) -> ModePlan:
    """Rebuild a plan for a *reordered* element list from a cached one.

    Everything order-invariant — the degree sort, the cyclic deal, the
    relabeling and the block layout — is reused from ``base`` verbatim
    (shared arrays); only the order-dependent ``slot_of_elem`` is
    recomputed. Caller must guarantee ``indices_d`` has exactly ``base``'s
    degree multiset per vertex (the plan cache verifies per-mode degree
    equality before taking this path); the result is then bitwise-equal to
    a cold :func:`plan_mode` on ``indices_d``.
    """
    part_of_vertex = (base.row_relabel // base.rows_pp).astype(
        _part_dtype(base.kappa))
    block_start = np.concatenate(
        [[0], np.cumsum(np.bincount(base.block_part,
                                    minlength=base.kappa))])
    slot_of_elem = _slots_for(np.asarray(indices_d), part_of_vertex,
                              base.part_nnz, block_start, base.block_p)
    return dataclasses.replace(base, slot_of_elem=slot_of_elem)


def plan_mode_reference(
    indices_d: np.ndarray,
    dim: int,
    mode: int,
    kappa: int | None = None,
    rows_pp: int | None = None,
    block_p: int = DEFAULT_BLOCK_P,
    schedule: str = DEFAULT_SCHEDULE,
) -> ModePlan:
    """Pre-autotuner ``plan_mode`` kept verbatim: the bitwise parity oracle
    for the vectorized path and the fig10 cold-plan speedup baseline
    (CI gates the vectorized path at >= 2x on the zipf dataset)."""
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")
    indices_d = np.asarray(indices_d, dtype=np.int64)
    nnz = indices_d.shape[0]
    if kappa is None:
        kappa = choose_kappa(dim, rows_pp or DEFAULT_ROWS_PER_PARTITION)
    rows_pp = math.ceil(dim / kappa)

    degrees = np.bincount(indices_d, minlength=dim)
    vsort = np.argsort(-degrees, kind="stable")  # (I_d,) vertex ids

    part_of_rank = np.arange(dim) % kappa
    local_of_rank = np.arange(dim) // kappa
    row_relabel = np.empty(dim, dtype=np.int64)
    row_relabel[vsort] = part_of_rank * rows_pp + local_of_rank
    part_of_vertex = np.empty(dim, dtype=np.int64)
    part_of_vertex[vsort] = part_of_rank

    part_of_elem = part_of_vertex[indices_d]
    part_nnz = np.bincount(part_of_elem, minlength=kappa)

    blocks_pp = max(1, math.ceil(int(part_nnz.max(initial=0)) / block_p))
    if schedule == "rect":
        part_blocks = np.full(kappa, blocks_pp, dtype=np.int64)
    else:
        part_blocks = np.maximum(1, -(-part_nnz // block_p))
    block_start = np.concatenate([[0], np.cumsum(part_blocks)])  # (kappa+1,)
    nblocks = int(block_start[-1])
    block_part = np.repeat(np.arange(kappa), part_blocks).astype(np.int32)

    order = np.argsort(part_of_elem, kind="stable")
    rank_within = np.empty(nnz, dtype=np.int64)
    part_starts = np.concatenate([[0], np.cumsum(part_nnz)])
    rank_within[order] = np.arange(nnz) - part_starts[part_of_elem[order]]
    slot_of_elem = block_start[part_of_elem] * block_p + rank_within

    return ModePlan(
        mode=mode,
        kappa=int(kappa),
        rows_pp=int(rows_pp),
        block_p=int(block_p),
        blocks_pp=int(blocks_pp),
        dim=int(dim),
        schedule=schedule,
        nblocks=nblocks,
        row_relabel=row_relabel.astype(np.int32),
        slot_of_elem=slot_of_elem,
        part_nnz=part_nnz,
        block_part=block_part,
        max_degree=int(degrees.max(initial=0)),
    )
