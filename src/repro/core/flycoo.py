"""FLYCOO-TPU sparse tensor format (paper Sec. 3, adapted per DESIGN.md Sec. 2).

A tensor element is the tuple ``<alpha_i, beta_i, val_i>`` (paper Sec. 3.5):
``beta_i``  = per-mode indices (c_0..c_{N-1}),
``alpha_i`` = per-mode remap ids (b_0..b_{N-1}) — the element's physical slot
in the mode-d kernel layout.

The mode-d *kernel layout* is block-scheduled (see ``partition.ModePlan``):
``nblocks_d`` blocks of ``P`` slots laid out partition-major, with the
``block_part`` descriptor naming each block's owning partition. The default
``compact`` schedule emits only real blocks; ``rect`` pads every partition
to the max partition's block count (the comparison baseline). Pad slots
hold ``val = 0`` and ``lrow = -1`` so they contribute nothing.

Per-slot arrays in layout d:
  val   (S_d,)    f32    nonzero value (0 in pads)
  idx   (S_d, N)  i32    original per-mode indices (0 in pads)
  lrow  (S_d,)    i32    relabeled row id *local to its partition* for the
                         output mode d (-1 in pads)
  dst   (S_d,)    i32    slot of the same element in layout (d+1) mod N
                         (-1 in pads) — drives dynamic remapping (Alg. 3)

``dst`` is what makes remapping "dynamic": the mode-d pass scatters its own
elements into the mode-(d+1) layout while computing mode d, exactly the
paper's Alg. 3 (unique remap ids => conflict-free scatter, Observation 1).

In-block factor-row dedup
-------------------------
The fused Pallas pipeline DMAs input-factor rows into VMEM per block; on
Zipf-heavy tensors the same hot row recurs many times within one block, so
per-slot copies re-fetch it up to ``P`` times. :meth:`FlycooTensor.
dedup_tables` sorts each block's factor-row list host-side and emits

  uidx  (N-1, S_d)       per block, the ``U <= P`` *unique* rows, compacted
                         to the block's first slots (rest zero-padded);
  upos  (S_d, N-1)       per slot, the local stage position of its row
                         among the block's uniques (0 for pad slots);
  nuniq (N-1, nblocks)   per block, the unique-row count ``U``,

so the kernel issues ``U`` row DMAs instead of ``P`` and the EC body
gathers its Hadamard operands through ``upos``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.obs.trace import span as _obs_span

from .partition import DEFAULT_SCHEDULE, ModePlan, plan_mode

_ROW_SENTINEL = np.iinfo(np.int32).max  # pad-slot marker; sorts last


def _dedup_tables_batched(rows: np.ndarray, nblocks: int, block_p: int):
    """Build (uidx, upos, nuniq) for ``F`` factors' per-slot row lists.

    ``rows`` is ``(F, S)`` integer with ``_ROW_SENTINEL`` marking pad
    slots; ``S == nblocks * block_p``. Fully vectorized over factors *and*
    blocks: sort each block's rows, mark firsts, compact the uniques to
    the block's front, and record every slot's position among them. All
    work happens on int32 (row ids are < 2^31 by the FLYCOO int32 index
    contract) — the batched narrow path is the dedup half of the cold-plan
    vectorization pass.
    """
    f = rows.shape[0]
    s = nblocks * block_p
    assert rows.shape == (f, s), (rows.shape, nblocks, block_p)
    rb = np.ascontiguousarray(rows, dtype=np.int32).reshape(
        f, nblocks, block_p)
    # stability is irrelevant here: equal rows share one upos/uidx entry,
    # so any permutation among equals yields identical tables
    order = np.argsort(rb, axis=2)
    srt = np.take_along_axis(rb, order, axis=2)
    isnew = np.ones(srt.shape, dtype=bool)
    isnew[:, :, 1:] = srt[:, :, 1:] != srt[:, :, :-1]
    isnew &= srt != _ROW_SENTINEL          # sentinels are not unique rows
    upos_sorted = np.maximum(
        np.cumsum(isnew, axis=2, dtype=np.int32) - 1, 0)
    upos = np.zeros(srt.shape, dtype=np.int32)
    np.put_along_axis(upos, order, upos_sorted, axis=2)
    upos[rb == _ROW_SENTINEL] = 0          # pad slots -> stage row 0
    nuniq = isnew.sum(axis=2).astype(np.int32)
    uidx = np.zeros(srt.shape, dtype=np.int32)
    fix, bix, six = np.nonzero(isnew)
    uidx[fix, bix, upos_sorted[fix, bix, six]] = srt[fix, bix, six]
    return uidx.reshape(f, s), upos.reshape(f, s), nuniq


def dedup_tables_from_rows(rows: np.ndarray, nblocks: int, block_p: int):
    """Single-factor wrapper over :func:`_dedup_tables_batched`.

    ``rows`` is ``(S,)`` with ``_ROW_SENTINEL`` marking pad slots;
    returns ``(uidx (S,), upos (S,), nuniq (nblocks,))`` int32.
    """
    uidx, upos, nuniq = _dedup_tables_batched(
        np.asarray(rows)[None, :], nblocks, block_p)
    return uidx[0], upos[0], nuniq[0]


@dataclasses.dataclass
class FlycooTensor:
    """A sparse tensor in FLYCOO-TPU format (host-side container).

    ``indices``/``values`` are kept in canonical (input) element order for
    reference computations; ``plans[d]`` carries each mode's kernel layout.
    """

    dims: tuple[int, ...]
    indices: np.ndarray           # (nnz, N) int32, canonical order
    values: np.ndarray            # (nnz,) float32, canonical order
    plans: list[ModePlan]
    # per-mode dedup tables, their row-copy sums and the pinned (val, lrow)
    # layouts, built lazily once (engine init + dma_row_model + the
    # autotuner's exact cost stage all consume the same tables)
    _dedup_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def nmodes(self) -> int:
        return len(self.dims)

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    # ---------------------------------------------------------------- layout
    def pinned_layout(self, d: int) -> tuple[np.ndarray, np.ndarray]:
        """``(val (S_d,) f32, lrow (S_d,) i32)`` of the mode-``d`` kernel
        layout: the values (0 in pads) and each slot's relabeled row local
        to its partition (-1 in pads) — all the compact fused kernel reads
        of a layout. Built once per mode and memoized with the dedup
        tables."""
        key = ("pinned", d)
        cached = self._dedup_cache.get(key)
        if cached is None:
            plan = self.plans[d]
            val = np.zeros(plan.padded_nnz, dtype=np.float32)
            lrow = np.full(plan.padded_nnz, -1, dtype=np.int32)
            val[plan.slot_of_elem] = self.values
            rel = plan.row_relabel[self.indices[:, d]].astype(np.int64)
            lrow[plan.slot_of_elem] = (rel % plan.rows_pp).astype(np.int32)
            cached = self._dedup_cache[key] = (val, lrow)
        return cached

    def _slot_rows(self, d: int) -> np.ndarray:
        """(N-1, S_d) int32 factor row per mode-``d`` slot for every input
        mode ``w != d`` in ascending mode order (sentinel marks pads)."""
        plan = self.plans[d]
        in_modes = [w for w in range(self.nmodes) if w != d]
        rows = np.full((len(in_modes), plan.padded_nnz), _ROW_SENTINEL,
                       dtype=np.int32)
        rows[:, plan.slot_of_elem] = self.indices[:, in_modes].T
        return rows

    def dedup_tables(self, d: int):
        """Per-block factor-row dedup tables for the mode-``d`` layout.

        Returns ``(uidx (N-1, S_d) i32, upos (S_d, N-1) i32,
        nuniq (N-1, nblocks) i32)`` over the input modes ``w != d`` in
        ascending mode order (matching the kernels' factor operand order).
        Built once per mode and memoized on the tensor.
        """
        cached = self._dedup_cache.get(d)
        if cached is None:
            with _obs_span("plan.dedup_tables", mode=d):
                plan = self.plans[d]
                uidx, upos, nuniq = _dedup_tables_batched(
                    self._slot_rows(d), plan.nblocks, plan.block_p)
                cached = (uidx, np.ascontiguousarray(upos.T), nuniq)
            self._dedup_cache[d] = cached
        return cached

    def dedup_row_copies(self, d: int) -> int:
        """``nuniq.sum()`` of :meth:`dedup_tables`: the factor-row DMAs one
        pass of the mode-``d`` fused gather issues. Memoized with the
        tables."""
        key = ("row_copies", d)
        copies = self._dedup_cache.get(key)
        if copies is None:
            _, _, nuniq = self.dedup_tables(d)
            copies = self._dedup_cache[key] = int(nuniq.sum())
        return copies

    def trivial_dedup_tables(self, d: int):
        """Dedup-off tables in the same ``(uidx, upos, nuniq)`` encoding.

        Every slot stages its own factor row (``upos = slot % P``,
        ``nuniq = P`` everywhere, pad slots stage row 0), so the fused
        compact kernels run unchanged but issue one row DMA per slot —
        the ``dedup=False`` point of the plan space, letting the autotuner
        price the dedup preprocessing against its DMA savings.
        """
        plan = self.plans[d]
        nm1 = self.nmodes - 1
        rows = self._slot_rows(d)
        uidx = np.where(rows == _ROW_SENTINEL, 0, rows)
        upos = np.repeat(
            (np.arange(plan.padded_nnz, dtype=np.int32)
             % plan.block_p)[:, None], nm1, axis=1)
        nuniq = np.full((nm1, plan.nblocks), plan.block_p, dtype=np.int32)
        return uidx, upos, nuniq

    def dma_row_model(self, d: int) -> dict:
        """Modeled factor-row DMA copies for the mode-``d`` fused gather:
        per-slot copies (``nblocks * P`` per input factor — what the
        non-dedup pipeline issues) vs per-block-unique copies
        (``sum nuniq``). The ratio is the in-block hot-row re-fetch factor
        the dedup stage removes."""
        plan = self.plans[d]
        nm1 = self.nmodes - 1
        rows = self.dedup_row_copies(d)
        per_slot = plan.nblocks * plan.block_p * nm1
        return {
            "per_slot_rows": int(per_slot),
            "dedup_rows": rows,
            "dedup_reduction_x": float(per_slot / max(rows, 1)),
        }

    # -------------------------------------------------------------- metadata
    def memory_bits_per_element(self, float_bits: int = 32) -> float:
        """Paper Sec. 3.5.1: N*log2(|X|) + sum_h log2(I_h) + delta_float."""
        n = self.nmodes
        return (
            n * math.log2(max(self.nnz, 2))
            + sum(math.log2(max(i, 2)) for i in self.dims)
            + float_bits
        )

    def load_balance(self) -> list[dict]:
        return [p.load_balance() for p in self.plans]


def build_flycoo(
    indices: np.ndarray,
    values: np.ndarray,
    dims: Sequence[int],
    kappa: int | Sequence[int] | None = None,
    rows_pp: int | None = None,
    block_p: int = 128,
    schedule: str = DEFAULT_SCHEDULE,
    degrees: Sequence[np.ndarray] | None = None,
    plans: Sequence[ModePlan] | None = None,
) -> FlycooTensor:
    """Preprocess a COO tensor into FLYCOO-TPU format (paper Sec. 5.7 cost:
    O(nnz log nnz) per mode, touching only nonzeros — never the index space).

    ``kappa`` may be per-mode (a sequence) — the distributed factory path
    rounds each mode's partition count to the device count. ``degrees``
    (per-mode ``bincount`` vectors) lets the plan cache hand down the
    histograms it already computed for its signature; ``plans`` skips
    :func:`plan_mode` entirely (the cache-hit path — caller guarantees the
    plans match this element list).
    """
    indices = np.ascontiguousarray(np.asarray(indices, dtype=np.int32))
    values = np.ascontiguousarray(np.asarray(values, dtype=np.float32))
    assert indices.ndim == 2 and indices.shape[0] == values.shape[0]
    n = indices.shape[1]
    assert len(dims) == n and n >= 3, "paper targets tensors of mode >= 3"
    if plans is None:
        # one transposed copy so every mode's plan reads a contiguous column
        idx_t = np.ascontiguousarray(indices.T)
        for d in range(n):
            assert idx_t[d].min(initial=0) >= 0
            assert idx_t[d].max(initial=0) < dims[d]
        kappas = ([kappa] * n if kappa is None or np.isscalar(kappa)
                  else list(kappa))
        plans = []
        for d in range(n):
            with _obs_span("plan.mode", mode=d, nnz=int(values.shape[0])):
                plans.append(plan_mode(
                    idx_t[d], int(dims[d]), d, kappa=kappas[d],
                    rows_pp=rows_pp, block_p=block_p, schedule=schedule,
                    degrees=None if degrees is None else degrees[d]))
    else:
        # cache-hit path: caller (the plan cache) guarantees the plans
        # match this element list — skip the O(nnz) validation rescan
        plans = list(plans)
        assert len(plans) == n
    return FlycooTensor(tuple(int(x) for x in dims), indices, values, plans)
