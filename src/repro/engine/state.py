"""Pytree engine state for the functional spMTTKRP engine.

``EngineState`` is the device-resident half of a
:class:`~repro.core.flycoo.FlycooTensor`, in one of two layouts:

* **rotating** — the *current* FLYCOO layout (val/idx/alpha), padded to the
  uniform slot count ``S_max = max_d S_d`` so the same pytree shape serves
  every mode — which is exactly what makes the mode loop a ``lax.scan``
  carry and the T_in/T_out swap a buffer donation instead of a host
  round-trip. Each step remaps it to the next mode (Alg. 3).
* **pinned** — for a backend that reads only ``val`` and ``lrow`` of the
  layout (``pinned_layout``, under the compact schedule): each mode's
  ``(val, lrow)`` held on the device from init on, so no step remaps.
  ``val``/``idx``/``alpha`` are ``None``. It holds ``8 * sum_d S_d`` bytes
  where the rotating triple holds ``(4 + 8N) * S_max``.

Array leaves (pytree children):
  val      (S_max,)     f32   nonzero values, 0 in pads (rotating)
  idx      (S_max, N)   i32   beta — original per-mode indices, 0 in pads
                              (rotating)
  alpha    (S_max, N)   i32   alpha — slot of the element in every mode
                              layout (-1 in pads) (rotating)
  relabel  N x (I_d,)   i32   old row id -> relabeled row id, per mode
  sched    N x ModeSched      per-mode block-schedule tables: the block ->
                              partition descriptor and (compact schedule)
                              the in-block factor-row dedup tables. Unlike
                              the layout triple these never remap — they
                              describe the mode-d slot space itself.
  pinned   N x PinnedMode     each mode's (val, lrow), never remapped
                              (pinned); None on a rotating state

Static aux_data (hashable, part of the jit cache key):
  mode     int                 the mode the next step computes (whose
                               layout is resident, when rotating)
  dims     tuple[int, ...]
  statics  tuple[ModeStatic]   per-mode plan constants (kappa, rows_pp, ...)
  config   ExecutionConfig
  layout   str                 "pinned" | "rotating" (derived from
                               ``pinned``; part of ``aux_key``)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax

from .config import ExecutionConfig


class ModeStatic(NamedTuple):
    """Hashable subset of ``partition.ModePlan`` the kernels need."""

    kappa: int
    rows_pp: int
    blocks_pp: int
    block_p: int
    dim: int
    nblocks: int = -1        # total kernel blocks; -1 = rect default
    schedule: str = "rect"   # "compact" | "rect" block schedule

    @property
    def padded_nnz(self) -> int:
        if self.schedule == "compact":
            return self.nblocks * self.block_p
        return self.kappa * self.blocks_pp * self.block_p

    @property
    def relabeled_rows(self) -> int:
        return self.kappa * self.rows_pp


class ModeSched(NamedTuple):
    """Per-mode device-resident schedule tables (pytree of array leaves).

    ``bpart`` is the ``(nblocks,)`` block -> partition descriptor (present
    for both schedules). The dedup tables (see ``FlycooTensor.
    dedup_tables``) are built for the ``compact`` schedule only and are
    ``None`` under ``rect``:

      uidx   (N-1, S_d)      per-block unique factor rows, front-compacted
      upos   (S_d, N-1)      per-slot stage position among the uniques
      nuniq  (N-1, nblocks)  per-block unique-row counts
    """

    bpart: jax.Array
    uidx: Optional[jax.Array] = None
    upos: Optional[jax.Array] = None
    nuniq: Optional[jax.Array] = None


class PinnedMode(NamedTuple):
    """One mode's layout as the compact fused kernel reads it, held on the
    device from init on (pytree of array leaves):

      val   (S_d,)  f32  nonzero values, 0 in pads
      lrow  (S_d,)  i32  relabeled row local to its partition, -1 in pads
    """

    val: jax.Array
    lrow: jax.Array


def mode_static_from_plan(plan) -> ModeStatic:
    return ModeStatic(kappa=plan.kappa, rows_pp=plan.rows_pp,
                      blocks_pp=plan.blocks_pp, block_p=plan.block_p,
                      dim=plan.dim, nblocks=plan.nblocks,
                      schedule=plan.schedule)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EngineState:
    """Immutable, pytree-registered engine state (see module docstring)."""

    val: Optional[jax.Array]
    idx: Optional[jax.Array]
    alpha: Optional[jax.Array]
    relabel: tuple[jax.Array, ...]
    sched: tuple[ModeSched, ...]
    mode: int
    dims: tuple[int, ...]
    statics: tuple[ModeStatic, ...]
    config: ExecutionConfig
    pinned: Optional[tuple[PinnedMode, ...]] = None

    # ------------------------------------------------------------ derived
    @property
    def nmodes(self) -> int:
        return len(self.dims)

    @property
    def smax(self) -> int:
        """Uniform physical slot count (max over per-mode padded sizes)."""
        return max(s.padded_nnz for s in self.statics)

    @property
    def rmax(self) -> int:
        """Max relabeled-row count over modes (scan output row padding)."""
        return max(s.relabeled_rows for s in self.statics)

    @property
    def imax(self) -> int:
        return max(self.dims)

    @property
    def layout(self) -> str:
        return "rotating" if self.pinned is None else "pinned"

    def aux_key(self):
        """Hashable key identifying every static property of this state."""
        return (self.mode, self.dims, self.statics, self.config, self.layout)

    def replace(self, **kw) -> "EngineState":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------- pytree
    def tree_flatten(self):
        children = (self.val, self.idx, self.alpha, self.relabel,
                    self.sched, self.pinned)
        aux = (self.mode, self.dims, self.statics, self.config)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        val, idx, alpha, relabel, sched, pinned = children
        mode, dims, statics, config = aux
        return cls(val=val, idx=idx, alpha=alpha, relabel=tuple(relabel),
                   sched=tuple(sched), mode=mode, dims=dims,
                   statics=statics, config=config,
                   pinned=None if pinned is None else tuple(pinned))


__all__ = ["EngineState", "ModeStatic", "ModeSched", "PinnedMode",
           "mode_static_from_plan"]
