"""spMTTKRP along all modes (paper Alg. 2/4/5) — deprecated stateful shims.

The implementation now lives in :mod:`repro.engine` as pure functions over
a pytree ``EngineState`` (``engine.init`` / ``engine.mttkrp`` /
``engine.all_modes`` — the latter a single jitted ``lax.scan`` over the
mode rotation). This module keeps the original surface alive:

  * :func:`mttkrp_ref` — the COO oracle (unchanged, still the test anchor);
  * :func:`mode_step` — the one-mode EC+remap jit, now resolving its
    elementwise-computation backend through the engine's registry instead
    of string dispatch;
  * :class:`MTTKRPExecutor` — a thin deprecation shim over the engine.
    It no longer requires starting at mode 0 and gained ``reset()``.

New code should import from :mod:`repro.engine`. Migration table:

  ===============================  =====================================
  old (stateful)                   new (functional)
  ===============================  =====================================
  ``MTTKRPExecutor(t, backend=b)`` ``s = engine.init(t,
                                   ExecutionConfig(backend=b))``
  ``exe.step(factors)``            ``out, s = engine.mttkrp(s, factors)``
  ``exe.all_modes(factors)``       ``outs, s = engine.all_modes(s,
                                   factors)``
  ``exe.layout["val"]`` etc.       ``s.val`` / ``s.idx`` / ``s.alpha``
  ``exe.current_mode``             ``s.mode``
  ===============================  =====================================
"""
from __future__ import annotations

import functools
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import engine as _engine
from repro.engine import ExecutionConfig
from repro.engine.backends import compute_lrow, get_backend  # noqa: F401
from repro.engine.state import ModeStatic

from .flycoo import FlycooTensor


# --------------------------------------------------------------------------
# Reference oracle (canonical COO order, no FLYCOO machinery).
# --------------------------------------------------------------------------
def mttkrp_ref(indices, values, factors, mode: int, dim: int):
    """Pure-jnp oracle: out[i_d, r] = sum_nnz val * prod_{w!=d} F_w[i_w, r]."""
    partials = values[:, None].astype(jnp.float32)
    for w, f in enumerate(factors):
        if w == mode:
            continue
        partials = partials * f[indices[:, w]]
    return jax.ops.segment_sum(partials, indices[:, mode], num_segments=dim)


# --------------------------------------------------------------------------
# Compat wrappers over the engine's backend registry (benchmarks import
# these; the registry is the source of truth).
# --------------------------------------------------------------------------
def _ec_xla(layout, factors, mode: int, *, rows_pp, blocks_pp, block_p,
            kappa, schedule: str = "rect", nblocks: int = -1):
    """Compact-schedule layouts must carry the ``bpart`` descriptor in
    ``layout`` (pass ``schedule="compact"``/``nblocks`` from the plan)."""
    plan = ModeStatic(kappa=kappa, rows_pp=rows_pp, blocks_pp=blocks_pp,
                      block_p=block_p, dim=0, nblocks=nblocks,
                      schedule=schedule)
    return get_backend("xla")(layout, tuple(factors), mode, plan=plan,
                              config=ExecutionConfig())


def _ec_pallas(layout, factors, mode: int, interpret: bool, *, kappa,
               rows_pp, blocks_pp, block_p, schedule: str = "rect",
               nblocks: int = -1):
    plan = ModeStatic(kappa=kappa, rows_pp=rows_pp, blocks_pp=blocks_pp,
                      block_p=block_p, dim=0, nblocks=nblocks,
                      schedule=schedule)
    config = ExecutionConfig(backend="pallas", interpret=interpret)
    return get_backend("pallas")(layout, tuple(factors), mode, plan=plan,
                                 config=config)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "rows_pp", "blocks_pp", "block_p", "kappa",
                     "next_size", "backend", "interpret", "schedule",
                     "nblocks"),
)
def mode_step(layout, factors, row_relabel_d, *, mode: int, rows_pp: int,
              blocks_pp: int, block_p: int, kappa: int, next_size: int,
              backend: str = "xla", interpret: bool = False,
              schedule: str = "rect", nblocks: int = -1):
    """One iteration of Alg. 5's mode loop: EC (Alg. 2) + remap (Alg. 3).

    Returns (out_rel, next_layout). ``out_rel`` is the mode-d MTTKRP result
    in relabeled row space; caller maps back with ``row_relabel``. Kept for
    per-mode benchmarking; the scanned path is ``engine.all_modes``. Under
    ``schedule="compact"`` pass ``nblocks`` and put the plan's ``bpart``
    descriptor in ``layout``.
    """
    nmodes = layout["idx"].shape[1]
    plan = ModeStatic(kappa=kappa, rows_pp=rows_pp, blocks_pp=blocks_pp,
                      block_p=block_p, dim=int(row_relabel_d.shape[0]),
                      nblocks=nblocks, schedule=schedule)
    s = layout["val"].shape[0]
    if s != plan.padded_nnz:
        # The usual cause: a compact-schedule layout (build_flycoo's
        # default) driven with the rect-default kwargs. A balanced compact
        # layout coincides with the rect one slot-for-slot, so equal sizes
        # are always safe; unequal means wrong partition arithmetic ahead.
        raise ValueError(
            f"layout has {s} slots but the {schedule!r} schedule expects "
            f"{plan.padded_nnz}; for compact-schedule plans pass "
            "schedule='compact', nblocks=plan.nblocks and include "
            "layout['bpart'] (= plan.block_part)")
    if schedule == "compact" and layout.get("bpart") is None:
        raise KeyError(
            "compact-schedule layout needs the 'bpart' block->partition "
            "descriptor (plan.block_part)")
    config = ExecutionConfig(backend=backend, interpret=interpret)
    alive = layout["alpha"][:, mode] >= 0
    lrow = compute_lrow(layout["idx"][:, mode], row_relabel_d, rows_pp, alive)
    ec_layout = {"val": layout["val"], "idx": layout["idx"], "lrow": lrow,
                 "bpart": layout.get("bpart")}
    out_rel = get_backend(config)(ec_layout, tuple(factors), mode, plan=plan,
                                  config=config)

    # ---- Alg. 3: dynamic remap into the mode-(d+1) layout. -----------------
    nxt = (mode + 1) % nmodes
    dst = layout["alpha"][:, nxt]
    sdst = jnp.where(alive, dst, next_size)  # park pads out of range -> drop
    next_layout = {
        "val": jnp.zeros((next_size,), jnp.float32)
        .at[sdst].set(layout["val"], mode="drop", unique_indices=True),
        "idx": jnp.zeros((next_size, nmodes), jnp.int32)
        .at[sdst].set(layout["idx"], mode="drop", unique_indices=True),
        "alpha": jnp.full((next_size, nmodes), -1, jnp.int32)
        .at[sdst].set(layout["alpha"], mode="drop", unique_indices=True),
    }
    return out_rel, next_layout


# --------------------------------------------------------------------------
# Deprecated host-side driver (Alg. 5) — delegates to repro.engine.
# --------------------------------------------------------------------------
class MTTKRPExecutor:
    """DEPRECATED stateful wrapper around :mod:`repro.engine`.

    The executor used to own mutable layout state and a host-side mode
    loop; it now merely threads an immutable ``EngineState`` through the
    functional API. Unlike the original, ``all_modes`` works from *any*
    resident mode (the mode-0 assertion is gone) and ``reset()`` returns
    the executor to the mode-0 layout.
    """

    def __init__(self, tensor: FlycooTensor, backend: str = "xla",
                 interpret: bool = False):
        warnings.warn(
            "MTTKRPExecutor is deprecated; use repro.engine "
            "(init/mttkrp/all_modes) — see repro.core.mttkrp docstring "
            "for the migration table", DeprecationWarning, stacklevel=2)
        self.tensor = tensor
        self.backend = backend
        self.interpret = interpret
        self.plans = tensor.plans
        # interpret=False historically meant "library default", which off-TPU
        # must interpret anyway; map it to the config's auto mode.
        self.config = ExecutionConfig(backend=backend,
                                      interpret=True if interpret else None)
        self._state = _engine.init(tensor, self.config, _rotating=True)
        # note: out_user[v] = out_rel[row_relabel[v]] (relabel is old->new)
        self.row_relabel = list(self._state.relabel)

    # ------------------------------------------------------------ state view
    @property
    def state(self):
        """The underlying functional ``EngineState`` (read-only)."""
        return self._state

    @property
    def current_mode(self) -> int:
        return self._state.mode

    @property
    def layout(self) -> dict:
        """Resident layout sliced to the current mode's padded size
        (the engine stores it padded to the uniform S_max)."""
        sd = self.plans[self._state.mode].padded_nnz
        return {"val": self._state.val[:sd], "idx": self._state.idx[:sd],
                "alpha": self._state.alpha[:sd]}

    # ------------------------------------------------------------ execution
    def step(self, factors: Sequence[jax.Array]) -> jax.Array:
        """Compute MTTKRP for the current mode; remap to the next; rotate."""
        out, self._state = _engine.mttkrp(self._state, tuple(factors))
        return out

    def all_modes(self, factors: Sequence[jax.Array]) -> list[jax.Array]:
        """All-modes MTTKRP (one scanned dispatch), from ANY current mode;
        returns outputs indexed by mode d."""
        outs, self._state = _engine.all_modes(self._state, tuple(factors))
        return outs

    def reset(self) -> None:
        """Return to the pristine mode-0 layout (re-derives device state
        from the host tensor; cheap relative to preprocessing)."""
        self._state = _engine.init(self.tensor, self.config,
                                   _rotating=True)
