"""Distributed spMTTKRP — deprecated stateful shims over ``repro.engine.dist``.

The implementation moved to :mod:`repro.engine.dist`: a sharded pytree
``DistState`` (``shard_state``) executed by pure functions
(``dist_mttkrp`` / ``dist_all_modes`` — the latter ONE jitted ``lax.scan``
under ``shard_map``), with the dynamic remap exchanged via a precomputed
static ``collective_permute`` schedule instead of this module's original
``all_gather`` of the full element list (that baseline survives as
``DistConfig(exchange="all_gather")`` for measurement). See DESIGN.md §6
and the migration table in :mod:`repro.core`.

This module keeps the original surface alive:

  * :func:`build_sharded_flycoo` — FLYCOO preprocessing with per-device
    partition rounding, now delegating to
    :meth:`repro.engine.ExecutionConfig.kappa_for`;
  * :class:`DistributedMTTKRP` — a thin deprecation shim over the new
    subsystem (mirroring how ``MTTKRPExecutor`` shims ``repro.engine``).
    Unlike the original it works from *any* resident mode (the
    ``current_mode == 0`` assertion is gone) and gained ``reset()``.

New code should import from :mod:`repro.engine.dist`.
"""
from __future__ import annotations

import warnings
from typing import Sequence

import jax
import numpy as np

from repro import engine as _engine
from repro.engine import ExecutionConfig
from repro.engine.dist import (DistConfig, dist_all_modes, dist_mttkrp,
                               shard_map, shard_state)  # noqa: F401

from .flycoo import FlycooTensor
from .partition import plan_mode


def build_sharded_flycoo(indices, values, dims, n_dev: int,
                         rows_pp: int = 512, block_p: int = 128,
                         schedule: str | None = None) -> FlycooTensor:
    """FLYCOO preprocessing with kappa forced to a multiple of n_dev, so
    each device owns an equal, contiguous run of partitions (and hence
    rows and blocks — the compact schedule keeps blocks partition-major).
    The rounding rule lives in :meth:`ExecutionConfig.kappa_for`."""
    indices = np.asarray(indices, np.int32)
    values = np.asarray(values, np.float32)
    cfg = ExecutionConfig(rows_pp=rows_pp, block_p=block_p,
                          **({} if schedule is None
                             else {"schedule": schedule}))
    plans = [
        plan_mode(indices[:, d], int(dims[d]), d,
                  kappa=cfg.kappa_for(int(dims[d]), n_dev), block_p=block_p,
                  schedule=cfg.schedule)
        for d in range(len(dims))
    ]
    return FlycooTensor(tuple(int(x) for x in dims), indices, values, plans)


class DistributedMTTKRP:
    """DEPRECATED stateful wrapper around :mod:`repro.engine.dist`.

    Threads an immutable sharded ``DistState`` through the functional API.
    ``all_modes`` works from *any* resident mode and ``reset()`` returns to
    the pristine start-mode layout, matching the ``MTTKRPExecutor`` shim.
    The remap exchange defaults to the collective_permute schedule; pass
    ``exchange="all_gather"`` for the original baseline.
    """

    def __init__(self, tensor: FlycooTensor, mesh, data_axis: str = "data",
                 model_axis: str | None = None, exchange: str = "permute"):
        warnings.warn(
            "DistributedMTTKRP is deprecated; use repro.engine.dist "
            "(shard_state/dist_mttkrp/dist_all_modes) — see repro.core "
            "docstring for the migration table", DeprecationWarning,
            stacklevel=2)
        self.tensor = tensor
        self.mesh = mesh
        self.da = data_axis
        self.ma = model_axis
        self.n_dev = mesh.shape[data_axis]
        self.config = ExecutionConfig()
        self.dist = DistConfig(data_axis=data_axis, model_axis=model_axis,
                               exchange=exchange)
        self._dstate = shard_state(
            _engine.init(tensor, self.config, _rotating=True), mesh,
            self.dist)
        self.row_relabel = list(self._dstate.relabel)

    # ------------------------------------------------------------ state view
    @property
    def state(self):
        """The underlying functional ``DistState`` (read-only)."""
        return self._dstate

    @property
    def current_mode(self) -> int:
        return self._dstate.mode

    @property
    def layout(self) -> dict:
        """Mesh-sharded global layout arrays (device-major numbering)."""
        return {"val": self._dstate.val, "idx": self._dstate.idx,
                "alpha": self._dstate.alpha}

    # ------------------------------------------------------------ execution
    def step(self, factors: Sequence[jax.Array]) -> jax.Array:
        """MTTKRP for the current mode + cross-device remap; rotate."""
        out, self._dstate = dist_mttkrp(self._dstate, tuple(factors))
        return out

    def all_modes(self, factors: Sequence[jax.Array]) -> list[jax.Array]:
        """All-modes MTTKRP (one scanned shard_map dispatch), from ANY
        current mode; returns outputs indexed by mode d."""
        outs, self._dstate = dist_all_modes(self._dstate, tuple(factors))
        return outs

    def reset(self) -> None:
        """Return to the pristine start-mode sharded layout."""
        self._dstate = shard_state(
            _engine.init(self.tensor, self.config, _rotating=True),
            self.mesh, self.dist)
