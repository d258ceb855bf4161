"""CPD via Alternating Least Squares on top of the functional spMTTKRP engine.

For each mode d (Eq. 1 of the paper):
    M_d   = X_(d) * KRP(Y_w, w != d)          <- the paper's kernel
    V_d   = hadamard_{w != d} (Y_w^T Y_w)      (R x R)
    Y_d   = M_d @ pinv(V_d); column-normalize -> lambda

A full ALS sweep is ONE traced program: ``engine.all_modes`` runs the mode
rotation as a jitted ``lax.scan`` and the Gauss-Seidel factor update rides
inside it as the scan's ``fold`` hook — no per-mode host dispatch, and the
layout rotation (the paper's T_in/T_out swap) never leaves the device.

Fit is computed with the standard sparse-CPD identity:
    ||X - X_hat||^2 = ||X||^2 - 2<X, X_hat> + ||X_hat||^2
    <X, X_hat>      = sum_r lambda_r * sum_i M_last[i, r] * Y_last[i, r]
    ||X_hat||^2     = lambda^T (hadamard_w Y_w^T Y_w) lambda
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import engine
from repro.engine import ExecutionConfig
from repro.obs.metrics import gauge as _obs_gauge
from repro.obs.trace import span
from repro.resilience import chaos as _chaos
from repro.resilience import guard as _guard
from repro.resilience.ladder import (classify, next_backend,
                                     record_degradation, resolve_policy)
from repro.resilience.snapshot import as_store, fingerprint

from .flycoo import FlycooTensor
from .mttkrp import mttkrp_ref


def init_factors(key, dims: Sequence[int], rank: int) -> list[jax.Array]:
    keys = jax.random.split(key, len(dims))
    return [jax.random.uniform(k, (d, rank), jnp.float32) for k, d in
            zip(keys, dims)]


# TPU matmuls default to one bf16 pass; the ALS algebra is f32.
_HIGHEST = lax.Precision.HIGHEST


def gram(f: jax.Array) -> jax.Array:
    return jnp.dot(f.T, f, precision=_HIGHEST)


@jax.jit
def _als_update(mttkrp_out, grams_other, eps=1e-8):
    """Y_d = M_d @ pinv(hadamard of other grams); normalize columns."""
    v = grams_other[0]
    for g in grams_other[1:]:
        v = v * g
    # Solve M @ pinv(V): V is PSD (R x R). Relative ridge keeps overcomplete
    # ALS (rank > true rank) stable when V becomes singular.
    r = v.shape[0]
    ridge = eps + 1e-6 * jnp.trace(v) / r
    v = v + ridge * jnp.eye(r, dtype=v.dtype)
    with jax.default_matmul_precision("highest"):   # the solve's matmuls
        y = jnp.linalg.solve(v.T, mttkrp_out.T).T
    lam = jnp.linalg.norm(y, axis=0)
    lam = jnp.where(lam < eps, 1.0, lam)
    return y / lam, lam


def _als_fold(d: int, m_d, factors, lam):
    """Gauss-Seidel update for mode ``d``, traced inside the engine scan."""
    n = len(factors)
    grams_other = tuple(gram(factors[w]) for w in range(n) if w != d)
    y, lam = _als_update(m_d, grams_other)
    return tuple(factors[:d]) + (y,) + tuple(factors[d + 1:]), lam


#: Ridge strength the recovery fold replays a rolled-back sweep under —
#: strong enough to dominate a near-singular gram product that NaN'd the
#: plain solve, small enough to leave a well-conditioned sweep's fixed
#: point essentially unchanged.
RECOVERY_EPS = 1e-3


def _als_fold_recovery(d: int, m_d, factors, lam):
    """The Gauss-Seidel update under the stronger :data:`RECOVERY_EPS`
    ridge — used to replay a sweep after a NaN/Inf burst (see
    ``resilience.guard``). A separate module-level callable because the
    fold's identity is part of the engine's jit cache key."""
    n = len(factors)
    grams_other = tuple(gram(factors[w]) for w in range(n) if w != d)
    y, lam = _als_update(m_d, grams_other, RECOVERY_EPS)
    return tuple(factors[:d]) + (y,) + tuple(factors[d + 1:]), lam


@dataclasses.dataclass
class CPDResult:
    factors: list[jax.Array]
    lam: jax.Array
    fits: list[float]


def cp_als(
    tensor: FlycooTensor,
    rank: int,
    iters: int = 10,
    key=None,
    config: ExecutionConfig | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    track_fit: bool = True,
    mesh=None,
    dist=None,
    *,
    ladder=None,
    checkpoint=None,
    checkpoint_every: int = 1,
    resume: bool = False,
) -> CPDResult:
    """Run CPD-ALS for ``iters`` sweeps over all modes (paper Alg. 5 outer).

    Execution policy comes from ``config``; ``backend``/``interpret`` are
    legacy conveniences that build one (mutually exclusive with ``config``).

    With ``mesh`` (a ``jax.sharding.Mesh`` or ``repro.sharding.ShardingCtx``)
    the engine state shards over the mesh's data axis and every sweep runs
    as ONE ``engine.dist.dist_all_modes`` program — the same scanned fold,
    distributed. ``tensor``'s partition counts must divide over the mesh
    (build with ``core.distributed.build_sharded_flycoo``); ``dist`` is an
    optional ``engine.DistConfig`` (its ``model_axis`` must stay ``None`` —
    the ALS fold needs the full rank on every device).

    Resilience (see :mod:`repro.resilience`):

    * ``ladder``: ``True`` / a :class:`repro.resilience.LadderPolicy`
      enables the degradation ladder — a compile/lowering failure steps
      the backend down ``pallas_fused -> pallas -> xla -> ref`` and
      rebuilds the engine state (bitwise-identical output, every rung) —
      plus the per-sweep NaN/Inf guard: on a burst the sweep is rolled
      back and replayed under the stronger :data:`RECOVERY_EPS` ridge.
      Every transition lands on the obs registry; nothing degrades
      silently.
    * ``checkpoint``: a directory or :class:`repro.resilience.
      SnapshotStore`; every ``checkpoint_every`` completed sweeps the
      ``(factors, lam, fits)`` state is snapshotted atomically under the
      problem fingerprint. ``resume=True`` restores the newest intact
      snapshot *for the same problem* (tensor bytes + rank + config +
      key) and replays only the remaining sweeps — bitwise-identical
      final factors vs an uninterrupted run, because at a sweep boundary
      the layout has rotated back to its start arrangement and
      ``(factors, lam)`` are the complete dynamic state.

    Distributed resilience (``mesh`` given): snapshots are written in the
    sharded v2 format (per-device factor shards + mesh fingerprint, see
    :mod:`repro.resilience.snapshot`) but the problem fingerprint is
    mesh-independent — a run killed on 4 devices resumes on 2 (or 1)
    bitwise-identically, re-sharding onto the *current* mesh. With a
    ladder, two extra rungs activate: an exchange failure steps
    ``collective_permute -> all_gather`` (bitwise-identical by the
    exchange parity guarantee), and a lost device shrinks the mesh —
    the engine state is re-planned and re-sharded on the survivors and
    the run rolls back to the latest snapshot (or the sweep boundary),
    never silently. Transient dispatch failures retry with the same
    seeded backoff stream uploads use.
    """
    if config is None:
        config = ExecutionConfig(backend=backend or "xla",
                                 interpret=interpret)
    elif backend is not None or interpret is not None:
        raise ValueError("pass either config or backend/interpret, not both")
    policy = resolve_policy(ladder)
    if key is None:
        key = jax.random.PRNGKey(0)
    n = tensor.nmodes
    mesh_raw = None
    if mesh is not None:
        from repro.sharding import ShardingCtx

        if isinstance(mesh, ShardingCtx):
            mesh_raw = mesh.mesh
            if dist is None:
                # ALS folds inside the sweep, which needs the full rank
                # on every device — never inherit the ctx's tp axis here.
                dist = engine.DistConfig(data_axis=mesh.data_axis)
        else:
            mesh_raw = mesh
    elif dist is not None:
        raise ValueError("dist config given without a mesh")

    def build_state(cfg):
        # shard_state re-lays the rotating layout across the mesh
        st = engine.init(tensor, cfg, _rotating=mesh is not None)
        if mesh is not None:
            st = engine.dist.shard_state(st, mesh, dist)
        return st

    if mesh is None:
        sweep = engine.all_modes
    else:
        sweep = functools.partial(engine.dist.dist_all_modes,
                                  policy=policy)
    # Everything a start does before its first sweep.
    with span("cpd.start"):
        factors = tuple(init_factors(key, tensor.dims, rank))
        lam = jnp.ones((rank,), jnp.float32)
        state = build_state(config)
        norm_x_sq = float(np.sum(tensor.values.astype(np.float64) ** 2))

    store = as_store(checkpoint)
    fits: list = []
    first = 0
    fp = None
    if store is not None:
        fp = fingerprint(tensor.indices, tensor.values, tensor.dims, rank,
                         config=config, key=key,
                         extra="resident" if mesh is None else "dist")
        if resume:
            snap = store.latest(fp)
            if snap is not None:
                factors = tuple(jnp.asarray(f) for f in snap.factors)
                lam = jnp.asarray(snap.lam)
                fits = list(snap.fits)
                first = snap.sweep
    backend_steps = 0
    i = first
    while i < iters:
        cz = _chaos.active()
        if cz is not None:
            cz.maybe_kill(i)
        prev = (factors, lam)
        rewind = None
        # One dispatch per sweep: scan over modes, ALS update in the fold.
        with span("cpd.sweep", sweep=i, streamed=False) as sp:
            fold = _als_fold
            while True:
                try:
                    outs, state, factors, lam = sweep(
                        state, factors, fold=fold, carry=lam)
                except Exception as exc:
                    if policy is None:
                        raise
                    kind = classify(exc)
                    # Compile/lowering failures happen before any factor
                    # update (the sweep is one program): step the backend
                    # down a rung, rebuild the state from the tensor (at a
                    # sweep boundary the layout bitwise-equals a fresh
                    # init), and retry the sweep.
                    if kind == "compile" \
                            and backend_steps < policy.max_backend_steps:
                        nb = next_backend(state.config.backend)
                        if nb is None:
                            raise
                        backend_steps += 1
                        record_degradation("compile", state.config.backend,
                                           nb, site="cpd.backend", sweep=i)
                        state = build_state(dataclasses.replace(
                            state.config, backend=nb))
                        continue
                    # Exchange failure: step collective_permute ->
                    # all_gather (bitwise-identical by the exchange parity
                    # guarantee) without re-sharding — only the traced
                    # program changes.
                    if kind == "exchange" and mesh is not None \
                            and state.dist.exchange == "permute":
                        record_degradation(
                            "exchange", "permute", "all_gather",
                            site="cpd.exchange", sweep=i)
                        dist = dataclasses.replace(state.dist,
                                                   exchange="all_gather")
                        state = state.replace(dist=dist)
                        continue
                    # Device loss: shrink to the largest viable surviving
                    # mesh, re-plan + re-shard there, and roll back to the
                    # latest snapshot (or this sweep's boundary state).
                    if kind == "device_lost" and mesh is not None:
                        lost = getattr(exc, "lost", 1)
                        old_n = int(state.n_dev)
                        new_mesh = engine.dist.surviving_mesh(
                            mesh_raw, lost,
                            [p.kappa for p in tensor.plans],
                            data_axis=(dist.data_axis if dist is not None
                                       else "data"))
                        new_n = int(np.asarray(
                            new_mesh.devices).reshape(-1).size)
                        record_degradation("device_lost", old_n, new_n,
                                           site="cpd.mesh", sweep=i,
                                           lost=lost)
                        mesh = mesh_raw = new_mesh
                        # Restore from the latest snapshot when there is
                        # one (the real-loss path: device buffers are
                        # gone); otherwise the in-memory sweep-boundary
                        # state is already `prev`, untouched by the
                        # failed dispatch.
                        resume_at = i
                        snap = store.latest(fp) if store is not None \
                            else None
                        if snap is not None:
                            factors = tuple(jnp.asarray(f)
                                            for f in snap.factors)
                            lam = jnp.asarray(snap.lam)
                            fits = list(snap.fits)
                            resume_at = snap.sweep
                        state = build_state(state.config)
                        if resume_at == i:
                            prev = (factors, lam)
                            continue
                        rewind = resume_at
                        break
                    raise
                if cz is not None:
                    factors = tuple(cz.mangle_factors(i, factors))
                if policy is not None \
                        and not _guard.all_finite(factors, lam):
                    if fold is _als_fold_recovery:
                        raise FloatingPointError(
                            f"NaN/Inf burst in sweep {i} persisted "
                            "through the ridge-recovery replay")
                    # Roll back and replay under the stronger ridge: the
                    # layout is bitwise back at its start arrangement, so
                    # the replay sees exactly the pre-sweep problem.
                    _guard.record_recovery("nan_rollback", sweep=i,
                                           streamed=False)
                    factors, lam = prev
                    fold = _als_fold_recovery
                    continue
                break
            if rewind is None and track_fit:
                with span("cpd.fit"):   # the host's sync on the sweep
                    fit = _fit(norm_x_sq, outs[n - 1], factors, lam)
                fits.append(fit)
                sp.set("fit", float(fit))
                _obs_gauge("cpd_fit", "latest ALS fit per tier").set(
                    "resident", float(fit))
        if rewind is not None:
            i = rewind
            continue
        if store is not None and ((i + 1) % checkpoint_every == 0
                                  or i + 1 == iters):
            if mesh is not None:
                store.save(fp, i + 1, list(factors), np.asarray(lam),
                           fits, mesh=mesh_raw, dist=state.dist)
            else:
                store.save(fp, i + 1, [np.asarray(f) for f in factors],
                           np.asarray(lam), fits)
        i += 1
    return CPDResult(factors=list(factors), lam=lam, fits=fits)


def _fit(norm_x_sq: float, m_last, factors, lam) -> float:
    n = len(factors)
    inner = jnp.sum(m_last * (factors[n - 1] * lam[None, :]))
    g = gram(factors[0])
    for f in factors[1:]:
        g = g * gram(f)
    norm_est_sq = jnp.dot(lam, jnp.dot(g, lam, precision=_HIGHEST),
                          precision=_HIGHEST)
    resid_sq = jnp.maximum(norm_x_sq - 2 * inner + norm_est_sq, 0.0)
    return float(1.0 - jnp.sqrt(resid_sq) / np.sqrt(norm_x_sq))


def cp_als_reference(indices, values, dims, rank, iters=10, key=None):
    """Oracle ALS using plain COO mttkrp_ref (no FLYCOO) for tests."""
    if key is None:
        key = jax.random.PRNGKey(0)
    n = len(dims)
    factors = init_factors(key, dims, rank)
    lam = jnp.ones((rank,), jnp.float32)
    norm_x_sq = float(np.sum(np.asarray(values, np.float64) ** 2))
    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    fits = []
    for _ in range(iters):
        m_last = None
        for d in range(n):
            m = mttkrp_ref(indices, values, factors, d, dims[d])
            grams_other = [gram(factors[w]) for w in range(n) if w != d]
            y, lam = _als_update(m, tuple(grams_other))
            factors[d] = y
            m_last = m
        fits.append(_fit(norm_x_sq, m_last, factors, lam))
    return CPDResult(factors=factors, lam=lam, fits=fits)
