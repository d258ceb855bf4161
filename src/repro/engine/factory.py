"""Declarative plan/backend factory: ``PlanSpec`` / ``PlanSpace`` /
``make_engine``.

Before this layer, every callsite hand-assembled an ``ExecutionConfig``,
chose a ``DistConfig`` exchange, decided whether to pre-build a
``FlycooTensor`` (and with which kappa rounding for sharding), and plumbed
the knobs through ``engine.init`` / ``dist.shard_state`` separately. The
factory collapses that into one declarative object:

``PlanSpec``
    One *point* in the plan space — every searchable knob (block size P,
    block schedule, kappa policy, VMEM budget, dedup, fused remap, backend,
    distributed exchange) in a single frozen dataclass. ``to_config()`` /
    ``to_dist_config()`` derive the engine- and distribution-layer configs.

``PlanSpace``
    A *set* of candidate values per searchable dimension (the autotuner's
    search domain). ``specs()`` enumerates the cartesian product as
    ``PlanSpec`` points; skewed-irrelevant combinations (e.g. dedup under
    the ``rect`` schedule, where no dedup tables exist) are canonicalized
    away so the space has no duplicate semantics.

``make_engine``
    The single entry point: COO triple or prebuilt tensor + spec ->
    device-resident state, going through the sparsity-signature plan cache
    (:mod:`repro.core.plancache`) so streaming re-inits skip ``plan_mode``,
    and through ``dist.shard_state`` when a mesh is given (per-mode kappa
    rounded to the device count via ``ExecutionConfig.kappa_for``).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Sequence

from repro.resilience import chaos as _chaos
from repro.resilience.ladder import (classify, record_degradation,
                                     resolve_policy)

from .config import SCHEDULES, ExecutionConfig
from .dist import EXCHANGES, DistConfig, shard_state

# Searchable spec fields, in enumeration order (PlanSpace dimensions).
SPACE_DIMS = ("backend", "schedule", "block_p", "rows_pp",
              "vmem_budget_bytes", "dedup", "fuse_remap", "exchange",
              "residency", "chunk_nnz")


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """One point in the plan space (frozen — usable as a dict/jit key).

    Engine knobs mirror :class:`~repro.engine.config.ExecutionConfig`;
    ``exchange`` is the distributed remap exchange schedule (consumed only
    when :func:`make_engine` is given a mesh).
    """

    backend: str = "xla"
    schedule: str = "compact"
    block_p: int = 128
    kappa_policy: str = "vmem"
    kappa: int | None = None
    rows_pp: int | None = None
    vmem_budget_bytes: int | None = None
    rank_hint: int = 32
    dedup: bool = True
    fuse_remap: bool = True
    interpret: bool | None = None
    exchange: str = "permute"
    residency: str = "auto"
    chunk_nnz: int | None = None
    device_budget_bytes: int | None = None
    stream_ring: int = 2
    #: degradation-ladder default for engines built from this spec:
    #: ``None`` defers to ``make_engine(ladder=...)`` and the ambient
    #: ``REPRO_LADDER`` policy; ``True``/``False`` force it per spec.
    ladder: bool | None = None

    def __post_init__(self):
        if self.exchange not in EXCHANGES:
            raise ValueError(
                f"exchange {self.exchange!r} not in {EXCHANGES}")
        # delegate the remaining validation to ExecutionConfig
        self.to_config()

    def to_config(self) -> ExecutionConfig:
        return ExecutionConfig(
            backend=self.backend, interpret=self.interpret,
            block_p=self.block_p, kappa_policy=self.kappa_policy,
            kappa=self.kappa, rows_pp=self.rows_pp,
            fuse_remap=self.fuse_remap, dedup=self.dedup,
            vmem_budget_bytes=self.vmem_budget_bytes,
            rank_hint=self.rank_hint, schedule=self.schedule,
            residency=self.residency, chunk_nnz=self.chunk_nnz,
            device_budget_bytes=self.device_budget_bytes,
            stream_ring=self.stream_ring)

    def to_dist_config(self, data_axis: str = "data") -> DistConfig:
        return DistConfig(data_axis=data_axis, exchange=self.exchange)

    def canonical(self) -> "PlanSpec":
        """Collapse knob settings with identical semantics to one point:
        dedup only exists for needs_dedup backends under ``compact``;
        fused remap only for backends exposing ``fused_remap``; streaming
        knobs only for the streaming tier; and the VMEM budget is made
        explicit from ``device_budget_bytes`` (``derive_vmem_budget``)
        when only the device budget is given — ONE budget source of truth,
        so residency, ``rows_pp``, and chunking can never silently
        contradict each other."""
        from .backends import get_backend
        from .config import derive_vmem_budget

        backend = get_backend(self.backend)
        spec = self
        if self.schedule != "compact" or \
                not getattr(backend, "needs_dedup", False):
            spec = dataclasses.replace(spec, dedup=True)
        if getattr(backend, "fused_remap", None) is None:
            spec = dataclasses.replace(spec, fuse_remap=True)
        if spec.vmem_budget_bytes is None and \
                spec.device_budget_bytes is not None:
            spec = dataclasses.replace(
                spec,
                vmem_budget_bytes=derive_vmem_budget(
                    spec.device_budget_bytes))
        if spec.residency == "auto" and spec.device_budget_bytes is None:
            # auto without a budget can only ever resolve to full
            spec = dataclasses.replace(spec, residency="full")
        if spec.residency == "full":
            spec = dataclasses.replace(spec, chunk_nnz=None, stream_ring=2)
        return spec


@dataclasses.dataclass(frozen=True)
class PlanSpace:
    """Candidate values per searchable knob (the autotuner's domain).

    Each field lists the values that dimension may take; ``base`` carries
    the non-searched remainder (kappa policy, rank hint, interpret mode).
    """

    backend: tuple = ("pallas_fused",)
    schedule: tuple = SCHEDULES
    block_p: tuple = (64, 128, 256)
    rows_pp: tuple = (None,)
    vmem_budget_bytes: tuple = (None,)
    dedup: tuple = (True, False)
    fuse_remap: tuple = (True,)
    exchange: tuple = ("permute",)
    residency: tuple = ("auto",)
    chunk_nnz: tuple = (None,)
    base: PlanSpec = PlanSpec()

    def specs(self) -> tuple[PlanSpec, ...]:
        """The cartesian product as canonicalized, deduplicated PlanSpecs
        (deterministic enumeration order — the autotuner's tie-break)."""
        seen: dict[PlanSpec, None] = {}
        axes = [getattr(self, f) for f in SPACE_DIMS]
        for combo in itertools.product(*axes):
            spec = dataclasses.replace(
                self.base, **dict(zip(SPACE_DIMS, combo))).canonical()
            seen.setdefault(spec, None)
        return tuple(seen)

    @property
    def size(self) -> int:
        return len(self.specs())


def make_engine(tensor, spec: PlanSpec | None = None, *,
                start_mode: int = 0, cache=None, mesh=None,
                data_axis: str = "data", ladder=None, resume=None):
    """Build a device-resident engine from one declarative ``spec``.

    ``tensor`` is a raw COO triple ``(indices, values, dims)`` or a
    prebuilt :class:`~repro.core.flycoo.FlycooTensor` (its plans win).
    ``cache`` is a :class:`repro.core.plancache.PlanCache` (``None`` uses
    the process-wide default; pass ``cache=False`` to force cold planning).
    With ``mesh``, the state is sharded via ``dist.shard_state`` under the
    spec's exchange schedule, and raw COO input is planned with per-mode
    kappa rounded to the device count.

    The spec's ``residency`` picks the memory tier: ``"full"`` returns a
    device-resident ``EngineState`` (or ``DistState`` with a mesh),
    ``"stream"`` the out-of-core ``StreamState``
    (:mod:`repro.engine.stream`), and ``"auto"`` compares the resident
    footprint (:func:`repro.engine.stream.resident_bytes`) against
    ``device_budget_bytes`` — tensors that don't fit stream, tensors that
    do stay resident.

    ``ladder`` (``True`` / :class:`repro.resilience.LadderPolicy`)
    enables the residency rung of the degradation ladder: if placing the
    *full* layout OOMs on a single device, the factory falls back to the
    streaming tier (recorded as a ``resilience_degradations`` counter +
    span — never silent) instead of dying. ``ladder=None`` defers first
    to ``spec.ladder``, then to the ambient ``REPRO_LADDER`` env policy
    (:func:`repro.resilience.ladder.from_env`) — fleet defaults need no
    code changes.

    ``resume`` (a :class:`repro.resilience.Snapshot`) is validated
    against this engine's problem before any state is built: the snapshot
    must carry one factor per mode with matching row counts, so a resumed
    ALS loop can never silently continue from a different tensor's
    factors. (The ALS entry points additionally match the full content
    fingerprint — this is the structural floor.)
    """
    from repro.core.flycoo import FlycooTensor
    from repro.core.plancache import DEFAULT_CACHE
    from repro.obs.trace import span

    from .api import init
    from .stream import resident_bytes, stream_init

    spec = (spec or PlanSpec()).canonical()
    config = spec.to_config()
    if ladder is None:
        ladder = spec.ladder
    policy = resolve_policy(ladder)
    if cache is None:
        cache = DEFAULT_CACHE
    elif cache is False:
        cache = None

    if resume is not None:
        dims = (tensor.dims if isinstance(tensor, FlycooTensor)
                else tuple(int(d) for d in tensor[2]))
        shapes = tuple(int(f.shape[0]) for f in resume.factors)
        if shapes != tuple(dims):
            raise ValueError(
                f"snapshot {resume.path!r} does not match this problem: "
                f"factor rows {shapes} != dims {tuple(dims)}")

    with span("factory.make_engine", backend=spec.backend,
              schedule=spec.schedule, residency=spec.residency,
              sharded=mesh is not None) as sp:
        if mesh is not None and not isinstance(tensor, FlycooTensor):
            # raw COO + mesh: per-mode kappa rounded to the device count so
            # every device owns an equal, contiguous run of partitions
            indices, values, dims = tensor
            n_dev = int(mesh.shape[data_axis])
            kappas = [config.kappa_for(int(d), n_dev) for d in dims]
            builder = cache.get_tensor if cache is not None else None
            if builder is None:
                from repro.core.flycoo import build_flycoo as builder
            tensor = builder(indices, values, dims, kappa=kappas,
                             rows_pp=config.resolve_rows_pp(),
                             block_p=config.block_p,
                             schedule=config.schedule)

        residency = spec.residency
        if residency == "auto":
            # plans are needed to size the resident footprint; build once
            # through the cache and hand the planned tensor down either tier
            from .api import _as_flycoo

            tensor = _as_flycoo(tensor, config, cache=cache)
            over = (config.device_budget_bytes is not None
                    and resident_bytes(tensor, config)
                    > config.device_budget_bytes)
            residency = "stream" if (over and mesh is None) else "full"
        sp.set("resolved_residency", residency)

        if residency == "full":
            cz = _chaos.active()
            try:
                if cz is not None:
                    cz.on_resident_init()
                state = init(tensor, config, start_mode, cache=cache,
                             _rotating=mesh is not None)
            except Exception as exc:
                # residency rung of the degradation ladder: the full
                # layout doesn't fit -> stream it (single-device only;
                # bitwise-identical results, see engine.stream)
                if (policy is None or mesh is not None
                        or classify(exc) != "oom"):
                    raise
                record_degradation("oom", "full", "stream",
                                   site="factory.residency")
                sp.set("resolved_residency", "stream")
                residency = "stream"
            else:
                if mesh is None:
                    return state
                return shard_state(state, mesh,
                                   spec.to_dist_config(data_axis))

        if mesh is not None:
            raise ValueError(
                "residency='stream' is a single-device tier; drop mesh "
                "or use residency='full'")
        return stream_init(tensor, config, start_mode, cache=cache)


__all__ = ["PlanSpec", "PlanSpace", "make_engine", "SPACE_DIMS"]
