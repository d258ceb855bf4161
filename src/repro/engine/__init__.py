"""Functional spMTTKRP engine (paper Alg. 5 as pure functions).

Public surface:

  ExecutionConfig                  frozen, hashable execution policy
  EngineState                      pytree layout state (scan/shard_map ready)
  init(tensor, config)             -> EngineState
  mttkrp(state, factors[, mode])   -> (out, EngineState)
  all_modes(state, factors)        -> (outs_by_mode, EngineState), ONE
                                      jitted lax.scan over the mode rotation
  BACKENDS / register_backend / get_backend
                                   elementwise-computation backend registry
                                   (``xla`` | ``pallas`` | ``pallas_fused``
                                   | ``ref``; replaces string-typed
                                   ``backend=`` kwargs). ``pallas_fused`` is
                                   the zero-HBM-intermediate pipeline: the
                                   factor gather runs inside the kernel grid
                                   and the Alg. 3 remap scatter is fused
                                   into the same pass (``fuse_remap`` knob).
                                   Every backend serves both block
                                   schedules: ``schedule="compact"`` (the
                                   default — descriptor-driven grid of real
                                   blocks + in-block factor-row dedup) and
                                   ``"rect"`` (the padded baseline)
  dist (DistConfig / shard_state / dist_mttkrp / dist_all_modes)
                                   multi-device subsystem: EngineState sharded
                                   under shard_map, remap exchanged via a
                                   static collective_permute schedule
  stream (StreamPlan / StreamState / stream_init / stream_mttkrp /
          stream_all_modes / cp_als_stream)
                                   out-of-core residency tier for tensors
                                   larger than device memory: the FLYCOO
                                   layout lives host-side and visits the
                                   device as a double-buffered ring of
                                   partition-aligned chunks
                                   (``stream_ring`` buffers, chunk k+1
                                   uploading while chunk k computes), each
                                   chunk served by the UNCHANGED backend
                                   contract — every backend row below works
                                   streamed, bitwise-identical to the
                                   resident engine; the Alg. 3 remap is
                                   reassembled host-side per chunk (the
                                   streaming analogue of dist's exchange)

Residency — which tier holds the element list:

  ``ExecutionConfig.residency`` / ``PlanSpec.residency`` picks it:
  ``"full"`` (classic device-resident engine), ``"stream"`` (the chunk
  ring), or ``"auto"`` — ``make_engine`` compares the resident footprint
  (``stream.resident_bytes``) against ``device_budget_bytes`` and streams
  exactly when the tensor does not fit. One budget drives everything:
  ``device_budget_bytes`` sizes the chunk ring (``chunk_nnz`` overrides),
  and — via ``derive_vmem_budget`` in ``PlanSpec.canonical()`` — the VMEM
  share that sizes row tiles (``rows_pp``), so the two tiers can never
  disagree about memory. The autotuner prices streamed specs with a
  transfer-bytes term (chunk H2D + remap fragments per hop), so tuned
  chunk sizes are chosen, not guessed.
  PlanSpec / PlanSpace / make_engine
                                   declarative plan+backend factory: one
                                   frozen spec naming every searchable knob
                                   (backend, schedule, block_p, kappa
                                   policy, rows_pp, vmem budget, dedup,
                                   fuse_remap, exchange), canonicalized and
                                   enumerable as a ``PlanSpace``;
                                   ``make_engine(tensor_or_coo, spec)``
                                   builds the FLYCOO layout (through the
                                   sparsity-signature ``PlanCache`` by
                                   default) and returns a ready
                                   ``EngineState`` — pass ``mesh=`` to get a
                                   sharded ``DistState`` instead
  autotune (analytic_cost / modeled_cost / autotune / hill_climb)
                                   cost-model-guided knob search over a
                                   PlanSpace: analytic nnz-histogram ranking
                                   prunes the space, exact modeled cost (pad
                                   slots + dedup DMA rows) picks the winner,
                                   optional measured greedy hill-climb;
                                   deterministic under a fixed seed and never
                                   worse than the default spec on modeled
                                   cost
  ExecutionConfig(dedup=False)     keeps the compact schedule but feeds the
                                   fused kernels trivial identity dedup
                                   tables — an autotunable knob for tensors
                                   whose blocks have no row reuse

Observability (``repro.obs``):

  Every layer is instrumented with hierarchical wall-clock spans —
  ``factory.make_engine`` (cache lookup -> per-mode ``plan.mode`` ->
  dedup tables -> device placement), ``autotune`` stages (analytic /
  exact / measured), ``engine.dispatch`` per jitted call, streamed
  ``stream.mode``/``stream.upload``/``stream.compute``/``stream.remap``
  per chunk, ``dist.shard_state`` + exchange-schedule build, and
  ``cpd.sweep`` with per-sweep fit. The all-modes program names each
  mode's step ``mode<d>`` with ``ec``/``remap``/``fold`` scopes, mapped
  to its compiled instructions by ``api.op_scopes()``, and ``init`` sets
  the ``engine_row_copies`` gauge. Tracing is OFF by default and free
  when off (a single ``is None`` test per span site); enable with
  ``repro.obs.enable()`` or ``REPRO_TRACE=1`` (``REPRO_TRACE=path.json``
  additionally writes a Perfetto-loadable Chrome trace at exit), then
  export with ``obs.write_chrome_trace(path)`` / summarize with
  ``obs.render_report()``.

  ``TRACE_COUNTS`` / ``DISPATCH_COUNTS`` (below) live on the
  ``repro.obs`` metrics registry as the ``engine_traces`` /
  ``engine_dispatches`` counters — same dict-style surface as before
  (``DISPATCH_COUNTS["all_modes"]``, ``reset_counters()``), but exported
  with every trace alongside the stream transfer counters, plan-cache
  outcome taxonomy, and CPD fit gauges. The span-derived streaming
  ``overlap_efficiency`` (``obs.stream_overlap_from_spans``) is the
  profiler-timeline cross-check of ``StreamStats.overlap_efficiency``.

Resilience (``repro.resilience``):

  Long runs survive the failures that used to kill them, and every
  recovery is observable — never silent:

  * **Degradation ladder** — ``ladder=True`` (or a ``LadderPolicy``) on
    ``make_engine`` / ``cp_als`` / ``cp_als_stream`` enables policy-driven
    fallback: a compile/lowering failure steps the backend down
    ``BACKEND_LADDER`` (``pallas_fused -> pallas -> xla -> ref``; every
    rung bitwise-identical), a resident-placement OOM drops residency
    ``full -> stream``, a streamed-chunk OOM halves ``chunk_nnz`` and
    replans (partition-aligned chunks make ANY chunking bitwise-equal),
    and transient ``device_put`` upload failures retry with bounded
    exponential backoff + seeded jitter (attempts surface in
    ``StreamStats.upload_retries``). Each transition lands on the obs
    registry as a ``resilience_degradations`` / ``resilience_retries``
    counter + span.
  * **Checkpoint/resume** — ``checkpoint=dir`` on ``cp_als`` /
    ``cp_als_stream`` writes atomic, checksummed sweep snapshots bound to
    the problem fingerprint; ``resume=True`` restores the newest intact
    one and continues bitwise-identically (at a sweep boundary
    ``(factors, lam)`` are the complete dynamic state). Corrupt blobs are
    quarantined and skipped, same as the ``PlanCache`` disk tier.
  * **NaN guard** — under a ladder policy each sweep is checked for
    NaN/Inf; a burst rolls the sweep back and replays it under a
    stronger ridge (``resilience_recoveries`` counter).
  * **Chaos** — ``REPRO_CHAOS="upload_fail=1,oom_chunk=3,..."`` installs
    deterministic seeded fault injectors through the
    stream/factory/plancache/dispatch hooks (``engine.dist`` dispatch
    included: ``exchange_fail=k``, ``device_lost=k``,
    ``dist_transient=k``); ``obs.resilience_report()`` pairs every
    injected fault with the resilience event that answered it (the CI
    chaos gate asserts ``unanswered == []``).
  * **Distributed resilience** — the ladder extends to the sharded tier.
    Sharded runs write the **v2 sharded snapshot** format: per-device
    factor shards keyed by row offset, plus the saving mesh's
    fingerprint (device count, axis shape, platform) and the
    ``DistConfig`` knobs inside the digest-covered meta. The *problem*
    fingerprint deliberately excludes the mesh, so ``resume=True`` on a
    **different** device count gathers the shards host-side and
    re-shards onto the current mesh — elastic restart, bitwise-equal
    final factors (device-major partition order makes the sweep
    mesh-independent). Dist-specific rungs: an exchange failure steps
    ``collective_permute -> all_gather`` (bitwise by the exchange
    parity guarantee); a device loss shrinks the mesh onto the
    survivors via ``dist.surviving_mesh`` (kappa-divisibility decides
    the survivor count), rebuilds ``DistState``, and rolls back to the
    latest snapshot — re-plan + re-shard, never silent; transient dist
    dispatch failures retry with the same seeded backoff as stream
    uploads (``resilience_retries["dist.dispatch"]``). ``REPRO_LADDER``
    installs an ambient policy from the environment, mirroring
    ``REPRO_CHAOS``.

Migration from the deprecated stateful executor:

  MTTKRPExecutor(t, backend=b)     -> s = engine.init(t, ExecutionConfig(backend=b))
  exe.step(factors)                -> out, s = engine.mttkrp(s, factors)
  exe.all_modes(factors)           -> outs, s = engine.all_modes(s, factors)
  exe.layout / exe.current_mode    -> s.val / s.idx / s.alpha / s.mode
"""
from .config import (ExecutionConfig, KAPPA_POLICIES, RESIDENCIES,
                     SCHEDULES, derive_vmem_budget,
                     platform_default_interpret)
from .state import (EngineState, ModeSched, ModeStatic,
                    mode_static_from_plan)
from .backends import (BACKENDS, register_backend, get_backend,
                       compute_lrow)
from .api import (init, mttkrp, all_modes, scan_jaxpr, scan_hlo,
                  reset_counters, TRACE_COUNTS, DISPATCH_COUNTS, FoldFn)
from . import dist
from .dist import (DistConfig, DistState, ExchangeSchedule, shard_state,
                   dist_mttkrp, dist_all_modes, surviving_mesh)
from .factory import PlanSpec, PlanSpace, make_engine, SPACE_DIMS
from . import autotune
from . import stream
from .stream import (StreamPlan, StreamState, cp_als_stream, plan_stream,
                     plan_stream_cached, resident_bytes, stream_all_modes,
                     stream_init, stream_mttkrp)

__all__ = [
    "ExecutionConfig", "KAPPA_POLICIES", "SCHEDULES", "RESIDENCIES",
    "derive_vmem_budget",
    "platform_default_interpret", "EngineState", "ModeSched", "ModeStatic",
    "mode_static_from_plan", "BACKENDS", "register_backend", "get_backend",
    "compute_lrow", "init", "mttkrp", "all_modes", "scan_jaxpr", "scan_hlo",
    "reset_counters", "TRACE_COUNTS", "DISPATCH_COUNTS", "FoldFn",
    "dist", "DistConfig", "DistState", "ExchangeSchedule", "shard_state",
    "dist_mttkrp", "dist_all_modes", "surviving_mesh",
    "PlanSpec", "PlanSpace", "make_engine", "SPACE_DIMS", "autotune",
    "stream", "StreamPlan", "StreamState", "stream_init", "stream_mttkrp",
    "stream_all_modes", "cp_als_stream", "plan_stream",
    "plan_stream_cached", "resident_bytes",
]
