"""Functional spMTTKRP engine: ``init`` / ``mttkrp`` / ``all_modes``.

The paper's Alg. 5 as pure functions over a pytree
:class:`~repro.engine.state.EngineState`:

  init(tensor, config)            -> EngineState           (host, once)
  mttkrp(state, factors[, mode])  -> (out, EngineState)    (one mode + remap)
  all_modes(state, factors)       -> (outs, EngineState)   (one jitted scan)

``all_modes`` is a *single* jitted program: ``lax.scan`` over the mode
sequence, each step a ``lax.switch`` into that mode's statically-shaped
elementwise computation + dynamic remap (Alg. 2 + 3). There is no per-mode
Python dispatch, the T_in/T_out layout swap is the scan carry (donated on
TPU/GPU), and the rotation may start at *any* resident mode — the old
executor's ``current_mode == 0`` restriction is gone. On a *pinned* state
(see :mod:`repro.engine.state`) each step reads its mode's own
``(val, lrow)`` and nothing remaps: the carry is the factors alone.

An optional ``fold`` callback runs inside the scan after each mode's
MTTKRP with that mode's output — this is how CPD-ALS updates factor
matrices mode-by-mode (Gauss-Seidel) while keeping the whole sweep one
traced program (see ``repro.core.cpd``).
"""
from __future__ import annotations

import re
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs.metrics import REGISTRY
from repro.obs.trace import get_tracer, span
from repro.resilience import chaos as _chaos

from .backends import (compute_lrow, empty_slots, get_backend, pack_slots,
                       pins_layout, scatter_slots, unpack_slots)
from .config import ExecutionConfig
from .state import (EngineState, ModeSched, ModeStatic, PinnedMode,
                    mode_static_from_plan)

# Fold callback: fold(mode, out_d, factors, carry) -> (factors, carry),
# called inside the traced scan with *static* mode and out_d of shape
# (dims[mode], R). Must be a stable (module-level) callable: its identity
# is part of the jit cache key.
FoldFn = Callable[[int, jax.Array, tuple, object], tuple]

# Observability: traces = how many times a program was (re)built; dispatches
# = how many jitted calls were issued. The benchmarks report the host-loop
# elimination as dispatches-per-sweep (was nmodes, now 1). These live on the
# repro.obs metrics registry (exported with every trace); the module-level
# names and dict-style access (`TRACE_COUNTS["all_modes"]`, `dict(...)`,
# `reset_counters()`) are the stable public surface.
TRACE_COUNTS = REGISTRY.counter(
    "engine_traces", "program (re)builds per entry point")
DISPATCH_COUNTS = REGISTRY.counter(
    "engine_dispatches", "jitted calls issued per entry point")
# Factor-row DMAs one pass of the mode-d EC kernel issues: the sum of the
# dedup tables' per-block unique-row counts, the bound of the kernel's
# row-copy loop (0 where the backend stages no rows through dedup tables).
ROW_COPIES = REGISTRY.gauge(
    "engine_row_copies", "factor-row DMAs per EC kernel pass, per mode")
# Slot records the mode-d step's Alg. 3 remap moves: S_max on a rotating
# state, 0 on a pinned one.
REMAP_SLOTS = REGISTRY.gauge(
    "engine_remap_slots", "slot records the remap moves per step, per mode")

_JIT_CACHE: dict = {}
# Argument shapes (jax.ShapeDtypeStruct, with shardings) of each all-modes
# program's first call, under its _JIT_CACHE key: what op_scopes compiles.
_SCAN_ARGS: dict = {}


def reset_counters() -> None:
    TRACE_COUNTS.clear()
    DISPATCH_COUNTS.clear()


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init(tensor, config: ExecutionConfig | None = None,
         start_mode: int = 0, *, cache=None,
         _rotating: bool = False) -> EngineState:
    """Build the device-resident engine state for ``tensor``.

    ``tensor`` is a prebuilt :class:`~repro.core.flycoo.FlycooTensor` (its
    plans govern the layout) or a raw COO triple ``(indices, values, dims)``
    — then the FLYCOO plans are built here under ``config``'s kappa policy,
    through ``cache`` (a :class:`repro.core.plancache.PlanCache`) when one
    is given so repeated/streaming inits skip ``plan_mode``.

    Where the backend reads only ``val`` and ``lrow`` of the layout
    (:func:`~repro.engine.backends.pins_layout`) the state pins each
    mode's ``(val, lrow)``; otherwise it holds the ``start_mode`` layout,
    padded to the uniform slot count ``S_max`` so every mode shares one
    pytree shape, and remaps it every step. ``_rotating=True`` asks for the
    rotating layout whatever the backend, for callers that re-lay it
    (``engine.dist.shard_state``) or hand it out (the deprecated shims).
    """
    config = config or ExecutionConfig()
    with span("engine.init", start_mode=start_mode) as sp:
        tensor = _as_flycoo(tensor, config, cache=cache)
        n = tensor.nmodes
        if not 0 <= start_mode < n:
            raise ValueError(
                f"start_mode {start_mode} out of range for {n} modes")
        statics = tuple(mode_static_from_plan(p) for p in tensor.plans)
        smax = max(s.padded_nnz for s in statics)
        pinned = not _rotating and pins_layout(config, statics)
        sp.set("nmodes", n)
        sp.set("smax", smax)
        sp.set("layout", "pinned" if pinned else "rotating")
        for d in range(n):
            REMAP_SLOTS.set(d, 0 if pinned else smax)

        with span("engine.host_layout", mode=start_mode):
            if pinned:
                host = [tensor.pinned_layout(d) for d in range(n)]
            else:
                base = tensor.plans[start_mode]
                val = np.zeros(smax, dtype=np.float32)
                idx = np.zeros((smax, n), dtype=np.int32)
                alpha = np.full((smax, n), -1, dtype=np.int32)
                val[base.slot_of_elem] = tensor.values
                idx[base.slot_of_elem] = tensor.indices
                for d in range(n):
                    alpha[base.slot_of_elem, d] = \
                        tensor.plans[d].slot_of_elem.astype(np.int32)

        with span("engine.sched_tables"):
            sched = tuple(_mode_sched(tensor, d, config) for d in range(n))
        with span("engine.device_place"):
            if pinned:
                layout = dict(val=None, idx=None, alpha=None, pinned=tuple(
                    PinnedMode(jnp.asarray(v), jnp.asarray(lr))
                    for v, lr in host))
            else:
                layout = dict(val=jnp.asarray(val), idx=jnp.asarray(idx),
                              alpha=jnp.asarray(alpha))
            state = EngineState(
                relabel=tuple(jnp.asarray(p.row_relabel)
                              for p in tensor.plans),
                sched=sched,
                mode=int(start_mode),
                dims=tensor.dims,
                statics=statics,
                config=config,
                **layout,
            )
    tracer = get_tracer()
    if tracer is not None:
        # The transfers run behind device_place's return; only while
        # tracing, wait for them so the span ends with the state on the
        # device. The first sweep reads these buffers anyway.
        nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
        with tracer.span("engine.upload", bytes=nbytes):
            jax.block_until_ready(state)
    return state


def _mode_sched(tensor, d: int, config: ExecutionConfig) -> ModeSched:
    """Device-resident per-mode schedule tables: the block->partition
    descriptor always; the in-block factor-row dedup tables only when the
    configured backend consumes them (``needs_dedup`` registry attribute —
    the fused Pallas pipeline) under the compact schedule, so xla/ref/
    pallas states skip the per-block sort and the device-resident
    ``(N-1, S_d)`` tables entirely. ``config.dedup=False`` installs the
    trivial tables instead (one row DMA per slot, no host-side sort)."""
    plan = tensor.plans[d]
    bpart = jnp.asarray(plan.block_part)
    if plan.schedule != "compact" or \
            not getattr(get_backend(config), "needs_dedup", False):
        ROW_COPIES.set(d, 0)
        return ModeSched(bpart=bpart)
    if config.dedup:
        uidx, upos, nuniq = tensor.dedup_tables(d)
        ROW_COPIES.set(d, tensor.dedup_row_copies(d))
    else:
        uidx, upos, nuniq = tensor.trivial_dedup_tables(d)
        ROW_COPIES.set(d, int(nuniq.sum()))
    return ModeSched(bpart=bpart, uidx=jnp.asarray(uidx),
                     upos=jnp.asarray(upos), nuniq=jnp.asarray(nuniq))


def _as_flycoo(tensor, config: ExecutionConfig, cache=None):
    from repro.core.flycoo import FlycooTensor, build_flycoo

    if isinstance(tensor, FlycooTensor):
        return tensor
    indices, values, dims = tensor
    kappa = config.kappa if config.kappa_policy == "fixed" else None
    build = cache.get_tensor if cache is not None else build_flycoo
    return build(indices, values, dims, kappa=kappa,
                 rows_pp=config.resolve_rows_pp(),
                 block_p=config.block_p,
                 schedule=config.schedule)


# --------------------------------------------------------------------------
# One mode: EC (Alg. 2/4) + dynamic remap (Alg. 3), statically shaped.
# --------------------------------------------------------------------------
def _mode_branch(d: int, *, statics: Sequence[ModeStatic], smax: int,
                 config: ExecutionConfig, fold: FoldFn | None,
                 pad_out_to: int | None, pinned: bool = False):
    """Build the traced step for (static) mode ``d``.

    Returns a function (layout, relabels, sched, factors, carry) ->
    (next_layout, out, factors, carry). On a rotating state ``layout`` is
    the S_max-padded (val, idx, alpha) triple and ``next_layout`` its remap
    to mode ``d + 1``; on a pinned one ``layout`` is every mode's
    :class:`PinnedMode` and ``next_layout`` is ``None``. ``sched`` holds the
    per-mode schedule tables, and ``out`` is the mode-``d`` MTTKRP in user
    row space, zero-padded to ``pad_out_to`` rows when a uniform stacked
    shape is needed (the scan path).
    """
    plan = statics[d]
    n = len(statics)
    nxt = (d + 1) % n
    sd = plan.padded_nnz
    backend = get_backend(config)
    # Fusing backends (e.g. ``pallas_fused``) emit the Alg. 3 remap scatter
    # inside the EC kernel pass; ``config.fuse_remap=False`` keeps the XLA
    # scatter path as the comparison baseline. A pinned layout has no
    # remap to fuse.
    fused = (getattr(backend, "fused_remap", None)
             if config.fuse_remap and not pinned else None)

    def step(layout, relabels, sched, factors, carry):
        with jax.named_scope(f"mode{d}"):
            return _step(layout, relabels, sched, factors, carry)

    # The scopes name each op's layer in the compiled program's metadata
    # (``op_scopes``); the ops keep the order they had without them, so
    # the optimized HLO differs in metadata only.
    def _rotating_ec(layout3, relabels, sched, factors):
        val, idx, alpha = layout3
        with jax.named_scope("ec"):
            v, ix, al = val[:sd], idx[:sd], alpha[:sd]
            alive = al[:, d] >= 0
            lrow = compute_lrow(ix[:, d], relabels[d], plan.rows_pp, alive)
            layout = {"val": v, "idx": ix, "alpha": al, "lrow": lrow,
                      **sched[d]._asdict()}
            if fused is not None:
                # One Pallas pass: EC + remap; slots beyond S_{d+1} stay
                # empty (the kernel initializes the next layout to the pad
                # pattern).
                out_rel, (nval, nidx, nalpha) = fused(
                    layout, tuple(factors), d, plan=plan, config=config,
                    smax=smax, next_mode=nxt)
            else:
                out_rel = backend(layout, tuple(factors), d, plan=plan,
                                  config=config)
        with jax.named_scope("remap"):
            if fused is not None:
                nval = nval.astype(val.dtype)
                nidx = nidx.astype(idx.dtype)
            else:
                # Alg. 3: conflict-free scatter into the mode-(d+1) layout
                # (pads parked at S_max -> dropped); slots beyond S_{d+1}
                # stay empty.
                dst = jnp.where(alive, al[:, nxt], smax)
                nval, nidx, nalpha = unpack_slots(scatter_slots(
                    dst, pack_slots(v, ix, al), empty_slots(smax, n)))
        return out_rel, (nval, nidx, nalpha)

    def _step(layout, relabels, sched, factors, carry):
        if pinned:
            nl = None
            with jax.named_scope("ec"):
                out_rel = backend({**layout[d]._asdict(),
                                   **sched[d]._asdict()}, tuple(factors), d,
                                  plan=plan, config=config)
        else:
            out_rel, nl = _rotating_ec(layout, relabels, sched, factors)
        with jax.named_scope("ec"):
            # un-relabel -> (I_d, R)
            out = jnp.take(out_rel, relabels[d], axis=0)
        if fold is not None:
            with jax.named_scope("fold"):
                factors, carry = fold(d, out, factors, carry)
        if pad_out_to is not None:
            out = jnp.pad(out, ((0, pad_out_to - plan.dim), (0, 0)))
        return nl, out, factors, carry

    return step


# --------------------------------------------------------------------------
# mttkrp: one mode, one dispatch.
# --------------------------------------------------------------------------
def mttkrp(state: EngineState, factors: Sequence[jax.Array],
           mode: int | None = None):
    """MTTKRP for the resident mode + remap to the next; returns
    ``(out, next_state)``. ``mode`` (optional) must name the resident mode
    — the layout physically *is* mode-``state.mode``'s (a pinned state
    steps the same way, though it holds every mode's)."""
    if mode is not None and mode != state.mode:
        raise ValueError(
            f"state holds the mode-{state.mode} layout; cannot compute "
            f"mode {mode} without rotating (use all_modes or step to it)")
    d = state.mode
    key = ("mttkrp", state.aux_key())
    fn = _JIT_CACHE.get(key)
    if fn is None:
        step = _mode_branch(d, statics=state.statics, smax=state.smax,
                            config=state.config, fold=None,
                            pad_out_to=None,
                            pinned=state.pinned is not None)

        def run(layout, relabels, sched, factors):
            TRACE_COUNTS["mttkrp"] += 1  # trace-time side effect
            nl, out, _, _ = step(layout, relabels, sched, factors, None)
            return nl, out

        fn = _JIT_CACHE[key] = jax.jit(run, donate_argnums=_donate(state))
    _c = _chaos.active()
    if _c is not None:
        _c.on_dispatch(state.config.backend)
    DISPATCH_COUNTS["mttkrp"] += 1
    with span("engine.dispatch", kind="mttkrp", mode=d):
        nl, out = fn(_layout(state), state.relabel, state.sched,
                     tuple(factors))
    return out, _advance(state, nl).replace(mode=(d + 1) % state.nmodes)


def _layout(state: EngineState):
    """The layout a step reads: every mode's ``PinnedMode`` on a pinned
    state, the (val, idx, alpha) triple on a rotating one."""
    if state.pinned is not None:
        return state.pinned
    return (state.val, state.idx, state.alpha)


def _advance(state: EngineState, next_layout) -> EngineState:
    """``state`` holding the layout a program handed back (a pinned state
    gets none: it keeps its own)."""
    if next_layout is None:
        return state
    nval, nidx, nalpha = next_layout
    return state.replace(val=nval, idx=nidx, alpha=nalpha)


def _donate(state: EngineState) -> tuple:
    """Donate the rotating layout into the program that remaps it; a pinned
    layout is read again by every call."""
    if state.pinned is None and state.config.resolve_donate():
        return (0,)
    return ()


# --------------------------------------------------------------------------
# all_modes: one jitted lax.scan over the full rotation.
# --------------------------------------------------------------------------
def _build_scan(state: EngineState, fold: FoldFn | None):
    """The traced all-modes program (pre-jit, for jaxpr inspection).

    Captures only the state's *static* aux (ints/tuples), never its device
    arrays — the built function lives in the long-lived jit cache and must
    not pin the first caller's layout buffers.
    """
    n, m0, smax, imax = state.nmodes, state.mode, state.smax, state.imax
    dims = state.dims
    pinned = state.pinned is not None
    seq = tuple((m0 + i) % n for i in range(n))
    branches = [
        _mode_branch(d, statics=state.statics, smax=smax,
                     config=state.config, fold=fold, pad_out_to=imax,
                     pinned=pinned)
        for d in range(n)
    ]

    def run(layout, relabels, sched, factors, carry):
        TRACE_COUNTS["all_modes"] += 1  # trace-time side effect

        # A rotating layout is carried from step to step; a pinned one
        # never changes, so the steps read it from outside the scan and
        # its carry slot stays None.
        def body(sc, mode_t):
            moving, factors, carry = sc
            nl, out, factors, carry = lax.switch(
                mode_t,
                [lambda m, f, c, b=b: b(layout if pinned else m, relabels,
                                        sched, f, c)
                 for b in branches],
                moving, factors, carry)
            return (nl, factors, carry), out

        (moving, factors, carry), outs = lax.scan(
            body, (None if pinned else layout, factors, carry),
            jnp.asarray(seq, dtype=jnp.int32))
        # outs[i] is mode seq[i], padded to imax rows; hand back per-mode
        # views in mode order, statically sliced to each I_d.
        by_mode = tuple(
            outs[seq.index(d)][: dims[d]] for d in range(n))
        return moving, by_mode, factors, carry

    return run


def _scan_args(state: EngineState, factors, carry) -> tuple:
    """The all-modes program's arguments for ``state``."""
    return (_layout(state), state.relabel, state.sched, tuple(factors),
            carry)


def _arg_shape(x) -> jax.ShapeDtypeStruct:
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


def _scan_fn(state: EngineState, fold: FoldFn | None, args: tuple):
    """The jitted all-modes program for ``state``'s static aux (cached);
    a new program records the shapes of ``args`` for :func:`op_scopes`."""
    key = ("all_modes", state.aux_key(), fold)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = jax.jit(_build_scan(state, fold),
                                       donate_argnums=_donate(state))
        _SCAN_ARGS[key] = jax.tree.map(_arg_shape, args)
    return fn


def all_modes(state: EngineState, factors: Sequence[jax.Array], *,
              fold: FoldFn | None = None, carry=None):
    """spMTTKRP along all N modes as ONE jitted ``lax.scan`` dispatch.

    Starts from the resident ``state.mode`` (any mode — the alpha tables
    rotate the layout back to it by the end; a pinned layout never leaves
    it) and returns outputs indexed by mode, i.e. ``outs[d]`` is the
    mode-``d`` MTTKRP of shape ``(dims[d], R)``.

    Without ``fold``: returns ``(outs, next_state)``.
    With ``fold`` (stable module-level callable, see :data:`FoldFn`):
    returns ``(outs, next_state, factors, carry)`` — the hook runs inside
    the scan right after each mode's output, which is how an ALS sweep
    stays a single traced program.
    """
    args = _scan_args(state, factors, carry)
    fn = _scan_fn(state, fold, args)
    _c = _chaos.active()
    if _c is not None:
        _c.on_dispatch(state.config.backend)
    DISPATCH_COUNTS["all_modes"] += 1
    with span("engine.dispatch", kind="all_modes", start_mode=state.mode):
        layout, outs, out_factors, out_carry = fn(*args)
    next_state = _advance(state, layout)
    if fold is None:
        return list(outs), next_state
    return list(outs), next_state, list(out_factors), out_carry


def scan_jaxpr(state: EngineState, factors: Sequence[jax.Array],
               fold: FoldFn | None = None, carry=None):
    """Jaxpr of the all-modes program (tests assert it is one scan)."""
    return jax.make_jaxpr(_build_scan(state, fold))(
        *_scan_args(state, factors, carry))


def scan_hlo(state: EngineState, factors: Sequence[jax.Array],
             fold: FoldFn | None = None, carry=None) -> str:
    """Optimized HLO of the all-modes program ``all_modes`` runs, as XLA
    compiled it for the state's backend (Pallas kernels compiled through
    Mosaic appear as ``tpu_custom_call``). Same jitted function, so a
    persistent compilation cache serves the compile."""
    args = _scan_args(state, factors, carry)
    return _compiled_text(_scan_fn(state, fold, args), args)


def _compiled_text(fn, args) -> str:
    return fn.lower(*args).compile().as_text()


class OpScope(NamedTuple):
    """Where one instruction of a sweep program sits: the mode's step and
    its named scope (``ec``, ``remap`` or ``fold``). ``crossed`` holds the
    other scopes a fusion took ops from; it is charged to its root's."""

    mode: int
    scope: str
    crossed: frozenset = frozenset()


_SCOPE = re.compile(r"(?:^|/)mode(\d+)/(ec|remap|fold)(?:/|$)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) ")
_INSTRUCTION = re.compile(
    r"^\s+(ROOT )?(%[^\s=]+) = (.+?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[^\s,]+)")
_OP_SCOPES: dict = {}   # _JIT_CACHE key -> that program's scopes


def op_scopes() -> dict[str, OpScope]:
    """The named scope of every scoped instruction of each all-modes
    program ``all_modes`` has built, from the compiled module's
    ``metadata={op_name=...}``.

    Keyed by the instruction's name and result shape
    (``%fusion.15 = s32[3309568,9]{0,1:T(4,128)}``), the way each op event
    of a device trace begins: names alone repeat across programs. Each
    program is compiled again from the argument shapes of its first call,
    with the same jitted function, so a compilation cache serves it.
    """
    out: dict[str, OpScope] = {}
    for key, args in list(_SCAN_ARGS.items()):
        fn = _JIT_CACHE.get(key)
        if fn is None:
            continue
        if key not in _OP_SCOPES:
            _OP_SCOPES[key] = _hlo_scopes(_compiled_text(fn, args))
        out.update(_OP_SCOPES[key])
    return out


class _Instr(NamedTuple):
    head: str              # "%name = shape"
    root: bool
    scope: tuple | None    # (mode, scope) of its op_name
    callee: str | None     # the fused computation of a fusion


def _hlo_scopes(text: str) -> dict[str, OpScope]:
    """:func:`op_scopes` of one compiled module's text. Instructions of
    fused computations run inside their fusion: a fusion takes the scope
    of its fused root, else its own."""
    comps: dict[str, list[_Instr]] = {}
    body = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and line.rstrip().endswith("{"):
            body = comps.setdefault(head.group(1), [])
        elif body is not None and (m := _INSTRUCTION.match(line)):
            name = _OP_NAME.search(line)
            sc = _SCOPE.search(name.group(1)) if name else None
            callee = _CALLS.search(line) if m.group(4) == "fusion" else None
            body.append(_Instr(f"{m.group(2)} = {m.group(3)}",
                               bool(m.group(1)),
                               (int(sc.group(1)), sc.group(2)) if sc
                               else None,
                               callee.group(1) if callee else None))

    def scopes_in(comp: str) -> set:
        found = set()
        for ins in comps.get(comp, ()):
            if ins.scope:
                found.add(ins.scope[1])
            if ins.callee:
                found |= scopes_in(ins.callee)
        return found

    fused = {ins.callee for body in comps.values() for ins in body
             if ins.callee}
    out: dict[str, OpScope] = {}
    for comp, body in comps.items():
        if comp in fused:
            continue
        for ins in body:
            scope, crossed = ins.scope, set()
            if ins.callee:
                scope = next((i.scope for i in comps.get(ins.callee, ())
                              if i.root and i.scope), scope)
                if scope:
                    crossed = scopes_in(ins.callee) - {scope[1]}
            if scope:
                out[ins.head] = OpScope(*scope, frozenset(crossed))
    return out


__all__ = ["init", "mttkrp", "all_modes", "scan_jaxpr", "scan_hlo",
           "op_scopes", "OpScope", "reset_counters",
           "TRACE_COUNTS", "DISPATCH_COUNTS", "ROW_COPIES", "REMAP_SLOTS",
           "FoldFn"]
