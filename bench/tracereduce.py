"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

What is read:

* device operations: the events of the ``XLA Ops`` line of every TPU plane
  (``/device:TPU:<n>``), one per executed HLO instruction, named by the
  instruction's HLO text (``%mttkrp_fused_gather_compact.5 = f32[...]
  custom-call(...)``). Control flow (``while``, ``conditional``, ``call``)
  is left out: its events span the instructions it runs;
* host spans: events of the host plane whose names start with one of
  ``HOST_SPAN_PREFIXES`` -- the benchmark's own ``bench.*`` annotations and
  the program's ``repro.obs`` spans, which it mirrors into the profiler as
  ``TraceAnnotation``s while its tracing is on.

The window is the ``bench.window`` host span. Busy time is the union of
the device operations' intervals inside it, per chip, averaged over chips.
An idle gap is a stretch of the window in which a chip runs nothing; it is
named by the innermost host span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIXES = ("bench.", "engine.", "cpd.", "plan.")
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
_CONTROL_FLOW = re.compile(r"[\]})] (while|conditional|call)\(")
# The Alg. 3 remap: a scatter fusion that writes the (S, 2N+1) int32 slot
# records into a base of the same shape (``engine.backends.scatter_slots``).
_SLOT_SCATTER = re.compile(
    r"^%[\w.\-]+ = s32\[(\d+),(\d+)\]\S* fusion\(s32\[\1,\2\]"
    r".*kind=kCustom")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    where: str = ""   # device plane name, or "" for host spans

    @property
    def op(self) -> str:
        """The HLO instruction's name (``%fusion.24``), or the span name."""
        return self.name.split(" = ", 1)[0]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Trace:
    ops: tuple[Event, ...]       # device operations
    spans: tuple[Event, ...]     # host spans

    @property
    def devices(self) -> tuple[str, ...]:
        return tuple(sorted({e.where for e in self.ops}))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(e.name, e.start_ns, e.duration_ns,
                                  plane.name)
                            for e in line.events
                            if not _CONTROL_FLOW.search(e.name)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(HOST_SPAN_PREFIXES)]
    return Trace(ops=tuple(ops), spans=tuple(spans))


def window(trace: Trace) -> tuple[float, float]:
    wins = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    return wins[0].start_ns, wins[0].end_ns


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of ``events`` cut to ``[lo, hi]``, empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of device-op time inside ``[lo, hi]``, averaged over chips."""
    devs = trace.devices
    if not devs:
        return 0.0
    total = sum(sum(b - a for a, b in union(
        clip([e for e in trace.ops if e.where == dev], lo, hi)))
        for dev in devs)
    return total / len(devs)


def gaps(trace: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle stretches of the first chip inside ``[lo, hi]``."""
    devs = trace.devices
    busy = union(clip([e for e in trace.ops if e.where == devs[0]], lo, hi)) \
        if devs else []
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(trace: Trace, a: float, b: float) -> str:
    """The innermost host span that covers at least half of ``[a, b]``:
    the shortest of those; else the span that overlaps it most;
    ``"(no span)"`` where none does."""
    best, most, inner = "(no span)", 0.0, None
    for s in trace.spans:
        if s.name == WINDOW_SPAN:
            continue
        ov = min(b, s.end_ns) - max(a, s.start_ns)
        if ov <= 0:
            continue
        if 2 * ov >= b - a and (inner is None or s.dur_ns < inner[1]):
            inner = (s.name, s.dur_ns)
        if ov > most:
            best, most = s.name, ov
    return inner[0] if inner else best


def is_ec_kernel(e: Event) -> bool:
    """A launch of one of the Mosaic MTTKRP kernels (``mttkrp_*``)."""
    return e.op.startswith("%mttkrp")


def is_remap(e: Event, nmodes: int) -> bool:
    """An Alg. 3 remap scatter of ``(S, 2 * nmodes + 1)`` slot records."""
    m = _SLOT_SCATTER.match(e.name)
    return m is not None and int(m.group(2)) == 2 * nmodes + 1


def op_seconds(trace: Trace, lo: float, hi: float, pred) -> float:
    """Device seconds of the operations ``e`` with ``pred(e)`` inside
    ``[lo, hi]``, summed over chips."""
    return sum(b - a for a, b in clip(
        [e for e in trace.ops if pred(e)], lo, hi)) * 1e-9


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The ``top`` device operations by time and the ``top`` longest idle
    gaps of the window, each ``[name, seconds]``."""
    per_op: dict[str, float] = defaultdict(float)
    for a, b, name in ((max(e.start_ns, lo), min(e.end_ns, hi), e.op)
                       for e in trace.ops):
        if b > a:
            per_op[name] += (b - a) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(trace, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[name_gap(trace, a, b), (b - a) * 1e-9]
                          for a, b in idle]}
