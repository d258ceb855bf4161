"""Device time per sweep of the sweep program's named scopes.

The program runs each mode's step of the ALS sweep program under the
scope ``mode<d>``, with the inner scopes ``ec`` (the EC kernel and its
operands), ``remap`` (the Alg. 3 move of the slot records) and ``fold``
(the ALS update), and ``repro.engine.api.op_scopes`` maps the compiled
program's instructions to them by name and result shape. An op event of
the trace is in a scope where its text begins with such an instruction;
every other op (the fit's and the factor init's small programs, and
whatever the map misses) is in none.

A program without ``op_scopes`` gives nothing to read.
"""
from __future__ import annotations

from collections import defaultdict

SCOPES = ("ec", "remap", "fold")
#: A fusion that takes ops from more than one scope is charged to its
#: root's; one over this many ms a sweep is named in the run's output.
CROSSED_MS = 1.0

_LAST: list = [None, None]   # (trace, its reading): the readers share one


def program_scopes():
    """``op_scopes()`` of the program in this process, or ``None``."""
    try:
        from repro.engine import api
    except ImportError:
        return None
    op_scopes = getattr(api, "op_scopes", None)
    return op_scopes() if op_scopes is not None else None


def per_sweep_ms(run) -> dict | None:
    """Device ms per sweep inside the window, by scope (``None`` for the
    ops in none), summed over chips; ``None`` without device ops or
    without a map of the program's scopes."""
    if run.trace is None or not run.trace.ops:
        return None
    if _LAST[0] is run.trace:
        return _LAST[1]
    scopes = program_scopes()
    if not scopes:
        return None
    by_name = defaultdict(list)
    for head, where in scopes.items():
        by_name[head.split(" = ", 1)[0]].append((head + " ", where))
    lo, hi = run.trace_window
    ms = dict.fromkeys((*SCOPES, None), 0.0)
    crossed: dict = defaultdict(float)
    for e in run.trace.ops:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b <= a:
            continue
        where = next((w for head, w in by_name.get(e.op, ())
                      if e.name.startswith(head)), None)
        t = (b - a) * 1e-6 / run.sweeps
        ms[where.scope if where else None] += t
        if where and where.crossed:
            crossed[(e.op, where)] += t
    print("device ms per sweep by scope: " + ", ".join(
        f"{k or 'none'} {v:.6g}" for k, v in ms.items()), flush=True)
    for (op, where), t in sorted(crossed.items(), key=lambda kv: -kv[1]):
        if t > CROSSED_MS:
            print(f"{op} ({t:.6g} ms a sweep) is charged to mode "
                  f"{where.mode} {where.scope} and also holds ops of "
                  f"{', '.join(sorted(where.crossed))}", flush=True)
    _LAST[:] = [run.trace, ms]
    return ms
