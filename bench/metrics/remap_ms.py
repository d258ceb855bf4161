"""Device milliseconds per sweep of the Alg. 3 remap: the scatters that
move every slot record into the next mode's layout."""
from bench import tracereduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    s = tracereduce.op_seconds(
        run.trace, lo, hi, lambda e: tracereduce.is_remap(e, len(run.dims)))
    return s * 1e3 / run.sweeps if s > 0 else None
