"""Share of the traced window in which the chip runs no operation, in %."""
from bench import tracereduce


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - tracereduce.busy_ns(run.trace, lo, hi) / (hi - lo))
