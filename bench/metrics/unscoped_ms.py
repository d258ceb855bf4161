"""Device milliseconds per sweep of the ops in none of the sweep program's
scopes (``bench.opscope``): the fit's and the factor init's small
programs, and whatever the map of scopes misses."""
from bench import opscope


def read(run):
    ms = opscope.per_sweep_ms(run)
    return ms[None] if ms else None
