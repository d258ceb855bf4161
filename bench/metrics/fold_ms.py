"""Device milliseconds per sweep of the ops in the sweep program's ``fold``
scopes (``bench.opscope``): the ALS update of every mode."""
from bench import opscope


def read(run):
    ms = opscope.per_sweep_ms(run)
    return ms["fold"] if ms and ms["fold"] > 0 else None
