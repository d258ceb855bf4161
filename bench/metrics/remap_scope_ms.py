"""Device milliseconds per sweep of the ops in the sweep program's
``remap`` scopes (``bench.opscope``): the Alg. 3 remap of every mode, the
slot records' pack and unpack included."""
from bench import opscope


def read(run):
    ms = opscope.per_sweep_ms(run)
    return ms["remap"] if ms and ms["remap"] > 0 else None
