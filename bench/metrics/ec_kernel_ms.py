"""Device milliseconds per sweep of the EC kernels (the Mosaic MTTKRP
kernels, every mode)."""
from bench import tracereduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    s = tracereduce.op_seconds(run.trace, lo, hi, tracereduce.is_ec_kernel)
    return s * 1e3 / run.sweeps if s > 0 else None
