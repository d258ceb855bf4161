"""Share of the EC kernels' roofline, in %: the least time one sweep's
MTTKRPs need on this chip (``bench.roofline``, from shapes) over their
device time per sweep."""
from bench import roofline, tracereduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    s = tracereduce.op_seconds(run.trace, lo, hi, tracereduce.is_ec_kernel)
    if s <= 0:
        return None
    flops, nbytes = roofline.sweep_counts(run.nnz, run.rank, run.dims)
    least = roofline.roofline_seconds(flops, nbytes, run.device_kind)
    return 100.0 * least / (s / run.sweeps)
