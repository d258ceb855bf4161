"""Device nanoseconds of the EC kernels per factor-row copy they issue:
their device time per sweep over the sum, over modes, of the program's
``engine_row_copies`` gauge (the bound of each kernel's row-DMA loop, so
the copies of one sweep)."""
from bench import tracereduce


def row_copies(nmodes: int) -> int:
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return 0
    gauge = REGISTRY.metrics().get("engine_row_copies")
    return sum(int(gauge.get(d, 0)) for d in range(nmodes)) if gauge else 0


def read(run):
    if run.trace is None:
        return None
    copies = row_copies(len(run.dims))
    lo, hi = run.trace_window
    s = tracereduce.op_seconds(run.trace, lo, hi, tracereduce.is_ec_kernel)
    if not copies or s <= 0:
        return None
    ns = s * 1e9 / run.sweeps / copies
    print(f"ec_ns_per_row_copy: {ns:.6g} ns over {copies} row copies a "
          "sweep", flush=True)
    return ns
