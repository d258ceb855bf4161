"""Peaks of the chips the benchmark runs on, and the operation and byte
counts of an MTTKRP computed from shapes alone.

The counts say what any implementation of the mode-``d`` MTTKRP has to do,
whatever the kernel does in fact (lane padding, one-hot matmuls and
re-fetched rows are not useful work):

* operations: per nonzero and rank column, ``N - 2`` multiplies for the
  Khatri-Rao row, one by the value and one add into the output row, so
  ``nnz * R * N``;
* compulsory bytes: the COO stream once (a float32 value and ``N`` int32
  indices per nonzero), every input factor read once and the output written
  once, all float32 at the rank ``R``.
"""
from __future__ import annotations

#: device_kind -> peaks. Source: Google Cloud documentation, "TPU v5e"
#: (system architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def mode_flops(nnz: int, rank: int, nmodes: int) -> int:
    return nnz * rank * nmodes


def mode_bytes(nnz: int, rank: int, dims, mode: int) -> int:
    coo = nnz * (4 + 4 * len(dims))
    factors_in = sum(dims[w] for w in range(len(dims)) if w != mode)
    return coo + (factors_in + dims[mode]) * rank * 4


def sweep_counts(nnz: int, rank: int, dims) -> tuple[int, int]:
    """(operations, compulsory bytes) of one ALS sweep's MTTKRPs, all
    modes."""
    n = len(dims)
    return (n * mode_flops(nnz, rank, n),
            sum(mode_bytes(nnz, rank, dims, d) for d in range(n)))


def roofline_seconds(flops: int, nbytes: int, device_kind: str) -> float:
    """Least time the chip needs for ``flops`` and ``nbytes``: the larger of
    the compute and the memory bound."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
