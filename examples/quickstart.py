"""Quickstart: FLYCOO spMTTKRP + CPD-ALS on a synthetic FROSTT-like tensor.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro import engine
from repro.core import cp_als, datasets, init_factors, mttkrp_ref
from repro.engine import ExecutionConfig


def main():
    # 1. Load a scaled synthetic of the paper's Nell-1 (Table 3 family).
    tensor = datasets.load("nell1", scale=3e-4, max_nnz=40_000)
    print(f"tensor dims={tensor.dims} nnz={tensor.nnz} "
          f"bits/elem={tensor.memory_bits_per_element():.1f}")
    for d, bal in enumerate(tensor.load_balance()):
        # imbalance is vs the Graham bound OPT >= max(mean, max degree)
        print(f"  mode {d}: max/mean = {bal['max']:.0f}/{bal['mean']:.1f} "
              f"nnz per partition; vs OPT lower bound "
              f"{bal['imbalance']:.3f} "
              f"(4/3 bound holds: {bal['imbalance'] <= 4 / 3 + 0.01})")

    # 2. spMTTKRP along all modes with dynamic remapping (paper Alg. 5):
    #    one engine state, one jitted lax.scan over the mode rotation.
    rank = 32
    factors = init_factors(jax.random.PRNGKey(0), tensor.dims, rank)
    # backend="xla" by default; ExecutionConfig(backend="pallas_fused")
    # runs the Mosaic kernels on a TPU (interpret mode elsewhere)
    config = ExecutionConfig()
    state = engine.init(tensor, config)
    outs, state = engine.all_modes(state, tuple(factors))
    ref = mttkrp_ref(tensor.indices, tensor.values, factors, 0,
                     tensor.dims[0])
    err = float(np.max(np.abs(np.asarray(outs[0]) - np.asarray(ref))))
    print(f"mode-0 max |FLYCOO - COO oracle| = {err:.2e} "
          f"({engine.DISPATCH_COUNTS['all_modes']} dispatch for "
          f"{tensor.nmodes} modes)")

    # 3. Full CPD via ALS (each sweep is a single traced program).
    res = cp_als(tensor, rank=8, iters=5, config=config)
    print("CPD-ALS fits:", [round(f, 4) for f in res.fits])


if __name__ == "__main__":
    main()
