"""Mosaic compiles of the main-path kernels for a TPU v5e, at real sizes.

Nothing runs: each test compiles a kernel for a described (not attached)
v5e chip from shapes alone, which catches what interpret mode cannot —
tile-misaligned slices, SMEM/VMEM overflows, programs that do not fit HBM.
The block counts are those of published tensors: vast (Table 3, 5 modes,
26.0M generated nonzeros, 18.9M after dedupe at seed 0) and nell1 (143.6M
nonzeros, 3 modes); a scalar-prefetched per-block descriptor alone would
need 0.6 MB and 4.5 MB of the 1 MiB SMEM there, so these compiles also
show that the kernels' SMEM use does not grow with the block count.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import mttkrp_kernel as K

P, R = 128, 32
VAST_BLOCKS = 148_067                         # mode 0 of vast, seed 0
NELL1_BLOCKS = -(-143_600_000 // P) + 5_664
VAST_IN_DIMS = (11_400, 2, 100, 89)           # mode-0 output: inputs 1..4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _gather_compact(sharding, nblocks, kappa, rows_pp, in_dims):
    nm1, s = len(in_dims), nblocks * P

    def fn(val, lrow, upos, bpart, uidx, nuniq, *factors):
        return K.mttkrp_fused_gather_compact(
            val, lrow, upos, bpart, uidx, nuniq, factors, kappa=kappa,
            rows_pp=rows_pp, nblocks=nblocks, block_p=P)

    shapes = [((s,), jnp.float32), ((s,), jnp.int32), ((s, nm1), jnp.int32),
              ((nblocks,), jnp.int32), ((nm1, s), jnp.int32),
              ((nm1, nblocks), jnp.int32)]
    shapes += [((d, R), jnp.float32) for d in in_dims]
    return _compile(fn, shapes, sharding)


@pytest.mark.parametrize("kappa,rows_pp", [(324, 511), (1, 2)],
                         ids=["mode0", "mode2"])
def test_gather_compact_compiles_at_vast(one_chip, kappa, rows_pp):
    """R=32 (lane-padded row DMAs) at vast's full block count, for its
    widest and narrowest output modes."""
    c = _gather_compact(one_chip, VAST_BLOCKS, kappa, rows_pp, VAST_IN_DIMS)
    assert "tpu_custom_call" in c.as_text()


def test_gather_compact_compiles_at_nell1_block_count(one_chip):
    """A 3-mode tensor with ~1.1M blocks per mode (nell1's count)."""
    c = _gather_compact(one_chip, NELL1_BLOCKS, 5_664, 512,
                        (2_100_000, 1_000_000))
    assert "tpu_custom_call" in c.as_text()


def test_fused_compact_compiles_at_vast(one_chip):
    """The pre-gathered EC baseline at vast's block count: its lane-dense
    (N-1, R, S) operand fits one chip's HBM."""
    nblocks, s = VAST_BLOCKS, VAST_BLOCKS * P

    def fn(gathered, val, lrow, bpart):
        return K.mttkrp_fused_compact(gathered, val, lrow, bpart, kappa=324,
                                      rows_pp=511, nblocks=nblocks,
                                      block_p=P)

    c = _compile(fn, [((4, R, s), jnp.float32), ((s,), jnp.float32),
                      ((s,), jnp.int32), ((nblocks,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_fused_remap_vmem_bound_raises():
    """A compiled fused remap whose resident next layout exceeds VMEM is
    refused when the program is built — no silent XLA-scatter fallback —
    while interpret mode (no VMEM) and a small S_max are accepted."""
    from repro import engine
    from repro.core import build_flycoo, init_factors
    from repro.engine import ExecutionConfig

    rng = np.random.default_rng(0)
    dims = (400, 300, 200)
    idx = np.unique(np.stack([rng.integers(0, d, 95_000) for d in dims], 1)
                    .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    t = build_flycoo(idx, val, dims, rows_pp=64)
    factors = init_factors(jax.random.PRNGKey(0), dims, 8)
    cfg = ExecutionConfig(backend="pallas_fused", interpret=False)
    # the fused remap runs on the rotating layout (a pinned one has none)
    state = engine.init(t, cfg, _rotating=True)
    assert state.smax > 87_000
    with pytest.raises(ValueError, match="VMEM"):
        engine.scan_jaxpr(state, factors)
    # interpret mode keeps nothing in VMEM: the same plan traces
    engine.scan_jaxpr(engine.init(t, ExecutionConfig(
        backend="pallas_fused", interpret=True), _rotating=True), factors)
    K.check_fused_remap_fits(8_192, 3, 8, 64, P)
    with pytest.raises(ValueError, match="fuse_remap=False"):
        K.check_fused_remap_fits(90_000, 5, 32, 512, P)
