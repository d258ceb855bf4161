"""RG-LRU gated linear recurrence Pallas TPU kernel (Griffin, arXiv:2402.19427).

Computes h_t = a_t * h_{t-1} + x_t over the time axis, with the recurrent
state resident in VMEM scratch across sequence chunks. The grid walks
(time-chunks,); within a chunk the loop is unrolled (static ``chunk``) so
every step is a fully vectorized (B, D) VPU op — the TPU analogue of the
recurrence being register-resident.

Used by the recurrentgemma-9b blocks and by long-context serving, where the
O(1)-state scan is what makes ``long_500k`` feasible (DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, x_ref, o_ref, h_ref, *, chunk: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]                      # (B, D) carry
    a = a_ref[...]                      # (B, C, D)
    x = x_ref[...]
    for c in range(chunk):              # static unroll: VPU steps
        h = a[:, c, :] * h + x[:, c, :]
        o_ref[:, c, :] = h
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def lru_scan(a: jax.Array, x: jax.Array, *, chunk: int = 32,
             interpret: bool = False) -> jax.Array:
    """h_t = a_t * h_{t-1} + x_t ;  a, x: (B, T, D) -> h: (B, T, D)."""
    b, t, d = x.shape
    assert a.shape == x.shape
    assert t % chunk == 0, (t, chunk)
    return pl.pallas_call(
        functools.partial(_lru_kernel, chunk=chunk),
        grid=(t // chunk,),
        in_specs=[
            pl.BlockSpec((b, chunk, d), lambda i: (0, i, 0)),
            pl.BlockSpec((b, chunk, d), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((b, chunk, d), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, d), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), x.astype(jnp.float32))
