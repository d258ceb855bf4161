"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Stages live on a dedicated mesh axis; microbatches stream through the
classic (n_micro + n_stages - 1)-tick schedule with activations handed to
the next stage by ``ppermute`` each tick (bubbles included — this is honest
GPipe, not an idealized overlap model).

Not used by the production dry-run meshes (DESIGN.md §6 explains why DP x
TP x EP + SP is the right regime for the assigned archs at 512 chips); it
exists so the framework has a tested PP primitive for deeper-than-HBM
models, and is exercised by tests/test_distributed.py on a 4-stage mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pipeline_apply(stage_fn, stage_params, x, *, mesh, axis: str = "pp",
                   n_micro: int):
    """Run ``y = stage_{S-1}(...stage_0(x))`` on a pipeline mesh axis.

    Args:
      stage_fn: (params_one_stage, h) -> h, the per-stage computation.
      stage_params: pytree stacked on a leading n_stages axis (sharded on
        ``axis``).
      x: (batch, ...) global input; batch must divide n_micro.
      mesh: mesh containing ``axis`` of size n_stages.
      n_micro: number of microbatches streamed through the pipe.

    Returns y with x's shape.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro
    ticks = n_micro + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def local(params_st, x_loc):
        # params_st: this stage's params (leading axis 1); x_loc: the full
        # batch (replicated along the pp axis — inputs enter at stage 0).
        params_one = jax.tree.map(lambda p: p[0], params_st)
        stage_id = jax.lax.axis_index(axis)
        mbs = x_loc.reshape(n_micro, mb, *x_loc.shape[1:])
        carry = jnp.zeros_like(mbs[0])
        outs = jnp.zeros_like(mbs)
        for t in range(ticks):  # static schedule: exact HLO
            # stage 0 injects microbatch t (if any); others use the carry
            feed_idx = min(t, n_micro - 1)
            inject = mbs[feed_idx]
            h_in = jnp.where(stage_id == 0, inject, carry)
            h_out = stage_fn(params_one, h_in)
            # last stage retires microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)  # static
            if 0 <= out_idx < n_micro:
                keep = jnp.where(stage_id == n_stages - 1, h_out,
                                 jnp.zeros_like(h_out))
                outs = outs.at[out_idx].add(keep)
            # hand activations to the next stage
            carry = jax.lax.ppermute(h_out, axis, fwd_perm)
        # non-last stages hold zeros; psum materializes the pipe's output
        outs = jax.lax.psum(outs, axis)
        return outs.reshape(b, *x_loc.shape[1:])

    return _smap(
        local, mesh,
        in_specs=(P(axis), P()),       # stage params sharded; x replicated
        out_specs=P(),
    )(stage_params, x)
