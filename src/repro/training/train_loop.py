"""Loss, train_step factory, and the fault-tolerant training controller."""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .. import sharding
from ..models import forward
from ..models.common import ModelConfig
from . import optimizer as opt_lib
from .optimizer import OptimizerConfig

log = logging.getLogger("repro.train")


def softmax_xent(logits, targets, vocab: int):
    """fp32 cross-entropy; positions with target < 0 are masked; padded
    vocab rows (>= vocab) are excluded from the partition function.

    The picked-logit term is a one-hot contraction (not take_along_axis) so
    the vocab dim can stay model-sharded — no logits all-gather.
    """
    lf = logits.astype(jnp.float32)
    vp = lf.shape[-1]
    if vp > vocab:
        pad_mask = jnp.arange(vp) >= vocab
        lf = jnp.where(pad_mask, -1e30, lf)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    tgt = jnp.maximum(targets, 0)
    onehot = jax.nn.one_hot(tgt, vp, dtype=lf.dtype)
    onehot = sharding.shard(onehot, "dp", None, "tp")
    picked = jnp.einsum("bsv,bsv->bs", lf, onehot)
    nll = lse - picked
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_xent(x, head, targets, vocab: int, cfg, chunk: int = 512):
    """Cross-entropy with the head matmul fused into a sequence-chunk loop:
    full (B, S, V) logits are never materialized (the dominant 0-layer
    memory term at 256k vocab). Chunk bodies are rematerialized in backward.
    """
    from ..models import layers as _layers

    b, s, d = x.shape
    cs = min(chunk, s)
    n_chunks = (s + cs - 1) // cs
    hd = head.astype(x.dtype)
    # gather the seq-sharded hidden ONCE; otherwise every chunk's slice
    # (and its remat twin) re-all-gathers x — was the dominant collective
    x = sharding.shard(x, "dp", None, None)

    def body(lo):
        xc = jax.lax.dynamic_slice_in_dim(x, lo, cs, axis=1)
        tc = jax.lax.dynamic_slice_in_dim(targets, lo, cs, axis=1)
        logits = xc @ hd
        logits = sharding.shard(logits, "dp", None, "tp")
        lf = logits.astype(jnp.float32)
        vp = lf.shape[-1]
        if vp > vocab:
            lf = jnp.where(jnp.arange(vp) >= vocab, -1e30, lf)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(tc, 0), vp, dtype=logits.dtype)
        onehot = sharding.shard(onehot, "dp", None, "tp")
        picked = jnp.einsum("bsv,bsv->bs", logits, onehot,
                            preferred_element_type=jnp.float32)
        mask = (tc >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * mask), jnp.sum(mask)

    body = jax.checkpoint(body)
    if n_chunks == 1 or _layers.cost_mode():
        parts = [body(i * cs) for i in range(n_chunks)]
        nll = sum(p[0] for p in parts)
        cnt = sum(p[1] for p in parts)
    else:
        def scan_body(carry, i):
            nll, cnt = body(i * cs)
            return (carry[0] + nll, carry[1] + cnt), None

        (nll, cnt), _ = jax.lax.scan(scan_body, (0.0, 0.0),
                                     jnp.arange(n_chunks))
    return nll / jnp.maximum(cnt, 1.0)


def make_loss_fn(cfg: ModelConfig) -> Callable:
    from ..models.transformer import head_matrix

    def loss_fn(params, batch):
        kwargs = {}
        if cfg.kind == "vlm":
            kwargs["embeds"] = batch["embeds"]
        if cfg.kind == "audio":
            kwargs["enc_embeds"] = batch["enc_embeds"]
        targets = batch["targets"]
        if cfg.cpd_embedding:
            # CPD head: logits come factored (never a dense (V, D) table)
            logits = forward(params, cfg, tokens=batch["tokens"], **kwargs)
            if cfg.kind == "vlm":
                logits = logits[:, cfg.n_img_tokens:]
            return softmax_xent(logits, targets, cfg.vocab)
        x = forward(params, cfg, tokens=batch["tokens"], return_hidden=True,
                    **kwargs)
        if cfg.kind == "vlm":  # image prefix positions carry no loss
            x = x[:, cfg.n_img_tokens:]
        return chunked_xent(x, head_matrix(params, cfg), targets, cfg.vocab,
                            cfg)
    return loss_fn


def make_train_step(cfg: ModelConfig, ocfg: OptimizerConfig,
                    grad_accum: int = 1, param_shardings=None,
                    cast_params_once: bool = False) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    ``grad_accum`` > 1 splits the batch into microbatches on the leading
    axis (Python loop: exact HLO cost, overlappable by XLA).
    ``param_shardings`` (optional pytree) constrains gradients to the FSDP
    param layout so XLA emits reduce-scatter instead of full all-reduce.
    ``cast_params_once`` makes one bf16 working copy of the >=2D params at
    step entry (sharded like the masters, pinned with optimization_barrier)
    so FSDP all-gathers move bf16, not the f32 masters — halves fwd/bwd
    param collective bytes (§Perf iteration).
    """
    loss_fn = make_loss_fn(cfg)

    def train_step(state, batch):
        params = state["params"]
        if cast_params_once:
            def cast(p, s=None):
                if p.ndim < 2 or not jnp.issubdtype(p.dtype, jnp.floating):
                    return p
                c = p.astype(cfg.cdtype)
                if s is not None:
                    c = jax.lax.with_sharding_constraint(c, s)
                return jax.lax.optimization_barrier(c)

            if param_shardings is not None:
                fwd_params = jax.tree.map(cast, params, param_shardings)
            else:
                fwd_params = jax.tree.map(cast, params)
        else:
            fwd_params = params

        def one(mb):
            loss, g = jax.value_and_grad(loss_fn)(fwd_params, mb)
            return loss, g

        if grad_accum == 1:
            loss, grads = one(batch)
        else:
            from ..models import layers as _layers

            mbs = jax.tree.map(
                lambda x: x.reshape(grad_accum, -1, *x.shape[1:]), batch)
            if _layers.cost_mode():  # unrolled: exact HLO cost
                losses, grads = [], None
                for i in range(grad_accum):
                    li, gi = one(jax.tree.map(lambda x: x[i], mbs))
                    losses.append(li)
                    grads = gi if grads is None else jax.tree.map(
                        jnp.add, grads, gi)
                loss = sum(losses)
            else:                    # scanned: one microbatch live at a time
                def mb_body(carry, mb):
                    li, gi = one(mb)
                    acc_l, acc_g = carry
                    return (acc_l + li,
                            jax.tree.map(jnp.add, acc_g, gi)), None

                zero_g = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), fwd_params)
                (loss, grads), _ = jax.lax.scan(mb_body, (0.0, zero_g), mbs)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum

        if cast_params_once:  # grads back to master dtype for the update
            grads = jax.tree.map(lambda g, p: g.astype(jnp.float32)
                                 if g.dtype != p.dtype and p.ndim >= 2
                                 else g, grads, params)
        if param_shardings is not None:  # grads land sharded like params
            grads = jax.tree.map(jax.lax.with_sharding_constraint, grads,
                                 param_shardings)
        grads, gnorm = opt_lib.clip_by_global_norm(grads, ocfg.grad_clip)
        new_params, new_opt, lr = opt_lib.update(grads, state["opt"],
                                                 params, ocfg)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return train_step


def init_state(cfg: ModelConfig, ocfg: OptimizerConfig, key):
    from ..models import init_model

    params = init_model(cfg, key)
    return {"params": params, "opt": opt_lib.init(params, ocfg),
            "step": jnp.zeros((), jnp.int32)}


# --------------------------------------------------------------------------
# Fault-tolerant controller (checkpoint/auto-resume/straggler watchdog)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ControllerConfig:
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep: int = 3
    async_save: bool = True
    straggler_factor: float = 3.0   # step slower than factor*median -> flag
    max_failures: int = 3


class TrainController:
    """Runs the training loop with checkpoint/restart fault tolerance.

    - atomically checkpoints (params, opt, step, data cursor) every N steps;
    - auto-resumes from the newest checkpoint on (re)start — preemption
      recovery is "rerun the binary";
    - reshard-on-load: restore works onto a *different* mesh/device count
      than the checkpoint was written from (elastic shrink/grow);
    - straggler watchdog: flags steps slower than ``factor x`` running
      median (on multi-host this feeds the scheduler's quarantine list).
    """

    def __init__(self, cfg: ModelConfig, ocfg: OptimizerConfig,
                 ctrl: ControllerConfig, data_iter, train_step=None,
                 state=None, key=None):
        from .checkpoint import CheckpointManager

        self.cfg, self.ocfg, self.ctrl = cfg, ocfg, ctrl
        self.data = data_iter
        self.step_fn = train_step or jax.jit(make_train_step(cfg, ocfg))
        self.mgr = CheckpointManager(ctrl.ckpt_dir, keep=ctrl.keep,
                                     async_save=ctrl.async_save)
        self.state = state
        if self.state is None:
            self.state = init_state(cfg, ocfg, key or jax.random.PRNGKey(0))
            restored = self.mgr.restore_latest(like=self.state)
            if restored is not None:
                self.state, data_state = restored
                self.data.set_state(data_state)
                log.info("auto-resumed at step %s", int(self.state["step"]))
        self.durations: list[float] = []
        self.straggler_steps: list[int] = []

    def run(self, num_steps: int, fail_at: Optional[int] = None):
        """Train; ``fail_at`` injects a simulated preemption (tests)."""
        metrics = None
        while int(self.state["step"]) < num_steps:
            step = int(self.state["step"])
            if fail_at is not None and step == fail_at:
                raise InterruptedError(f"simulated preemption at {step}")
            t0 = time.monotonic()
            batch = self.data.next()
            self.state, metrics = self.step_fn(self.state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.monotonic() - t0
            self._watch(step, dt)
            if (step + 1) % self.ctrl.ckpt_every == 0:
                self.mgr.save(self.state, self.data.get_state())
        self.mgr.save(self.state, self.data.get_state())
        self.mgr.wait()
        return self.state, metrics

    def _watch(self, step: int, dt: float):
        self.durations.append(dt)
        hist = sorted(self.durations[-50:])
        med = hist[len(hist) // 2]
        if len(self.durations) > 5 and dt > self.ctrl.straggler_factor * med:
            self.straggler_steps.append(step)
            log.warning("straggler step %d: %.3fs (median %.3fs)",
                        step, dt, med)
