"""Training step builder: loss, microbatch accumulation, state plumbing.

Cross-entropy uses a max-subtracted logsumexp in fp32; with a vocab-sharded
LM head under GSPMD the reductions lower to collectives automatically (the
'xla' mode).  ``loss_mode='noc'`` is the beyond-paper variant that computes
the logsumexp with explicit NoC butterfly trees under shard_map (wired in
launch/dryrun.py perf experiments).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.train import optim


class TrainState(NamedTuple):
    params: dict
    opt: optim.OptState


def init_state(cfg: ModelConfig, rng, dtype=jnp.bfloat16) -> TrainState:
    params = M.init_params(cfg, rng, dtype)
    return TrainState(params, optim.adamw_init(params))


def init_state_shaped(cfg: ModelConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_state(cfg, jax.random.key(0), dtype))


def cross_entropy(logits, labels, *, mask=None):
    """logits [B,S,V] (any dtype), labels [B,S] int32 -> scalar mean nll.

    The gold logit is selected with a masked reduction rather than
    take_along_axis: a vocab-sharded gather would make GSPMD all-gather
    the full [B,S,V] fp32 logits (measured: ~26 GiB/device at train_4k),
    while iota-compare + reduce stays sharded end to end."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    v = lf.shape[-1]
    eq = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1) \
        == labels[..., None]
    gold = jnp.sum(jnp.where(eq, lf, 0.0), axis=-1)
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def cross_entropy_noc(logits, labels, mesh, dp_axes, tp_axis, *, mask=None):
    """Cross-entropy over vocab-sharded logits with the NoC butterfly
    logsumexp (core.noc.distributed_logsumexp) — the paper's distributed
    softmax applied to the LM loss.  Equivalent to ``cross_entropy`` (see
    tests/test_noc_xent.py); the collective payload is the [B,S] max/sum
    statistics instead of whatever GSPMD materializes.

    logits [B,S,V] sharded P(dp, None, tp); labels [B,S] sharded P(dp)."""
    from jax.sharding import PartitionSpec as P

    from repro.core import noc
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in dp_axes if a in axis_sizes) or None

    def body(lg, lb, mk):
        lf = lg.astype(jnp.float32)
        lse = noc.distributed_logsumexp(lf, tp_axis)         # [B,S]
        v_loc = lf.shape[-1]
        v0 = jax.lax.axis_index(tp_axis) * v_loc
        eq = (jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
              + v0) == lb[..., None]
        gold = jax.lax.psum(jnp.sum(jnp.where(eq, lf, 0.0), axis=-1), tp_axis)
        nll = lse - gold
        if mk is not None:
            num = jax.lax.psum(jnp.sum(nll * mk), dp) if dp else jnp.sum(nll * mk)
            den = jax.lax.psum(jnp.sum(mk), dp) if dp else jnp.sum(mk)
        else:
            num = jax.lax.psum(jnp.sum(nll), dp) if dp else jnp.sum(nll)
            den = float(labels.shape[0] * labels.shape[1])
        return num / jnp.maximum(den, 1.0)

    in_specs = (P(dp, None, tp_axis), P(dp, None),
                P(dp, None) if mask is not None else P())
    args = (logits, labels, mask if mask is not None else jnp.zeros((), jnp.float32))
    if mask is None:
        body2 = lambda lg, lb, _mk: body(lg, lb, None)
    else:
        body2 = body
    return jax.shard_map(body2, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(*args)


def make_loss_fn(cfg: ModelConfig, *, lb_coef: float = 0.01,
                 z_coef: float = 1e-3, attn_window: Optional[int] = None,
                 remat: bool = True):
    def loss_fn(params, batch):
        kwargs = {}
        if "embeds" in batch:
            kwargs["embeds"] = batch["embeds"]
        else:
            kwargs["tokens"] = batch["tokens"]
        logits, aux = M.forward(cfg, params, train=True, remat=remat,
                                attn_window=attn_window, **kwargs)
        nll = cross_entropy(logits, batch["labels"],
                            mask=batch.get("loss_mask"))
        loss = nll
        if cfg.family == "moe":
            loss = loss + lb_coef * aux[0] + z_coef * aux[1]
        return loss, {"nll": nll, "lb": aux[0], "z": aux[1]}

    return loss_fn


def make_train_step(cfg: ModelConfig, *, base_lr: float = 3e-4,
                    warmup: int = 100, total_steps: int = 10_000,
                    weight_decay: float = 0.1, clip_norm: float = 1.0,
                    microbatch: Optional[int] = None,
                    attn_window: Optional[int] = None,
                    remat: bool = True):
    """Returns train_step(state, batch) -> (state, metrics).

    ``microbatch``: split the (local) batch into this many sequential
    chunks with gradient accumulation (a lax.scan) — the activation-memory
    lever for the biggest shapes."""
    loss_fn = make_loss_fn(cfg, attn_window=attn_window, remat=remat)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if not microbatch or microbatch <= 1:
            return grad_fn(params, batch)
        b = batch["labels"].shape[0]
        assert b % microbatch == 0, (b, microbatch)
        mb = {k: v.reshape((microbatch, b // microbatch) + v.shape[1:])
              for k, v in batch.items()}

        def acc_step(carry, mbatch):
            (lsum, gsum, metr) = carry
            (l, met), g = grad_fn(params, mbatch)
            gsum = jax.tree.map(lambda a, bb: a + bb.astype(jnp.float32), gsum, g)
            metr = jax.tree.map(lambda a, bb: a + bb, metr, met)
            return (lsum + l, gsum, metr), None

        zeros_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                               params)
        zeros_m = {"nll": 0.0, "lb": 0.0, "z": 0.0}
        (lsum, gsum, metr), _ = jax.lax.scan(acc_step,
                                             (0.0, zeros_g, zeros_m), mb)
        inv = 1.0 / microbatch
        return (lsum * inv, jax.tree.map(lambda x: x * inv, metr)), \
            jax.tree.map(lambda g: g * inv, gsum)

    def train_step(state: TrainState, batch):
        (loss, metrics), grads = compute_grads(state.params, batch)
        # schedule at step+1: evaluating at raw step 0 yields lr=0 and a
        # silent no-op first update (caught by the per-arch smoke tests)
        lr = optim.cosine_schedule(state.opt.step + 1, base_lr=base_lr,
                                   warmup=warmup, total=total_steps)
        params, opt, gnorm = optim.adamw_update(
            state.params, grads, state.opt, lr=lr,
            weight_decay=weight_decay, clip_norm=clip_norm)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return TrainState(params, opt), metrics

    return train_step
