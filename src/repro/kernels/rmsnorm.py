"""Fused RMSNorm — Pallas TPU kernel.

The paper implements RMSNorm's rsqrt via Newton iteration in the Curry ALU
while the activation vector is in flight (§4.3.2).  On TPU the analogue is
a single fused VMEM-resident pass: one HBM read, one write — no separate
square/reduce/scale round-trips.  ``curry_rounds`` optionally uses the
paper-faithful Newton-iteration rsqrt instead of the native op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _newton_rsqrt(x, rounds: int):
    # Newton: y <- y * (1.5 - 0.5 * x * y^2); seed from the native estimate
    # at low precision to mimic the Curry ALU's iterative refinement.
    y = jax.lax.rsqrt(x.astype(jnp.bfloat16).astype(jnp.float32))
    for _ in range(rounds):
        y = y * (1.5 - 0.5 * x * y * y)
    return y


def _kernel(x_ref, w_ref, o_ref, *, eps: float, curry_rounds: int):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    if curry_rounds:
        inv = _newton_rsqrt(var + eps, curry_rounds)
    else:
        inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, w, *, eps: float = 1e-5, block_rows: int = 256,
            curry_rounds: int = 0, interpret: bool = False):
    """x [..., D]; w [D] -> normalized x."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    nb = -(-rows // block_rows)
    pad = nb * block_rows - rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps, curry_rounds=curry_rounds),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d), x.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(shape)
