"""Int8 gradient all-reduce with error feedback, riding the NoC butterfly.

A distributed-optimization trick for scale (beyond-paper, but in the spirit
of CompAir's compute-during-communication): gradients are quantized to int8
per tensor before each butterfly hop, summed in int32, and requantized; the
quantization residual is fed back into the next step's gradient (error
feedback), which keeps SGD/Adam convergence (Karimireddy et al., 2019).

Wire bytes per hop drop 4x vs fp32 / 2x vs bf16.  Used via shard_map over
the data axis; see tests/test_compress.py for the convergence check.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax



def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def butterfly_allreduce_int8(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce-mean of ``x`` with int8 payloads on every hop.

    Scales are agreed per hop with a pmax (scalar traffic); values travel
    as int8 and are accumulated in int32 then requantized — i.e. the
    Curry-ALU '+=' applied to compressed flits in transit."""
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0, "butterfly needs a power-of-two axis"
    xf = x.astype(jnp.float32)
    k = 1
    while k < n:
        perm = [(i, i ^ k) for i in range(n)]
        scale = jnp.maximum(lax.pmax(jnp.max(jnp.abs(xf)), axis_name), 1e-12) / 127.0
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        other = lax.ppermute(q, axis_name, perm)
        xf = (q.astype(jnp.int32) + other.astype(jnp.int32)).astype(jnp.float32) * scale
        k *= 2
    return (xf / n).astype(x.dtype)


def compressed_grad_sync(grads, axis_name: str, error=None):
    """Error-feedback int8 all-reduce over a gradient pytree.

    Returns (synced_grads fp32, new_error).  ``error`` is the residual
    pytree from the previous step (or None at step 0)."""
    if error is None:
        error = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        synced = butterfly_allreduce_int8(corrected, axis_name)
        # local residual: what quantization lost of *this* device's signal
        q, s = quantize_int8(corrected)
        new_e = corrected - dequantize(q, s)
        return synced.astype(jnp.float32), new_e

    out = jax.tree.map(one, grads, error)
    synced = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return synced, new_err
