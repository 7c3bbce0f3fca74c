"""Fused SwiGLU gating — Pallas TPU kernel.

silu(gate) * up in a single VMEM pass.  In CompAir the SiLU sits in the
Curry ALU on the path between the Gate/Up FC banks (§2.3 category ii —
"special function"); here it is fused so the gate tensor never makes a
second HBM trip.  ``curry_rounds`` switches the sigmoid's exp to the
paper-faithful Taylor iteration (Fig. 13) for fidelity experiments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _taylor_exp(x, rounds: int):
    # Horner form of the ArgReg-iterated Taylor expansion in Fig. 13, with
    # range reduction exp(x) = exp(x/16)^16 (squaring is also a Curry-ALU
    # iterated op), keeping the series argument small.
    xr = x * (1.0 / 16.0)
    p = jnp.ones_like(xr)
    for i in range(rounds, 0, -1):
        p = p * (xr / i) + 1.0
    for _ in range(4):
        p = p * p
    return p


def _kernel(g_ref, u_ref, o_ref, *, curry_rounds: int):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    if curry_rounds:
        # sigmoid(g) = 1 / (1 + exp(-g)); exp via bounded-range Taylor
        e = _taylor_exp(-jnp.abs(g), curry_rounds)
        sig = jnp.where(g >= 0, 1.0 / (1.0 + e), e / (1.0 + e))
    else:
        sig = jax.nn.sigmoid(g)
    o_ref[...] = (g * sig * u).astype(o_ref.dtype)


# VMEM one grid step may use: the gate/up/out blocks, double-buffered, plus
# the kernel's f32 temporaries (~4 live [rows, D] f32 values).  The scoped
# VMEM limit is 16 MiB; a full-row block of d_ff=5632 at 512 rows needs 38 MB.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024


def _auto_block_rows(d: int, itemsize: int) -> int:
    per_row = d * (2 * 3 * itemsize + 4 * 4)
    return max(16, (DEFAULT_VMEM_BUDGET // per_row) // 16 * 16)


def silu_mul(gate, up, *, block_rows=None, curry_rounds: int = 0,
             interpret: bool = False):
    """silu(gate) * up, elementwise; any shape with last dim D.

    ``block_rows`` (None: the largest multiple of 16 whose step fits
    ``DEFAULT_VMEM_BUDGET``) rows of full width D per grid step."""
    shape = gate.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    g2 = gate.reshape(rows, d)
    u2 = up.reshape(rows, d)
    if block_rows is None:
        block_rows = _auto_block_rows(d, jnp.dtype(up.dtype).itemsize)
    block_rows = min(block_rows, rows)
    nb = -(-rows // block_rows)
    pad = nb * block_rows - rows
    if pad:
        g2 = jnp.pad(g2, ((0, pad), (0, 0)))
        u2 = jnp.pad(u2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, curry_rounds=curry_rounds),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d), up.dtype),
        name="silu_mul",
        interpret=interpret,
    )(g2, u2)
    return out[:rows].reshape(shape)
