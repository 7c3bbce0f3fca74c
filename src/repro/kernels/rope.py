"""RoPE (interleaved) — Pallas TPU kernel.

Paper §4.3.1/Fig. 12: RoPE's neighbour-pair swap + negate is a granularity
mismatch for row-granular DRAM-PIM, so CompAir performs the rearrangement
inside NoC routers (34 cycles/bank).  The TPU analogue: do the pair
rotation entirely in registers inside one kernel — the (de)interleave is a
VREG shuffle, never a second HBM round-trip (the baseline it replaces is a
gather/scatter permutation at the XLA level).

cos/sin are computed in-kernel from the position block (no table in HBM).
The kernel works on ``[rows, D]`` tiles (one row per (token, head)), the
layout Mosaic tiles natively; the neighbour swap ``x[i] <-> x[i ^ 1]`` is a
product with a ``[D, D]`` pair-permutation matrix (exact: one 1.0 per
column), since TPU kernels have no in-register lane reshape to pairs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _kernel(x_ref, pos_ref, o_ref, *, theta: float):
    x = x_ref[...]                                   # [R, D]
    rows, d = x.shape
    half = d // 2
    lane = lax.broadcasted_iota(jnp.int32, (rows, d), 1)
    # angle(r, i) = pos[r] / theta^((i // 2) / half); TPU iota is int-only
    j = (lane // 2).astype(jnp.float32)
    inv = jnp.exp(-math.log(theta) * j / half)
    ang = pos_ref[...].astype(jnp.float32) * inv     # [R, 1] * [R, D]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a = lax.broadcasted_iota(jnp.int32, (d, d), 0)
    b = lax.broadcasted_iota(jnp.int32, (d, d), 1)
    swap = (a == b + 1 - 2 * (b % 2)).astype(x.dtype)
    partner = lax.dot_general(x, swap, (((1,), (0,)), ((), ())),
                              precision=(lax.Precision.HIGHEST
                                         if x.dtype == jnp.float32 else None),
                              preferred_element_type=jnp.float32)
    # even lane: x_e cos - x_o sin; odd lane: x_o cos + x_e sin
    sign = jnp.where(lane % 2 == 0, -1.0, 1.0)
    out = x.astype(jnp.float32) * cos + partner * sin * sign
    o_ref[...] = out.astype(o_ref.dtype)


def apply_rope(x, positions, *, theta: float = 10_000.0, block_s: int = 16,
               interpret: bool = False):
    """x [B, S, H, D]; positions [B, S] or [S] -> rotated x.

    A grid step covers ``block_s * H`` rows, i.e. ``block_s`` positions of
    one batch row's worth of heads."""
    b, s, h, d = x.shape
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (b, s))
    rows = b * s * h
    x2 = x.reshape(rows, d)
    pos2 = jnp.broadcast_to(positions.astype(jnp.int32)[:, :, None],
                            (b, s, h)).reshape(rows, 1)
    block_rows = min(block_s * h, rows)
    nb = -(-rows // block_rows)
    pad = nb * block_rows - rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        pos2 = jnp.pad(pos2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, theta=theta),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d), x.dtype),
        interpret=interpret,
    )(x2, pos2)
    return out[:rows].reshape(b, s, h, d)
