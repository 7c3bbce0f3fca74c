"""Mamba2 SSD chunked scan — Pallas TPU kernel.

The per-head state h [P, N] lives in VMEM scratch across the whole
sequence; each grid step processes one chunk with two small matmuls
(intra-chunk) plus a rank-1-style state update — the MXU-friendly
reformulation of the recurrence.  All decay exponents are pairwise
differences of a non-increasing cumulative sum, hence <= 0 (stable).

Grid: (B, H, n_chunks); chunk axis innermost-sequential.
Oracle: kernels/ref.py::mamba2_scan / mamba2_step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hf_ref, h_scr,
            *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xc = x_ref[0].astype(jnp.float32)                # [T, P]
    dt_col = dt_ref[0].astype(jnp.float32)           # [T, 1]
    A = a_ref[pl.program_id(1)]                      # scalar (SMEM)
    Bc = b_ref[0].astype(jnp.float32)                # [T, N]
    Cc = c_ref[0].astype(jnp.float32)                # [T, N]
    h = h_scr[...]                                   # [P, N]
    t = chunk

    # Mosaic has no cumsum or 1-D vectors: prefix sums are masked
    # reductions over a [T, U] grid, kept as [T, 1] columns / [1, U] rows
    r = lax.broadcasted_iota(jnp.int32, (t, t), 0)
    c = lax.broadcasted_iota(jnp.int32, (t, t), 1)
    tri = r >= c
    dt_row = jnp.sum(jnp.where(r == c, dt_col, 0.0), axis=0, keepdims=True)
    dA_col = dt_col * A                              # [T, 1], <= 0
    dA_row = dt_row * A                              # [1, U]
    cum_col = jnp.sum(jnp.where(tri, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(r <= c, dA_col, 0.0), axis=0, keepdims=True)
    total = jnp.sum(dA_col, axis=0, keepdims=True)   # [1, 1] = cum[-1]
    decay = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)       # [T, U]
    cb = lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)        # [T, U]
    wmat = decay * cb * dt_row
    y_intra = lax.dot_general(wmat, xc, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)   # [T, P]
    # inter-chunk: y += exp(cum_t) * Cc_t . h
    ch = lax.dot_general(Cc, h, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)        # [T, P]
    y = y_intra + ch * jnp.exp(cum_col)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update: h = exp(cum[-1]) h + (dec_rest*dt*x)^T B
    dec_rest = jnp.exp(total - cum_col) * dt_col     # [T, 1]
    h_new = h * jnp.exp(total) + lax.dot_general(
        xc * dec_rest, Bc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [P, N]
    h_scr[...] = h_new

    @pl.when(ic == nc - 1)
    def _final():
        hf_ref[0, 0] = h_new


def mamba2_chunked(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False):
    """x [Bt,S,H,P]; dt [Bt,S,H]; A [H]; B,C [Bt,S,N]
    -> (y [Bt,S,H,P], h_final [Bt,H,P,N])."""
    bt, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    nc = -(-s // chunk)
    pad = nc * chunk - s
    xh = jnp.moveaxis(x, 2, 1)                       # [Bt,H,S,P]
    dth = jnp.moveaxis(dt, 2, 1)[..., None]          # [Bt,H,S,1]
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dth = jnp.pad(dth, ((0, 0), (0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    xh = xh.reshape(bt * h, nc * chunk, p)
    dth = dth.reshape(bt * h, nc * chunk, 1)

    y, hf = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(bt, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda ib, ih, ic, _h=h: (ib * _h + ih, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ih, ic, _h=h: (ib * _h + ih, ic, 0)),
            # whole [H] decay vector in SMEM (a (1,) block is not tileable)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda ib, ih, ic, _h=h: (ib * _h + ih, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt * h, nc * chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bt, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), B, C)

    y = y.reshape(bt, h, nc * chunk, p)[:, :, :s]
    return jnp.moveaxis(y, 1, 2), hf
