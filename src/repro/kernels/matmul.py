"""Weight-stationary tiled matmul — Pallas TPU kernel (the "SRAM-PIM lane").

CompAir's SRAM-PIM holds a weight tile stationary (SRAM_Write) while input
vectors stream through (SRAM_Compute); profitability requires batch-level
weight reuse (paper Fig. 4B).  TPU analogue: grid order (n-panel OUTER,
m-tile INNER) so the weight panel [K, bn] is fetched HBM->VMEM once per n
and *reused across every input row tile* — consecutive grid steps with an
unchanged block index elide the re-fetch, exactly weight-stationarity.

The MXU wants 128-aligned tiles; `bm`/`bn` default to 256/256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref):
    x = x_ref[...]
    w = w_ref[...]
    o_ref[...] = lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def weight_stationary_matmul(x, w, *, bm: int = 256, bn: int = 256,
                             out_dtype=None, interpret: bool = False):
    """x [M, K] @ w [K, N] -> [M, N]; weight panel stationary across M tiles.

    Constraint: the [K, bn] panel must fit VMEM (K * bn * bytes <= ~4MB);
    callers route larger K through XLA's native dot (see ops.matmul).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    out_dtype = out_dtype or x.dtype
    bm = min(bm, m)
    bn = min(bn, n)
    nm = -(-m // bm)
    nn = -(-n // bn)
    pm, pn = nm * bm - m, nn * bn - n
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
    if pn:
        w = jnp.pad(w, ((0, 0), (0, pn)))
    out = pl.pallas_call(
        _kernel,
        grid=(nn, nm),  # n OUTER, m INNER: weight panel stationary over m
        in_specs=[
            pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
            pl.BlockSpec((k, bn), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), out_dtype),
        name="matmul",
        interpret=interpret,
    )(x, w)
    return out[:m, :n]
