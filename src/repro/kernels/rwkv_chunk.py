"""RWKV-6 wkv recurrence — chunked Pallas TPU kernel.

The wkv state S [D, D] is the "weight" that changes every token — the
paper's observation that input-dependent matrices (here the recurrent
state, in attention the K/V) defeat SRAM-PIM weight reuse and belong on
the bandwidth lane.  The kernel keeps S resident in VMEM scratch across
the whole sequence (grid-sequential chunk axis) and uses the
pairwise-difference decay form whose exponents are all <= 0 (stable).

Grid: (B * H, n_chunks); chunk axis innermost-sequential.
Oracle: kernels/ref.py::rwkv6_scan (exact recurrent form).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sf_ref, s_scr,
            *, chunk: int):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    rc = r_ref[0].astype(jnp.float32)                # [T, D]
    kc = k_ref[0].astype(jnp.float32)
    vc = v_ref[0].astype(jnp.float32)
    wc = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                 # [D]
    S = s_scr[...]                                   # [D, D]
    t = chunk

    logw = jnp.log(jnp.maximum(wc, 1e-20))
    cum = jnp.cumsum(logw, axis=0)                   # [T, D]
    cum_in = cum - logw                              # log prod_{j<t}
    # state contribution
    o_state = lax.dot_general(rc * jnp.exp(cum_in), S,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # intra-chunk pairwise decay (exponents <= 0 under the strict-lower mask)
    logdiff = cum_in[:, None, :] - cum[None, :, :]   # [T, U, D]
    tri = (lax.broadcasted_iota(jnp.int32, (t, t), 0)
           > lax.broadcasted_iota(jnp.int32, (t, t), 1))
    dec = jnp.where(tri[:, :, None], jnp.exp(logdiff), 0.0)
    att = jnp.sum(rc[:, None, :] * dec * kc[None, :, :], axis=-1)   # [T, U]
    o_intra = lax.dot_general(att, vc, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    bonus = jnp.sum(rc * u[None, :] * kc, axis=-1)   # [T]
    o_ref[0] = (o_state + o_intra + bonus[:, None] * vc).astype(o_ref.dtype)
    # state update
    dec_out = jnp.exp(cum[-1][None, :] - cum)        # [T, D]
    s_new = S * jnp.exp(cum[-1])[:, None] + lax.dot_general(
        kc * dec_out, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(ic == nc - 1)
    def _final():
        sf_ref[0] = s_new


def rwkv6_chunked(r, k, v, w, u, *, chunk: int = 32, interpret: bool = False):
    """r,k,v,w [B,S,H,D]; u [H,D] -> (o [B,S,H,D], S_final [B,H,D,D])."""
    b, s, h, d = r.shape
    chunk = min(chunk, s)
    nc = -(-s // chunk)
    pad = nc * chunk - s

    def prep(t, fill=0.0):
        th = jnp.moveaxis(t, 2, 1)                   # [B,H,S,D]
        if pad:
            th = jnp.pad(th, ((0, 0), (0, 0), (0, pad), (0, 0)),
                         constant_values=fill)
        return th.reshape(b * h, nc * chunk, d)

    rr, kk, vv = prep(r), prep(k), prep(v)
    ww = prep(w, fill=1.0)

    o, sf = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, d), lambda bh, ic, _h=h: (bh % _h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, d, d), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, nc * chunk, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        name="rwkv_chunk_scan",
        interpret=interpret,
    )(rr, kk, vv, ww, u)

    o = o.reshape(b, h, nc * chunk, d)[:, :, :s]
    return jnp.moveaxis(o, 1, 2), sf.reshape(b, h, d, d)
