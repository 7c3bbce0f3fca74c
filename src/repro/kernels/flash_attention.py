"""Causal flash attention — Pallas TPU kernel (prefill / training fwd).

TPU adaptation of the paper's "SRAM-PIM stacking DRAM" idea for attention:
K/V stream HBM->VMEM block by block (the DRAM->SRAM hybrid-bonding path),
while the online-softmax running statistics (m, l, acc) stay resident in
VMEM scratch — the same (m, l) statistics CompAir's NoC reduce-tree
combines across banks when the KV sequence is sharded (see core/noc.py).

Grid: (B * H, n_q_blocks, n_kv_blocks); the last axis is innermost and
sequential on TPU, so (m, l, acc) accumulate across KV blocks in scratch.
KV blocks strictly above the causal diagonal are compute-skipped.
GQA: each query head indexes its KV head's blocks via ``bh // group``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale: float, block_q: int, block_k: int, causal: bool,
            sq: int, sk: int, window):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:  # static python bool -> two kernel variants
        run = (ik * block_k) <= (iq * block_q + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, (ik + 1) * block_k - 1 > iq * block_q - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # [bq, D]
        k = k_ref[0].astype(jnp.float32)                     # [bk, D]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        qpos = iq * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ik * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        pv = lax.dot_general(p, v_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 256, block_k: int = 256,
                    window=None, interpret: bool = False):
    """q [B, Sq, H, D]; k, v [B, Sk, KvH, D] -> [B, Sq, H, D]."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = -(-sq // block_q)
    nk = -(-sk // block_k)
    pad_q = nq * block_q - sq
    pad_k = nk * block_k - sk

    qh = jnp.moveaxis(q, 2, 1)                               # [B, H, Sq, D]
    kh = jnp.moveaxis(k, 2, 1)                               # [B, KvH, Sk, D]
    vh = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    qh = qh.reshape(b * h, nq * block_q, d)
    kh = kh.reshape(b * kvh, nk * block_k, d)
    vh = vh.reshape(b * kvh, nk * block_k, d)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), block_q=block_q, block_k=block_k,
        causal=causal, sq=sq, sk=sk, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qh, kh, vh)

    out = out.reshape(b, h, nq * block_q, d)[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)
