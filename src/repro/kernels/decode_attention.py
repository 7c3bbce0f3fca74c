"""Flash-decoding — Pallas TPU kernel (single new token vs. a long KV cache).

This is the DRAM-PIM ("bandwidth lane") workload of the paper: GeMV-shaped,
zero weight reuse, latency dominated by streaming the KV cache from HBM.
The kernel keeps the query resident in VMEM and streams KV blocks, exactly
like AiM banks stream rows past their 16-input MAC units.

When the KV cache is *sequence-sharded* across devices (long_500k), each
device runs this kernel over its slab and returns (acc, m, l) partials;
``core.noc.tree_softmax_combine`` merges them over the mesh — the paper's
Fig. 10 in-transit Softmax reduction.

Contract (shared with ``prefill_attention.py``; quoted by docs/kernels.md):

* **Partials algebra.**  ``*_partial`` variants return un-normalized
  ``(acc f32 [..., D], m [...], l [...])`` online-softmax state per query
  row: ``m`` the running max, ``l`` the running exp-sum, ``acc`` the
  exp-weighted V sum.  Two partials over disjoint KV ranges combine
  associatively via ``ref.combine_partials``; normalizing is
  ``acc / max(l, eps)``.  A row that saw no valid KV degrades to
  ``(acc=0, m=NEG_INF, l=0)``, which combines to zero weight.
* **Paged addressing.**  The paged kernels never see a linearized cache:
  the block table rides scalar prefetch and is resolved inside the
  BlockSpec ``index_map``, so the DMA engine gathers (head, page) tiles
  directly.  Dead grid steps clamp their index to the last live page —
  consecutive identical indices elide the DMA — and skip compute.
* **``skip_null``.**  Off (default): a zero table entry is ordinary page
  0 (unsharded semantics).  On (the sequence-sharded shard-local-table
  contract): a zero entry marks a page some other shard owns — compute
  is skipped entirely, so foreign pages contribute nothing even inside
  the live range and an all-foreign row yields the zero-weight partial.

Grid: (B, KvH, n_seq_blocks) — last axis sequential, scratch accumulates.
The kernels write ``m``/``l`` as ``[B, KvH, G, 1]`` columns: a TPU block's
last two dims must be (8, 128)-aligned or span the array, which ``(G, 1)``
does for any G.  The wrappers reshape them to ``[B, H]``.
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


def _kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref,
            m_scr, l_scr, acc_scr, *, scale: float, block_s: int,
            kv_offset: int, return_partials: bool):
    ib = pl.program_id(0)
    isq = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(isq == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                      # [G, D]
    k = k_ref[0].astype(jnp.float32)                         # [bs, D]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale  # [G, bs]
    kpos = kv_offset + isq * block_s + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < len_ref[ib]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(isq == ns - 1)
    def _finalize():
        if return_partials:
            o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
            m_ref[0, 0] = m_scr[...]
            l_ref[0, 0] = l_scr[...]
        else:
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode(q, k, v, lengths, *, kv_offset: int, block_s: int,
            return_partials: bool, interpret: bool):
    b, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    block_s = min(block_s, sk)
    ns = -(-sk // block_s)
    pad = ns * block_s - sk
    kh = jnp.moveaxis(k, 2, 1)                               # [B, KvH, Sk, D]
    vh = jnp.moveaxis(v, 2, 1)
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qh = q.reshape(b, kvh, g, d)
    if lengths is None:
        lengths = jnp.full((b,), kv_offset + sk, jnp.int32)
    # clamp by the slab: positions beyond sk are invalid regardless
    lens = jnp.minimum(lengths.astype(jnp.int32), kv_offset + sk)

    out_dt = jnp.float32 if return_partials else q.dtype
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), block_s=block_s,
        kv_offset=kv_offset, return_partials=return_partials)

    out, m, l = pl.pallas_call(
        kernel,
        grid=(b, kvh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, isq: (ib, ih, 0, 0)),
            pl.BlockSpec((1, block_s, d), lambda ib, ih, isq, _kvh=kvh: (ib * _kvh + ih, isq, 0)),
            pl.BlockSpec((1, block_s, d), lambda ib, ih, isq, _kvh=kvh: (ib * _kvh + ih, isq, 0)),
            # whole [B] lengths vector in SMEM (a (1,) block is not tileable)
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, isq: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda ib, ih, isq: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda ib, ih, isq: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, d), out_dt),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        name="decode_attention",
        interpret=interpret,
    )(qh, kh.reshape(b * kvh, ns * block_s, d), vh.reshape(b * kvh, ns * block_s, d), lens)
    return out.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def decode_attention(q, k, v, *, lengths=None, block_s: int = 512,
                     interpret: bool = False):
    """q [B,H,D]; k,v [B,Sk,KvH,D] -> [B,H,D]."""
    out, _, _ = _decode(q, k, v, lengths, kv_offset=0, block_s=block_s,
                        return_partials=False, interpret=interpret)
    return out


def decode_attention_partial(q, k, v, *, lengths=None, kv_offset: int = 0,
                             block_s: int = 512, interpret: bool = False):
    """Per-shard partials (acc f32, m, l) for the NoC tree combine."""
    return _decode(q, k, v, lengths, kv_offset=kv_offset, block_s=block_s,
                   return_partials=True, interpret=interpret)


# ---------------------------------------------------------------------------
# paged variant: the KV cache lives in physical pages [KvH, NB, BS, D] and a
# per-sequence block table maps logical block -> page.  The page id feeds the
# BlockSpec index_map via scalar prefetch, so the DMA engine gathers pages
# directly — the host never linearizes the cache.  Everything else (online
# softmax over sequential KV blocks, the (acc, m, l) partials contract that
# ``core.noc.tree_softmax_combine`` consumes) is identical to the dense path.
#
# Quantized pool (``k_scales``/``v_scales`` not None): pages are int8 and a
# per-page-per-head f32 scale array [KvH, NB] rides scalar prefetch alongside
# the block table; the kernel dequantizes the (head, page) tile right after
# the DMA (``k * ks[ih, page]``) so the online softmax — and with it the
# (acc, m, l) contract, ``skip_null`` and the NoC combine — runs in f32
# exactly as on the fp16 path.  Scales live in SMEM; the extra traffic is one
# scalar per page step.
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, len_ref, *refs, scale: float, block_s: int,
                  kv_offset: int, return_partials: bool,
                  skip_null: bool = False, quantized: bool = False):
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr) = refs
    else:
        ks_ref = vs_ref = None
        (q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr) = refs
    ib = pl.program_id(0)
    ih = pl.program_id(1)
    ibk = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(ibk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # K-axis blocking: a big-page pool (block_s > 64) would otherwise hold
    # a whole [BS, D] f32 K and V tile live through the softmax update; a
    # static K-tile loop *under* the page step runs the identical online-
    # softmax recurrence per 64-row subtile (the carry (acc, m, l) is the
    # same state, updated more often), bounding live VMEM values at
    # [64, D] regardless of pool block size.  block_s stays the DMA grain.
    kt = block_s if (block_s <= 64 or block_s % 64) else 64

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [G, D]
        if quantized:
            page = bt_ref[ib, ibk]
        m_c = m_scr[...]
        l_c = l_scr[...]
        acc_c = acc_scr[...]
        for t in range(block_s // kt):
            k = k_ref[0, 0, pl.ds(t * kt, kt)].astype(jnp.float32)  # [kt, D]
            v = v_ref[0, 0, pl.ds(t * kt, kt)].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[ih, page]
                v = v * vs_ref[ih, page]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            kpos = (kv_offset + ibk * block_s + t * kt
                    + lax.broadcasted_iota(jnp.int32, s.shape, 1))
            valid = kpos < len_ref[ib]
            s = jnp.where(valid, s, NEG_INF)                 # [G, kt]
            m_new = jnp.maximum(m_c, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_c - m_new)
            l_c = l_c * corr + p.sum(axis=1, keepdims=True)
            acc_c = acc_c * corr + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_c = m_new
        m_scr[...] = m_c
        l_scr[...] = l_c
        acc_scr[...] = acc_c

    if skip_null:
        # shard-local table: entry 0 = a page another shard owns (or dead
        # tail) — elide its compute entirely; it must not touch (m, l, acc)
        pl.when(bt_ref[ib, ibk] != 0)(_compute)
    else:
        _compute()

    @pl.when(ibk == nb - 1)
    def _finalize():
        if return_partials:
            o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
            m_ref[0, 0] = m_scr[...]
            l_ref[0, 0] = l_scr[...]
        else:
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode(q, k_pages, v_pages, block_tables, lengths, *,
                  kv_offset: int, return_partials: bool, interpret: bool,
                  skip_null: bool = False, k_scales=None, v_scales=None):
    b, h, d = q.shape
    kvh, _, bs, _ = k_pages.shape
    g = h // kvh
    mb = block_tables.shape[1]
    qh = q.reshape(b, kvh, g, d)
    if lengths is None:
        lengths = jnp.full((b,), kv_offset + mb * bs, jnp.int32)
    lens = jnp.minimum(lengths.astype(jnp.int32), kv_offset + mb * bs)

    quantized = k_scales is not None
    out_dt = jnp.float32 if return_partials else q.dtype
    kernel = functools.partial(
        _paged_kernel, scale=1.0 / math.sqrt(d), block_s=bs,
        kv_offset=kv_offset, return_partials=return_partials,
        skip_null=skip_null, quantized=quantized)

    # trailing *_ absorbs the scalar-prefetch operands, so one index_map set
    # serves both the 2-operand (fp16) and 4-operand (quantized) grids
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables, lengths (+ k_scales, v_scales when quantized)
        num_scalar_prefetch=4 if quantized else 2,
        grid=(b, kvh, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ibk, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda ib, ih, ibk, bt, *_: (ih, bt[ib, ibk], 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda ib, ih, ibk, bt, *_: (ih, bt[ib, ibk], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ibk, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda ib, ih, ibk, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda ib, ih, ibk, *_: (ib, ih, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    prefetch = (block_tables.astype(jnp.int32), lens)
    if quantized:
        prefetch += (k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32))
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, d), out_dt),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        ],
        name="paged_decode_attention",
        interpret=interpret,
    )(*prefetch, qh, k_pages, v_pages)
    return out.reshape(b, h, d), m.reshape(b, h), l.reshape(b, h)


def paged_decode_attention(q, k_pages, v_pages, block_tables, *, lengths=None,
                           k_scales=None, v_scales=None,
                           interpret: bool = False):
    """q [B,H,D]; k_pages,v_pages [KvH,NB,BS,D]; block_tables [B,MB] -> [B,H,D].

    ``k_scales``/``v_scales`` [KvH, NB] f32 mark an int8-quantized pool:
    each (head, page) tile is dequantized in the inner page loop."""
    out, _, _ = _paged_decode(q, k_pages, v_pages, block_tables, lengths,
                              kv_offset=0, return_partials=False,
                              interpret=interpret,
                              k_scales=k_scales, v_scales=v_scales)
    return out


def paged_decode_attention_partial(q, k_pages, v_pages, block_tables, *,
                                   lengths=None, kv_offset: int = 0,
                                   skip_null: bool = False,
                                   k_scales=None, v_scales=None,
                                   interpret: bool = False):
    """Per-shard paged partials (acc f32, m, l) for the NoC tree combine.

    ``skip_null``: zero table entries skip compute (consecutive zeros also
    collapse their null-page DMAs, since the block index repeats) — the
    shard-local-table contract for sequence-sharded page pools.
    ``k_scales``/``v_scales``: per-page dequant scales (int8 pool)."""
    return _paged_decode(q, k_pages, v_pages, block_tables, lengths,
                         kv_offset=kv_offset, return_partials=True,
                         interpret=interpret, skip_null=skip_null,
                         k_scales=k_scales, v_scales=v_scales)
