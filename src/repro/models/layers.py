"""Shared building blocks: linear, RMSNorm, RoPE-GQA attention, SwiGLU FFN.

Parameters are plain nested dicts of jnp arrays (no framework).  Compute
dtype is bf16 with fp32 accumulation (matching the paper's BF16 MAC units);
kernel dispatch goes through ``repro.kernels.ops``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels import ops


def _split(rng, n):
    return jax.random.split(rng, n)


def linear_init(rng, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.bfloat16, scale: Optional[float] = None):
    w_rng, _ = _split(rng, 2)
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(w_rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = jnp.einsum("...k,kn->...n", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def rmsnorm_init(d: int, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-5):
    return ops.rmsnorm(x, p["scale"], eps=eps)


def embed_init(rng, vocab: int, d: int, dtype=jnp.bfloat16):
    return {"table": (jax.random.normal(rng, (vocab, d), jnp.float32) * d ** -0.5).astype(dtype)}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


# ---------------------------------------------------------------------------
# attention (GQA + RoPE)
# ---------------------------------------------------------------------------

def attention_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    r = _split(rng, 4)
    return {
        "wq": linear_init(r[0], d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": linear_init(r[1], d, kvh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": linear_init(r[2], d, kvh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": linear_init(r[3], h * hd, d, dtype=dtype),
    }


def attention(p, x, positions, cfg: ModelConfig, *,
              lengths=None, window=None):
    """Full-sequence attention (train / prefill).  x [B,S,d] -> [B,S,d]."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(p["wq"], x).reshape(b, s, h, hd)
    k = linear(p["wk"], x).reshape(b, s, kvh, hd)
    v = linear(p["wv"], x).reshape(b, s, kvh, hd)
    q = ops.apply_rope(q, positions, theta=cfg.rope_theta)
    k = ops.apply_rope(k, positions, theta=cfg.rope_theta)
    o = ops.flash_attention(q, k, v, causal=True, lengths=lengths,
                            window=window)
    return linear(p["wo"], o.reshape(b, s, h * hd))


def attention_prefill(p, x, positions, cfg: ModelConfig, cache, *,
                      lengths=None, window=None):
    """Prefill: run full attention AND fill the KV cache slab [0, S)."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(p["wq"], x).reshape(b, s, h, hd)
    k = linear(p["wk"], x).reshape(b, s, kvh, hd)
    v = linear(p["wv"], x).reshape(b, s, kvh, hd)
    q = ops.apply_rope(q, positions, theta=cfg.rope_theta)
    k = ops.apply_rope(k, positions, theta=cfg.rope_theta)
    cache = {
        "k": lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, 0, 0, 0)),
    }
    o = ops.flash_attention(q, k, v, causal=True, lengths=lengths,
                            window=window)
    return linear(p["wo"], o.reshape(b, s, h * hd)), cache


def attention_decode(p, x, cfg: ModelConfig, cache, lengths, *, window=None):
    """One-token decode. x [B,1,d]; lengths[B] = tokens already in cache.

    Returns (y [B,1,d], new_cache).  The new K/V are written at position
    ``lengths`` per sequence; attention spans [0, lengths] inclusive.

    With ``shardhints.set_decode_attn`` active, the KV cache is
    sequence-sharded over the TP axis and per-shard flash-decoding
    partials (acc, m, l) are combined by the CompAir-NoC tree softmax
    (paper Fig. 10) — §Perf iteration 3.
    """
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(p["wq"], x).reshape(b, 1, h, hd)
    k = linear(p["wk"], x).reshape(b, 1, kvh, hd)
    v = linear(p["wv"], x).reshape(b, 1, kvh, hd)
    pos = lengths.astype(jnp.int32)[:, None]                 # [B,1]
    q = ops.apply_rope(q, pos, theta=cfg.rope_theta)
    k = ops.apply_rope(k, pos, theta=cfg.rope_theta)
    bidx = jnp.arange(b)
    ck = cache["k"].at[bidx, lengths].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, lengths].set(v[:, 0].astype(cache["v"].dtype))

    from repro.core import shardhints
    da = shardhints.get_decode_attn()
    if da is not None:
        o = _decode_attn_seqsharded(q[:, 0], ck, cv, lengths + 1, da)
    else:
        o = ops.decode_attention(q[:, 0], ck, cv, lengths=lengths + 1)
    y = linear(p["wo"], o.reshape(b, 1, h * hd) if o.ndim == 3 else o.reshape(b, h * hd))
    return y.reshape(b, 1, -1), {"k": ck, "v": cv}


def attention_decode_stacked(p, x, cfg: ModelConfig, ck_all, cv_all,
                             layer_idx, lengths, *, window=None):
    """Decode with the FULL stacked cache carried through the layer scan
    (§Perf iteration: cache-as-scan-ys rewrites whole slabs every step —
    measured 810 GiB/step at qwen2-72b decode_32k; carrying the stack and
    scattering only the new KV row leaves reads as the only slab traffic).

    ck_all/cv_all: [L, B, Smax, KvH, hd]; layer_idx: scalar int32.
    Returns (y [B,1,d], ck_all, cv_all)."""
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(p["wq"], x).reshape(b, 1, h, hd)
    k = linear(p["wk"], x).reshape(b, 1, kvh, hd)
    v = linear(p["wv"], x).reshape(b, 1, kvh, hd)
    pos = lengths.astype(jnp.int32)[:, None]
    q = ops.apply_rope(q, pos, theta=cfg.rope_theta)
    k = ops.apply_rope(k, pos, theta=cfg.rope_theta)
    bidx = jnp.arange(b)
    li = jnp.broadcast_to(layer_idx, (b,))
    ck_all = ck_all.at[li, bidx, lengths].set(k[:, 0].astype(ck_all.dtype))
    cv_all = cv_all.at[li, bidx, lengths].set(v[:, 0].astype(cv_all.dtype))
    ck = lax.dynamic_index_in_dim(ck_all, layer_idx, 0, keepdims=False)
    cv = lax.dynamic_index_in_dim(cv_all, layer_idx, 0, keepdims=False)

    from repro.core import shardhints
    da = shardhints.get_decode_attn()
    if da is not None:
        o = _decode_attn_seqsharded(q[:, 0], ck, cv, lengths + 1, da)
    else:
        o = ops.decode_attention(q[:, 0], ck, cv, lengths=lengths + 1)
    y = linear(p["wo"], o.reshape(b, h * hd))
    return y.reshape(b, 1, -1), ck_all, cv_all


def _decode_attn_seqsharded(q, ck, cv, lens, da):
    """flash-decoding over a sequence-sharded KV cache: local partials +
    in-transit (butterfly) softmax combine over the seq axis."""
    import jax.lax as lax
    from jax.sharding import PartitionSpec as P

    from repro.core import noc
    mesh, dp_axes, seq_ax = da
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in dp_axes if a in axis_sizes) or None

    def body(qv, ckv, cvv, ln):
        s_loc = ckv.shape[1]
        off = lax.axis_index(seq_ax) * s_loc
        acc, m, l = ops.decode_attention_partial(qv, ckv, cvv, lengths=ln,
                                                 kv_offset=off)
        return noc.tree_softmax_combine(acc, m, l, seq_ax).astype(qv.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, seq_ax, None, None),
                  P(dp, seq_ax, None, None), P(dp)),
        out_specs=P(dp, None, None), check_vma=False,
    )(q, ck, cv, lens)


def attn_cache_init(cfg: ModelConfig, batch: int, max_seq: int,
                    dtype=jnp.bfloat16, n_slots: int = 1):
    """KV cache for one attention application; [B, Smax, KvH, Dh]."""
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.hd)
    if n_slots > 1:
        shape = (n_slots,) + shape
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# paged KV cache (block tables; vLLM-style physical pages)
# ---------------------------------------------------------------------------
#
# Pages are laid out [L, KvH, NB, BS, Dh] so the paged Pallas kernel can DMA
# one (head, page) tile per grid step straight from the block-table index.
# Physical page 0 is reserved as a *null sink*: writes for padding rows and
# for retired slots land there, so a block-table entry of 0 is always safe.
#
# With ``kv_dtype="int8"`` pages store int8 values plus a per-page-per-head
# f32 scale ``[L, KvH, NB]`` (``k_scales``/``v_scales``); a row's float
# value is ``int8 * scale``.  Scatter paths keep a page's scale consistent
# with ALL its live rows (see the quantized scatter helpers below) and the
# paged kernels dequantize in their inner page loop, so the (acc, m, l)
# partials contract is unchanged.

# Floor for per-page scales: an all-zero page quantizes against this
# instead of dividing by zero.
KV_SCALE_EPS = 1e-8


def paged_kv_cache_init(cfg: ModelConfig, num_blocks: int, block_size: int,
                        dtype=jnp.bfloat16, n_slots: int = 1,
                        kv_dtype: str = "fp16"):
    shape = (n_slots, cfg.n_kv_heads, num_blocks, block_size, cfg.hd)
    if kv_dtype == "int8":
        sshape = (n_slots, cfg.n_kv_heads, num_blocks)
        return {"k_pages": jnp.zeros(shape, jnp.int8),
                "v_pages": jnp.zeros(shape, jnp.int8),
                "k_scales": jnp.ones(sshape, jnp.float32),
                "v_scales": jnp.ones(sshape, jnp.float32)}
    if kv_dtype != "fp16":
        raise ValueError(f"kv_dtype must be 'fp16' or 'int8', got {kv_dtype!r}")
    return {"k_pages": jnp.zeros(shape, dtype), "v_pages": jnp.zeros(shape, dtype)}


def _decode_scatter_quant(pages_all, scales_all, layer_idx, phys, off, row):
    """Scatter one decode row per sequence into int8 pages.

    pages_all [L, KvH, NB, BS, hd] int8; scales_all [L, KvH, NB] f32;
    phys/off [B] target page and row; row [B, KvH, hd] float.

    The page scale is *monotone within a page's life*: a page starting a
    new occupancy (``off == 0``) drops the previous occupant's scale, then
    each appended row can only grow it (``max(old, amax(row)/127)``).  On
    growth the page's earlier rows are requantized at the new scale (ratio
    <= 1, so no clipping); rows past ``off`` are stale garbage and zeroed.
    Duplicate ``phys`` entries (retired slots -> null page 0) last-write
    garbage into the null sink, which is never read as valid KV."""
    pages = lax.dynamic_index_in_dim(pages_all, layer_idx, 0, keepdims=False)
    scales = lax.dynamic_index_in_dim(scales_all, layer_idx, 0, keepdims=False)
    bs = pages.shape[2]
    rowT = jnp.moveaxis(row.astype(jnp.float32), 0, 1)       # [KvH, B, hd]
    old_q = pages[:, phys].astype(jnp.float32)               # [KvH, B, BS, hd]
    old_s = scales[:, phys]                                  # [KvH, B]
    base_s = jnp.where(off[None, :] == 0, 0.0, old_s)
    new_s = jnp.maximum(base_s, jnp.max(jnp.abs(rowT), axis=-1) / 127.0)
    new_s = jnp.maximum(new_s, KV_SCALE_EPS)
    ridx = jnp.arange(bs)
    keep = ridx[None, None, :] < off[None, :, None]          # [1, B, BS]
    req = jnp.round(old_q * (base_s / new_s)[..., None, None])
    req = jnp.where(keep[..., None], req, 0.0)
    newq = jnp.round(rowT / new_s[..., None])
    sel = ridx[None, None, :] == off[None, :, None]
    page = jnp.where(sel[..., None], newq[:, :, None, :], req)
    page = jnp.clip(page, -127.0, 127.0).astype(jnp.int8)
    pages_all = pages_all.at[layer_idx, :, phys].set(jnp.moveaxis(page, 0, 1))
    scales_all = scales_all.at[layer_idx, :, phys].set(new_s.T)
    return pages_all, scales_all


def _prefill_scatter_quant(pages_all, scales_all, layer_idx, block_table,
                           q_offset, length, chunk_rows):
    """Scatter a prefill chunk's rows into int8 pages.

    chunk_rows [C, KvH, hd] float at global positions
    [q_offset, q_offset + C) (rows past ``length`` invalid).  Works on the
    static window of ``ceil(C/BS) + 1`` logical blocks the chunk can touch:
    gather + dequantize the window, scatter the chunk rows (invalid rows
    dropped out-of-range), zero stale rows past the live end so they can't
    inflate a page's amax, requantize each window page at its own fresh
    scale.  The table is zero-padded before the dynamic window slice, so
    windows at the table end read null entries instead of shifting."""
    pages = lax.dynamic_index_in_dim(pages_all, layer_idx, 0, keepdims=False)
    scales = lax.dynamic_index_in_dim(scales_all, layer_idx, 0, keepdims=False)
    kvh, _, bs, hd = pages.shape
    c = chunk_rows.shape[0]
    npg = -(-c // bs) + 1
    first_lb = q_offset // bs
    btp = jnp.concatenate([block_table.astype(jnp.int32),
                           jnp.zeros((npg,), jnp.int32)])
    tbl = lax.dynamic_slice(btp, (first_lb,), (npg,))
    win = pages[:, tbl].astype(jnp.float32) \
        * scales[:, tbl][..., None, None]                    # [KvH, npg, BS, hd]
    win = win.reshape(kvh, npg * bs, hd)
    t = jnp.arange(c)
    pos = q_offset + t
    valid = t < length
    lpos = jnp.where(valid, pos - first_lb * bs, npg * bs)   # invalid: dropped
    win = win.at[:, lpos].set(
        jnp.moveaxis(chunk_rows.astype(jnp.float32), 0, 1), mode="drop")
    gpos = first_lb * bs + jnp.arange(npg * bs)
    live = gpos < q_offset + length
    win = jnp.where(live[None, :, None], win, 0.0)
    win = win.reshape(kvh, npg, bs, hd)
    new_s = jnp.maximum(
        jnp.max(jnp.abs(win), axis=(2, 3)) / 127.0, KV_SCALE_EPS)
    q8 = jnp.clip(jnp.round(win / new_s[..., None, None]),
                  -127.0, 127.0).astype(jnp.int8)
    pages_all = pages_all.at[layer_idx, :, tbl].set(jnp.moveaxis(q8, 0, 1))
    scales_all = scales_all.at[layer_idx, :, tbl].set(new_s.T)
    return pages_all, scales_all


def attention_decode_paged(p, x, cfg: ModelConfig, kp_all, vp_all,
                           layer_idx, lengths, block_tables, *, window=None,
                           seq_axis=None, ks_all=None, vs_all=None):
    """One-token decode against a paged KV cache.

    x [B,1,d]; kp_all/vp_all [L, KvH, NB, BS, Dh]; layer_idx scalar int32;
    lengths [B] = tokens already cached; block_tables [B, MB] int32.
    The new K/V row is scattered into the page holding position ``lengths``
    (retired slots carry an all-zero table row, so they write the null page).
    Returns (y [B,1,d], kp_all, vp_all, ks_all, vs_all).

    ``ks_all``/``vs_all`` [L, KvH, NB] f32 mark an int8-quantized pool: the
    scatter requantizes the touched page (see ``_decode_scatter_quant``) and
    the kernels dequantize per page; None (default) is the fp16 path,
    bit-exact with the pre-quantization behavior.

    With ``seq_axis`` set this runs inside ``shard_map`` over a
    sequence-sharded page pool: ``kp_all/vp_all`` are the *local* page
    shard, ``block_tables`` is the shard-local table (foreign pages -> 0,
    so the scatter lands in the local null page and attention skips them),
    and the per-shard (acc, m, l) partials ride
    ``core.noc.tree_softmax_combine`` — the paper's in-transit reduction."""
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bs = kp_all.shape[3]
    q = linear(p["wq"], x).reshape(b, 1, h, hd)
    k = linear(p["wk"], x).reshape(b, 1, kvh, hd)
    v = linear(p["wv"], x).reshape(b, 1, kvh, hd)
    pos = lengths.astype(jnp.int32)[:, None]
    q = ops.apply_rope(q, pos, theta=cfg.rope_theta)
    k = ops.apply_rope(k, pos, theta=cfg.rope_theta)
    bidx = jnp.arange(b)
    phys = block_tables[bidx, lengths // bs]                 # [B]
    off = lengths % bs
    if ks_all is None:
        ks = vs = None
        kp_all = kp_all.at[layer_idx, :, phys, off].set(k[:, 0].astype(kp_all.dtype))
        vp_all = vp_all.at[layer_idx, :, phys, off].set(v[:, 0].astype(vp_all.dtype))
    else:
        kp_all, ks_all = _decode_scatter_quant(kp_all, ks_all, layer_idx,
                                               phys, off, k[:, 0])
        vp_all, vs_all = _decode_scatter_quant(vp_all, vs_all, layer_idx,
                                               phys, off, v[:, 0])
        ks = lax.dynamic_index_in_dim(ks_all, layer_idx, 0, keepdims=False)
        vs = lax.dynamic_index_in_dim(vs_all, layer_idx, 0, keepdims=False)
    kp = lax.dynamic_index_in_dim(kp_all, layer_idx, 0, keepdims=False)
    vp = lax.dynamic_index_in_dim(vp_all, layer_idx, 0, keepdims=False)
    if seq_axis is None:
        o = ops.paged_decode_attention(q[:, 0], kp, vp, block_tables,
                                       lengths=lengths + 1,
                                       k_scales=ks, v_scales=vs)
    else:
        from repro.core import noc
        acc, m, l = ops.paged_decode_attention_partial(
            q[:, 0], kp, vp, block_tables, lengths=lengths + 1,
            skip_null=True, k_scales=ks, v_scales=vs)
        o = noc.tree_softmax_combine(acc, m, l, seq_axis).astype(x.dtype)
    y = linear(p["wo"], o.reshape(b, h * hd))
    return y.reshape(b, 1, -1), kp_all, vp_all, ks_all, vs_all


def attention_prefill_paged(p, x, positions, cfg: ModelConfig, kp_all, vp_all,
                            layer_idx, block_table, q_offset, length, *,
                            window=None, seq_axis=None, q_tile=None,
                            ks_all=None, vs_all=None):
    """Chunked prefill of ONE sequence (batch 1) against paged KV.

    x [1,C,d] is the chunk at global positions [q_offset, q_offset+C);
    ``length`` (traced scalar) counts the valid rows of the chunk.  The
    chunk's K/V are scattered into their pages first (padding rows redirect
    to the null page 0), then attention runs *directly on the pages* via
    ``ops.paged_prefill_attention`` — the block table is resolved inside
    the Pallas index_map (scalar prefetch), so nothing is linearized on the
    kernel path, and the fallback gathers only the ``block_table`` slice
    the caller passes (prefix-length-bucketed, not the whole pool).
    Returns (y [1,C,d], kp_all, vp_all, ks_all, vs_all).

    ``ks_all``/``vs_all`` [L, KvH, NB] f32 mark an int8-quantized pool: the
    chunk scatter requantizes the touched page window (see
    ``_prefill_scatter_quant``) and the kernels dequantize per page; None
    (default) is the fp16 path, bit-exact with pre-quantization behavior.

    With ``seq_axis`` set (inside ``shard_map`` over a sequence-sharded
    page pool) ``block_table`` is the shard-local slice — foreign pages
    are 0, so their K/V scatter hits the local null page and attention
    skips them — and per-shard (acc, m, l) prefill partials merge via
    ``core.noc.tree_softmax_combine``, causal masking staying on global
    positions.

    ``q_tile`` threads through to the kernel's query-tile size (chunk
    positions; None = VMEM-budget auto) — it never changes results, only
    the kernel's VMEM footprint, which is what lets big prefill buckets
    through."""
    _, c, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bs = kp_all.shape[3]
    q = linear(p["wq"], x).reshape(1, c, h, hd)
    k = linear(p["wk"], x).reshape(1, c, kvh, hd)
    v = linear(p["wv"], x).reshape(1, c, kvh, hd)
    q = ops.apply_rope(q, positions, theta=cfg.rope_theta)
    k = ops.apply_rope(k, positions, theta=cfg.rope_theta)

    # scatter the chunk K/V into pages; invalid rows -> null page 0
    if ks_all is None:
        ks = vs = None
        t = jnp.arange(c)
        pos = q_offset + t
        valid = t < length
        phys = jnp.where(valid, block_table[jnp.clip(pos // bs, 0,
                                                     block_table.shape[0] - 1)], 0)
        off = pos % bs
        kp_all = kp_all.at[layer_idx, :, phys, off].set(k[0].astype(kp_all.dtype))
        vp_all = vp_all.at[layer_idx, :, phys, off].set(v[0].astype(vp_all.dtype))
    else:
        kp_all, ks_all = _prefill_scatter_quant(kp_all, ks_all, layer_idx,
                                                block_table, q_offset, length,
                                                k[0])
        vp_all, vs_all = _prefill_scatter_quant(vp_all, vs_all, layer_idx,
                                                block_table, q_offset, length,
                                                v[0])
        ks = lax.dynamic_index_in_dim(ks_all, layer_idx, 0, keepdims=False)
        vs = lax.dynamic_index_in_dim(vs_all, layer_idx, 0, keepdims=False)

    kp = lax.dynamic_index_in_dim(kp_all, layer_idx, 0, keepdims=False)
    vp = lax.dynamic_index_in_dim(vp_all, layer_idx, 0, keepdims=False)
    if seq_axis is None:
        o = ops.paged_prefill_attention(q, kp, vp, block_table,
                                        q_offset=q_offset, length=length,
                                        window=window, q_tile=q_tile,
                                        k_scales=ks, v_scales=vs)
    else:
        if window is not None:
            raise NotImplementedError(
                "windowed attention over a sequence-sharded page pool")
        from repro.core import noc
        acc, m, l = ops.paged_prefill_attention_partial(
            q, kp, vp, block_table, q_offset=q_offset, length=length,
            skip_null=True, q_tile=q_tile, k_scales=ks, v_scales=vs)
        o = noc.tree_softmax_combine(acc, m, l, seq_axis).astype(x.dtype)
    y = linear(p["wo"], o.reshape(1, c, h * hd))
    return y, kp_all, vp_all, ks_all, vs_all


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------

def ffn_init(rng, d: int, d_ff: int, dtype=jnp.bfloat16):
    r = _split(rng, 3)
    return {
        "gate": linear_init(r[0], d, d_ff, dtype=dtype),
        "up": linear_init(r[1], d, d_ff, dtype=dtype),
        "down": linear_init(r[2], d_ff, d, dtype=dtype),
    }


def ffn(p, x):
    g = linear(p["gate"], x)
    u = linear(p["up"], x)
    return linear(p["down"], ops.silu_mul(g, u))
