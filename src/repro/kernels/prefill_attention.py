"""Paged prefill attention — Pallas TPU kernel (q-tiled chunk queries vs.
the paged KV cache, causal within the chunk).

This is the prefill half of the paged serving path.  The decode kernel
(``decode_attention._paged_kernel``) streams one query row past the pages;
here a whole prefill *chunk* rides along: the chunk's K/V rows are first
scattered into their pages (``models/layers.attention_prefill_paged``), then
this kernel attends over pages ``[0, ceil((q_offset+length)/BS))`` with the
block table resolved inside the BlockSpec ``index_map`` via scalar prefetch.
The host never linearizes the page table (the old path gathered *all*
``max_blocks`` pages per layer per chunk — O(pool) copies for O(cached)
live tokens, the inter-bank shuffling overhead CompAir attacks).

**Q-tiling.**  The chunk axis C is tiled at ``q_tile`` positions (T): the
grid is ``(KvH, n_q_tiles, n_pages)`` with a fixed ``[T*G, d]`` query tile
in VMEM, and the online-softmax scratch ``(m, l, acc)`` — sized ``[T*G]``,
not ``[C*G]`` — is carried across the (sequential) page axis per q-tile.
VMEM footprint is therefore independent of the chunk size, which is what
lets the serving engine chunk prefill at buckets far above 512 (fewer,
fatter dispatches; the single-shard bound the ROADMAP calls the kernel
tentpole — sharding shrinks the KV range, never the q tile).  ``q_tile``
defaults to the largest power of two whose scratch fits
``DEFAULT_VMEM_BUDGET`` (see :func:`resolve_q_tile`).

Work is bounded by the live prefix *per q-tile*: tile ``iq`` covers global
positions ``[q_offset + iq*T, q_offset + (iq+1)*T)``, so its causal window
ends at ``min(q_offset + length, q_offset + (iq+1)*T)`` KV rows — the
scalar-prefetch ``index_map`` clamps grid steps past that tile-local live
page onto the final live page (consecutive identical indices elide the
DMA) and ``pl.when`` skips the compute.  Early q-tiles of a chunk thus
skip the page DMAs their causal window never reaches — a real win on the
first chunks of a long prompt, not just a correctness guard.

The kernel keeps the decode kernel's ``(acc, m, l)`` partials contract
(see ``decode_attention.py``'s module docstring for the full statement:
partials algebra, paged index-map addressing, and the ``skip_null``
shard-local-table flag), so ``core.noc.tree_softmax_combine`` applies
unchanged when the page pool is sequence-sharded.  Prefill-specific
points of that contract:

* Causal masking is on **global** positions (``q_offset + row``), KV
  validity on ``kpos < q_offset + length`` — chunked calls with growing
  ``q_offset`` reproduce a monolithic prefill exactly.
* The query tile is row-major ``(position, group)``: tile row ``r`` of
  q-tile ``iq`` is chunk position ``iq*T + r // G``, query head ``r % G``,
  so per-row masks read straight off an iota.
* ``block_table`` may be a prefix *slice* of the slot's table (the engine
  passes a power-of-two bucket covering the live prefix); work is bounded
  by ``ceil((q_offset + length) / BS)`` pages, never the pool size.
* A q-tile whose every live page is foreign under ``skip_null`` returns
  the zero-weight partial ``(0, NEG_INF, 0)`` row-wise — the combine
  identity, so an all-foreign tile contributes nothing over the mesh.

Testing recipe: every kernel here runs under ``interpret=True`` on CPU
against the dense oracles in ``kernels/ref.py`` (gather pages, run the
linear-cache reference, compare to fp32 tolerance) — see
``tests/test_serve_paged.py``, ``tests/test_kernels_prefill_qtile.py``
and docs/kernels.md.

Grid: (KvH, n_q_tiles, n_pages) — last axis sequential, scratch carried.
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

# Per-grid-step VMEM the q-tiled kernel may occupy (blocks + scratch +
# outputs, double-buffered streams included).  ~16 MB VMEM per TPU core;
# 4 MiB leaves generous room for the surrounding layer's other buffers.
DEFAULT_VMEM_BUDGET = 4 * 1024 * 1024


def q_tile_vmem_bytes(q_tile: int, group: int, head_dim: int,
                      block_s: int, itemsize: int = 4) -> int:
    """VMEM bytes one grid step of the q-tiled kernel occupies for a
    ``[q_tile*group, head_dim]`` query tile: streamed blocks (q tile +
    K/V page, x2 for double buffering) plus the f32 carried scratch and
    the output blocks.  The engine's construction-time guard prices
    ``prefill_buckets`` against this model (see ``serve.engine``)."""
    rows = q_tile * group
    blocks = rows * head_dim * itemsize + 2 * block_s * head_dim * itemsize
    scratch = rows * head_dim * 4 + 2 * rows * 4          # acc + m + l
    outs = rows * head_dim * 4 + 2 * rows * 4             # o + m + l
    return 2 * blocks + scratch + outs


def resolve_q_tile(c: int, group: int, head_dim: int, block_s: int,
                   q_tile=None, vmem_budget: int = DEFAULT_VMEM_BUDGET,
                   ) -> int:
    """Effective query-tile size (chunk positions) for a C-position chunk.

    An explicit ``q_tile`` is honored (clamped to ``[1, C]`` — callers
    wanting the old whole-chunk tile pass ``q_tile >= C``).  ``None``
    picks the largest power of two, floored at 8 positions, whose
    :func:`q_tile_vmem_bytes` fits ``vmem_budget`` — so small chunks keep
    the seed kernel's single-tile behavior and only big buckets tile."""
    if q_tile is not None:
        return max(1, min(int(q_tile), c))
    t = 1
    while t < c:
        t *= 2
    while t > 8 and q_tile_vmem_bytes(t, group, head_dim, block_s) > vmem_budget:
        t //= 2
    return min(t, c)


def _paged_prefill_kernel(bt_ref, qlen_ref, *refs, scale: float,
                          block_s: int, group: int, q_tile: int,
                          return_partials: bool, skip_null: bool = False,
                          quantized: bool = False):
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr) = refs
    else:
        ks_ref = vs_ref = None
        (q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr) = refs
    ih = pl.program_id(0)
    iq = pl.program_id(1)
    ibk = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(ibk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    total = qlen_ref[0]                  # q_offset + length (live KV rows)
    qoff = qlen_ref[1]                   # first global position of the chunk
    # this q-tile's causal window ends where its last row sits (or at the
    # live KV end, whichever is first) — pages past that are dead for it
    tile_end = jnp.minimum(total, qoff + (iq + 1) * q_tile)
    n_live = (tile_end + block_s - 1) // block_s

    live = ibk < n_live
    if skip_null:
        # shard-local table: a zero entry inside the live prefix is a page
        # another shard of the sequence-sharded pool owns — skip it too
        live &= bt_ref[ibk] != 0

    # K-axis blocking (mirrors decode_attention._paged_kernel): for pools
    # with block_s > 64 the identical online-softmax recurrence runs per
    # 64-row K-subtile under the page step, so live f32 K/V values stay
    # [64, D] however big the page is.  block_s stays the DMA grain.
    kt = block_s if (block_s <= 64 or block_s % 64) else 64

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # [T*G, D]
        if quantized:
            # compute only runs for live steps, whose bt entry IS the page
            page = bt_ref[ibk]
        m_c = m_scr[...]
        l_c = l_scr[...]
        acc_c = acc_scr[...]
        for ti in range(block_s // kt):
            k = k_ref[0, 0, pl.ds(ti * kt, kt)].astype(jnp.float32)
            v = v_ref[0, 0, pl.ds(ti * kt, kt)].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[ih, page]
                v = v * vs_ref[ih, page]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            # row r of tile iq is (position iq*T + r // G, head r % G)
            qpos = (qoff + iq * q_tile
                    + lax.broadcasted_iota(jnp.int32, s.shape, 0) // group)
            kpos = (ibk * block_s + ti * kt
                    + lax.broadcasted_iota(jnp.int32, s.shape, 1))
            valid = (kpos <= qpos) & (kpos < total)
            s = jnp.where(valid, s, NEG_INF)                 # [T*G, kt]
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

    @pl.when(ibk == nb - 1)
    def _finalize():
        if return_partials:
            o_ref[0] = acc_scr[...].astype(o_ref.dtype)
            m_ref[0] = m_scr[...]
            l_ref[0] = l_scr[...]
        else:
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_prefill(q, k_pages, v_pages, block_table, q_offset, length, *,
                   return_partials: bool, interpret: bool,
                   skip_null: bool = False, q_tile=None,
                   k_scales=None, v_scales=None):
    b, c, h, d = q.shape
    assert b == 1, "paged prefill is single-sequence (chunked serving)"
    kvh, _, bs, _ = k_pages.shape
    g = h // kvh
    mb = block_table.shape[0]
    t = resolve_q_tile(c, g, d, bs, q_tile)
    nqt = -(-c // t)
    # row-major (position, group) tile so qpos = tile_base + row // g
    qh = jnp.transpose(q.reshape(c, kvh, g, d), (1, 0, 2, 3))
    qh = qh.reshape(kvh, c * g, d)
    if nqt * t != c:
        # pad trailing positions (row-major layout: appended rows ARE the
        # appended positions); their rows are masked-garbage and sliced off
        qh = jnp.pad(qh, ((0, 0), (0, (nqt * t - c) * g), (0, 0)))
    total = jnp.asarray(q_offset + length, jnp.int32)
    qlen = jnp.stack([jnp.minimum(total, mb * bs),
                      jnp.asarray(q_offset, jnp.int32)])

    quantized = k_scales is not None
    out_dt = jnp.float32 if return_partials else q.dtype
    kernel = functools.partial(
        _paged_prefill_kernel, scale=1.0 / math.sqrt(d), block_s=bs,
        group=g, q_tile=t, return_partials=return_partials,
        skip_null=skip_null, quantized=quantized)

    def _page_idx(ih, iq, ibk, bt, ql):
        # clamp dead grid steps onto the tile's LAST live page: tile iq
        # never reads past its causal end min(total, qoff + (iq+1)*T), so
        # the repeated index elides the trailing page DMAs and pl.when
        # skips the compute — early q-tiles of a chunk do less IO
        tile_end = jnp.minimum(ql[0], ql[1] + (iq + 1) * t)
        n_live = jnp.maximum((tile_end + bs - 1) // bs, 1)
        return bt[jnp.minimum(ibk, n_live - 1)]

    # trailing *_ absorbs the scalar-prefetch operands, so one index_map
    # set serves both the 2-operand (fp16) and 4-operand (quantized) grids
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_table, (total, q_offset) (+ k_scales, v_scales quantized)
        num_scalar_prefetch=4 if quantized else 2,
        grid=(kvh, nqt, mb),
        in_specs=[
            pl.BlockSpec((1, t * g, d),
                         lambda ih, iq, ibk, *_: (ih, iq, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda ih, iq, ibk, bt, ql, *_:
                         (ih, _page_idx(ih, iq, ibk, bt, ql), 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda ih, iq, ibk, bt, ql, *_:
                         (ih, _page_idx(ih, iq, ibk, bt, ql), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, t * g, d),
                         lambda ih, iq, ibk, *_: (ih, iq, 0)),
            # [KvH, rows, 1] columns: (t*g, 1) is TPU-tileable, (1, t*g)
            # on a [KvH, rows] array is not
            pl.BlockSpec((1, t * g, 1), lambda ih, iq, ibk, *_: (ih, iq, 0)),
            pl.BlockSpec((1, t * g, 1), lambda ih, iq, ibk, *_: (ih, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, d), jnp.float32),
        ],
    )
    prefetch = (block_table.astype(jnp.int32), qlen)
    if quantized:
        prefetch += (k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32))
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((kvh, nqt * t * g, d), out_dt),
            jax.ShapeDtypeStruct((kvh, nqt * t * g, 1), jnp.float32),
            jax.ShapeDtypeStruct((kvh, nqt * t * g, 1), jnp.float32),
        ],
        name="paged_prefill_attention",
        interpret=interpret,
    )(*prefetch, qh, k_pages, v_pages)
    out = out[:, :c * g]
    m = m[:, :c * g, 0]
    l = l[:, :c * g, 0]
    out = jnp.transpose(out.reshape(kvh, c, g, d), (1, 0, 2, 3))
    m = jnp.transpose(m.reshape(kvh, c, g), (1, 0, 2))
    l = jnp.transpose(l.reshape(kvh, c, g), (1, 0, 2))
    return (out.reshape(1, c, h, d), m.reshape(1, c, h), l.reshape(1, c, h))


def paged_prefill_attention(q, k_pages, v_pages, block_table, *, q_offset,
                            length, q_tile=None, k_scales=None,
                            v_scales=None, interpret: bool = False):
    """q [1,C,H,D]; k_pages,v_pages [KvH,NB,BS,D]; block_table [MB] -> [1,C,H,D].

    The chunk's own K/V must already be scattered into the pages; causal
    masking is on global positions (``q_offset + row``), KV validity on
    ``kpos < q_offset + length``.  ``q_tile`` sets the query-tile size in
    chunk positions (None: auto per :func:`resolve_q_tile`).
    ``k_scales``/``v_scales`` [KvH, NB] f32 mark an int8-quantized pool:
    each (head, page) tile is dequantized in the inner page loop."""
    out, _, _ = _paged_prefill(q, k_pages, v_pages, block_table, q_offset,
                               length, return_partials=False,
                               interpret=interpret, q_tile=q_tile,
                               k_scales=k_scales, v_scales=v_scales)
    return out


def paged_prefill_attention_partial(q, k_pages, v_pages, block_table, *,
                                    q_offset, length, skip_null: bool = False,
                                    q_tile=None, k_scales=None,
                                    v_scales=None, interpret: bool = False):
    """Per-shard partials (acc f32 [1,C,H,D], m [1,C,H], l [1,C,H]) for the
    NoC tree combine — same algebra as the decode kernels.  ``skip_null``
    elides zero table entries (the shard-local-table contract); a q-tile
    whose live pages are all foreign yields ``(0, NEG_INF, 0)`` rows.
    ``k_scales``/``v_scales``: per-page dequant scales (int8 pool)."""
    return _paged_prefill(q, k_pages, v_pages, block_table, q_offset, length,
                          return_partials=True, interpret=interpret,
                          skip_null=skip_null, q_tile=q_tile,
                          k_scales=k_scales, v_scales=v_scales)
