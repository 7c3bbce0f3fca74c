"""Jit'd public kernel API with platform dispatch.

TPU  -> Pallas kernels (the tiled/fused implementations)
other-> pure-jnp references (kernels/ref.py) — the CPU dry-run lowers these
tests-> Pallas with ``interpret=True`` against the ref oracle

``set_mode`` / ``use_mode`` force a path globally (benchmarks flip this);
the default 'auto' picks by backend platform.  'pallas' always compiles the
kernels for the TPU (off-TPU that fails rather than interpreting);
'interpret' runs them in the Pallas interpreter.

A few call shapes have no kernel and always take the reference, in every
mode: ``flash_attention`` with ragged ``lengths`` or a ``q_offset``,
``paged_prefill_attention`` with a ``window``, ``rwkv6_scan`` /
``mamba2_scan`` with an initial state.  Each such trace is counted in
:func:`fallback_stats`, so a caller can assert its path has none.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import prefill_attention as _pf
from repro.kernels import rmsnorm as _rn
from repro.kernels import rope as _rope
from repro.kernels import swiglu as _sw
from repro.kernels import matmul as _mm
from repro.kernels import rwkv_chunk as _rwkv
from repro.kernels import mamba_chunk as _mamba

_MODE = "auto"  # 'auto' | 'ref' | 'pallas' | 'interpret'


def set_mode(mode: str) -> None:
    global _MODE
    assert mode in ("auto", "ref", "pallas", "interpret"), mode
    _MODE = mode


def get_mode() -> str:
    return _MODE


@contextlib.contextmanager
def use_mode(mode: str):
    prev = _MODE
    set_mode(mode)
    try:
        yield
    finally:
        set_mode(prev)


def _use_pallas() -> bool:
    if _MODE == "ref":
        return False
    if _MODE in ("pallas", "interpret"):
        return True
    return jax.default_backend() == "tpu"


def _interp() -> bool:
    return _MODE == "interpret"


# Trace-time count of kernel-less call shapes that took the reference while
# the Pallas path was selected, keyed by op name (see the module docstring).
_FALLBACKS: dict = {}


def reset_fallback_stats() -> None:
    _FALLBACKS.clear()


def fallback_stats() -> dict:
    return dict(_FALLBACKS)


def _fallback(op: str) -> None:
    if _use_pallas():
        _FALLBACKS[op] = _FALLBACKS.get(op, 0) + 1


def using_pallas() -> bool:
    """Public probe: will dispatch take the Pallas/kernel path right now?
    (Hosts use it to account work that only the fallback performs.)"""
    return _use_pallas()


# ---------------------------------------------------------------------------

def rmsnorm(x, w, *, eps: float = 1e-5, curry_rounds: int = 0):
    if _use_pallas():
        return _rn.rmsnorm(x, w, eps=eps, curry_rounds=curry_rounds,
                           interpret=_interp())
    return ref.rmsnorm(x, w, eps)


def apply_rope(x, positions, *, theta: float = 10_000.0):
    if _use_pallas():
        return _rope.apply_rope(x, positions, theta=theta, interpret=_interp())
    return ref.apply_rope(x, positions, theta)


def silu(x):
    return ref.silu(x)


def silu_mul(gate, up, *, curry_rounds: int = 0):
    if _use_pallas():
        return _sw.silu_mul(gate, up, curry_rounds=curry_rounds,
                            interpret=_interp())
    return ref.silu_mul(gate, up)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    lengths=None, q_offset: int = 0,
                    block_q: int = 256, block_k: int = 256):
    # the Pallas path handles causal/window; ragged ``lengths`` prefill and
    # offset decode fall back to the ref (serving-edge cases, small shapes)
    if _use_pallas() and lengths is None and q_offset == 0:
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=_interp())
    _fallback("flash_attention")
    return ref.flash_attention(q, k, v, causal=causal, window=window,
                               lengths=lengths, q_offset=q_offset)


def decode_attention(q, k, v, *, lengths=None, block_s: int = 512):
    if _use_pallas():
        return _da.decode_attention(q, k, v, lengths=lengths,
                                    block_s=block_s, interpret=_interp())
    return ref.decode_attention(q, k, v, lengths=lengths)


def decode_attention_partial(q, k, v, *, lengths=None, kv_offset: int = 0,
                             block_s: int = 512):
    if _use_pallas():
        return _da.decode_attention_partial(
            q, k, v, lengths=lengths, kv_offset=kv_offset, block_s=block_s,
            interpret=_interp())
    return ref.decode_attention_partial(q, k, v, lengths=lengths,
                                        kv_offset=kv_offset)


def paged_decode_attention(q, k_pages, v_pages, block_tables, *, lengths=None,
                           k_scales=None, v_scales=None):
    if _use_pallas():
        return _da.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                          lengths=lengths, k_scales=k_scales,
                                          v_scales=v_scales,
                                          interpret=_interp())
    return ref.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                      lengths=lengths, k_scales=k_scales,
                                      v_scales=v_scales)


def paged_decode_attention_partial(q, k_pages, v_pages, block_tables, *,
                                   lengths=None, kv_offset: int = 0,
                                   skip_null: bool = False,
                                   k_scales=None, v_scales=None):
    if _use_pallas():
        return _da.paged_decode_attention_partial(
            q, k_pages, v_pages, block_tables, lengths=lengths,
            kv_offset=kv_offset, skip_null=skip_null, k_scales=k_scales,
            v_scales=v_scales, interpret=_interp())
    return ref.paged_decode_attention_partial(q, k_pages, v_pages,
                                              block_tables, lengths=lengths,
                                              kv_offset=kv_offset,
                                              skip_null=skip_null,
                                              k_scales=k_scales,
                                              v_scales=v_scales)


# Trace-time gather accounting: ``gather_pages`` linearizes pages host-side
# (the data movement the paged kernels avoid), so every call site that still
# traces one is visible here.  ``pages`` counts block-table entries — the
# number of page copies the traced program performs per execution.
_GATHER_STATS = {"calls": 0, "pages": 0}


def reset_gather_stats() -> None:
    _GATHER_STATS["calls"] = 0
    _GATHER_STATS["pages"] = 0


def gather_stats() -> dict:
    return dict(_GATHER_STATS)


def gather_pages(pages, block_table, scales=None):
    n = block_table.shape[-1]
    if block_table.ndim == 2:
        n *= block_table.shape[0]
    _GATHER_STATS["calls"] += 1
    _GATHER_STATS["pages"] += int(n)
    return ref.gather_pages(pages, block_table, scales)


def paged_prefill_attention(q, k_pages, v_pages, block_table, *, q_offset,
                            length, window=None, q_tile=None,
                            k_scales=None, v_scales=None):
    """Prefill-chunk attention over paged KV (chunk K/V already scattered).

    Kernel path: scalar-prefetch page gather inside the Pallas index_map —
    no host-side linearization at all; ``q_tile`` sizes its query tile in
    chunk positions (None: VMEM-budget auto, see
    ``prefill_attention.resolve_q_tile``).  Fallback: gather exactly the
    pages in ``block_table`` (callers pass a prefix-length-bucketed slice,
    so the copy volume tracks the live prefix, not the pool); the ref path
    is dense so ``q_tile`` has no effect there.  ``k_scales``/``v_scales``
    [KvH, NB] dequantize an int8 pool (kernel: inner page loop; fallback:
    during the gather)."""
    if _use_pallas() and window is None:
        return _pf.paged_prefill_attention(
            q, k_pages, v_pages, block_table, q_offset=q_offset,
            length=length, q_tile=q_tile, k_scales=k_scales,
            v_scales=v_scales, interpret=_interp())
    _fallback("paged_prefill_attention")
    k_lin = gather_pages(k_pages, block_table, k_scales)[None]
    v_lin = gather_pages(v_pages, block_table, v_scales)[None]
    return ref.flash_attention(q, k_lin, v_lin, causal=True,
                               q_offset=q_offset,
                               lengths=jnp.reshape(q_offset + length, (1,)),
                               window=window)


def paged_prefill_attention_partial(q, k_pages, v_pages, block_table, *,
                                    q_offset, length, skip_null: bool = False,
                                    q_tile=None, k_scales=None,
                                    v_scales=None):
    if _use_pallas():
        return _pf.paged_prefill_attention_partial(
            q, k_pages, v_pages, block_table, q_offset=q_offset,
            length=length, skip_null=skip_null, q_tile=q_tile,
            k_scales=k_scales, v_scales=v_scales, interpret=_interp())
    return ref.paged_prefill_attention_partial(
        q, k_pages, v_pages, block_table, q_offset=q_offset, length=length,
        skip_null=skip_null, k_scales=k_scales, v_scales=v_scales)


def matmul(x, w, *, out_dtype=None, bm: int = 256, bn: int = 256,
           vmem_budget: int = 4 * 1024 * 1024):
    """2-D matmul; routes to the weight-stationary kernel when the weight
    panel fits VMEM (the SRAM-PIM condition), else XLA's native dot."""
    if _use_pallas() and x.ndim == 2:
        k, n = w.shape
        panel = k * min(bn, n) * w.dtype.itemsize
        if panel <= vmem_budget:
            return _mm.weight_stationary_matmul(
                x, w, bm=bm, bn=bn, out_dtype=out_dtype, interpret=_interp())
    return ref.matmul(x, w, out_dtype=out_dtype)


import os as _os
_RWKV_REF_CHUNKED = not _os.environ.get("REPRO_RWKV_RECURRENT")
# §Perf iteration 1 (rwkv6-3b x train_4k):
# the exact recurrent scan reads+writes the [H, D, D] wkv state every
# token (measured 5.4e3 s memory term at train_4k); the chunked form
# amortizes state traffic over `chunk` tokens. Flip False for baseline.


def set_rwkv_ref_chunked(flag: bool) -> None:
    global _RWKV_REF_CHUNKED
    _RWKV_REF_CHUNKED = flag


def rwkv6_scan(r, k, v, w, u, *, s0=None, chunk: int = 32, ref_chunk: int = 16):
    if _use_pallas() and s0 is None:
        return _rwkv.rwkv6_chunked(r, k, v, w, u, chunk=chunk,
                                   interpret=_interp())
    _fallback("rwkv6_scan")
    if _RWKV_REF_CHUNKED and r.shape[1] >= 2 * ref_chunk:
        return ref.rwkv6_scan_chunked(r, k, v, w, u, s0=s0, chunk=ref_chunk)
    return ref.rwkv6_scan(r, k, v, w, u, s0=s0)


def rwkv6_step(rt, kt, vt, wt, u, S):
    return ref.rwkv6_step(rt, kt, vt, wt, u, S)


def mamba2_scan(x, dt, A, B, C, *, h0=None, chunk: int = 64):
    if _use_pallas() and h0 is None:
        return _mamba.mamba2_chunked(x, dt, A, B, C, chunk=chunk,
                                     interpret=_interp())
    _fallback("mamba2_scan")
    return ref.mamba2_scan(x, dt, A, B, C, h0=h0, chunk=chunk)


def mamba2_step(xt, dtt, A, Bt_, Ct, h):
    return ref.mamba2_step(xt, dtt, A, Bt_, Ct, h)


def combine_partials(a, b):
    return ref.combine_partials(a, b)
