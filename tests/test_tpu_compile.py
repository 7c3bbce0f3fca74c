"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) accepts block shapes and VMEM
footprints that the TPU compiler refuses, so these tests compile each kernel
for a *described* v5e chip at the widths the configs serve: stablelm-1.6b
(head_dim 64, d_model 2048, d_ff 5632), olmoe-1b-7b (head_dim 128) and
zamba2-7b (head_dim 112, 112 Mamba2 heads).  No chip is needed; nothing runs.
Each compiled program must contain the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import mamba_chunk
from repro.kernels import prefill_attention as pf
from repro.kernels import rmsnorm as rn
from repro.kernels import rope
from repro.kernels import swiglu

HEADS = 32           # query and KV heads (MHA) of all three configs
POOL_PAGES = 513     # 8 slots x 1024 positions of 16-token pages + null
SLOTS = 8
MAX_SEQ = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _pool(spec, hd, bs, quantized):
    dt = jnp.int8 if quantized else jnp.bfloat16
    pages = [spec((HEADS, POOL_PAGES * 16 // bs, bs, hd), dt)] * 2
    scales = ([spec((HEADS, POOL_PAGES * 16 // bs), jnp.float32)] * 2
              if quantized else [])
    return pages, scales


def _scales_kw(s):
    return dict(k_scales=s[0], v_scales=s[1]) if s else {}


@pytest.mark.parametrize("partial", [False, True])
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("hd", [64, 112, 128])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode(spec, quantized, hd, bs, partial):
    pages, scales = _pool(spec, hd, bs, quantized)
    op = (da.paged_decode_attention_partial if partial
          else da.paged_decode_attention)

    def f(q, kp, vp, bt, lens, *s):
        return op(q, kp, vp, bt, lengths=lens, **_scales_kw(s))

    _assert_kernel(f, spec((SLOTS, HEADS, hd)), *pages,
                   spec((SLOTS, MAX_SEQ // bs), jnp.int32),
                   spec((SLOTS,), jnp.int32), *scales)


@pytest.mark.parametrize("partial", [False, True])
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("hd", [64, 112, 128])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_prefill_qtiled(spec, quantized, hd, bs, partial):
    """A 1024-position chunk: several query tiles at the VMEM-budget tile."""
    chunk = 1024
    pages, scales = _pool(spec, hd, bs, quantized)
    op = (pf.paged_prefill_attention_partial if partial
          else pf.paged_prefill_attention)

    def f(q, kp, vp, bt, q_offset, length, *s):
        return op(q, kp, vp, bt, q_offset=q_offset, length=length,
                  **_scales_kw(s))

    _assert_kernel(f, spec((1, chunk, HEADS, hd)), *pages,
                   spec((MAX_SEQ // bs,), jnp.int32), spec((), jnp.int32),
                   spec((), jnp.int32), *scales)


def test_dense_decode(spec):
    _assert_kernel(
        lambda q, k, v, lens: da.decode_attention(q, k, v, lengths=lens),
        spec((SLOTS, HEADS, 64)), spec((SLOTS, MAX_SEQ, HEADS, 64)),
        spec((SLOTS, MAX_SEQ, HEADS, 64)), spec((SLOTS,), jnp.int32))


@pytest.mark.parametrize("batch,seq", [(SLOTS, 1), (1, 512)],
                         ids=["decode", "prefill"])
def test_rope(spec, batch, seq):
    _assert_kernel(lambda x, pos: rope.apply_rope(x, pos),
                   spec((batch, seq, HEADS, 64)),
                   spec((batch, seq), jnp.int32))


@pytest.mark.parametrize("rows", [SLOTS, 2048], ids=["decode", "prefill"])
def test_silu_mul(spec, rows):
    _assert_kernel(swiglu.silu_mul, spec((rows, 5632)), spec((rows, 5632)))


@pytest.mark.parametrize("d", [2048, 3584], ids=["stablelm", "zamba2"])
def test_rmsnorm(spec, d):
    _assert_kernel(rn.rmsnorm, spec((512, d)), spec((d,)))


def test_mamba2_chunk_scan(spec):
    """zamba2-7b: d_model 3584 x expand 2 / head_dim 64 = 112 heads."""
    seq, heads, p, n = 256, 112, 64, 64
    _assert_kernel(mamba_chunk.mamba2_chunked,
                   spec((1, seq, heads, p)), spec((1, seq, heads), jnp.float32),
                   spec((heads,), jnp.float32), spec((1, seq, n)),
                   spec((1, seq, n)))
