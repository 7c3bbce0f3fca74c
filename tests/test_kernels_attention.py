"""Flash/decode attention Pallas kernels vs the pure-jnp oracle:
shape/dtype sweeps + hypothesis property tests (interpret mode)."""
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_partial)
from repro.kernels.flash_attention import flash_attention

TOL = dict(rtol=2e-3, atol=2e-3)


def _mk(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,h,kvh,d,bq,bk", [
    (1, 16, 16, 1, 1, 8, 8, 8),
    (2, 33, 33, 4, 2, 16, 16, 16),
    (2, 64, 64, 8, 8, 32, 32, 16),
    (1, 128, 128, 4, 1, 64, 64, 64),
    (3, 25, 25, 6, 2, 16, 8, 8),
])
def test_flash_sweep(rng, dtype, b, sq, sk, h, kvh, d, bq, bk):
    q = _mk(rng, (b, sq, h, d), dtype)
    k = _mk(rng, (b, sk, kvh, d), dtype)
    v = _mk(rng, (b, sk, kvh, d), dtype)
    got = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    want = ref.plain_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32))
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_flash_window(rng):
    q = _mk(rng, (2, 48, 4, 16), jnp.float32)
    k = _mk(rng, (2, 48, 2, 16), jnp.float32)
    v = _mk(rng, (2, 48, 2, 16), jnp.float32)
    got = flash_attention(q, k, v, window=9, block_q=16, block_k=16,
                          interpret=True)
    want = ref.plain_attention(q, k, v, window=9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kvh,d,bs", [
    (1, 32, 2, 1, 8, 16),
    (2, 100, 8, 4, 32, 32),
    (4, 64, 8, 8, 64, 64),
])
def test_decode_sweep(rng, dtype, b, s, h, kvh, d, bs):
    q = _mk(rng, (b, h, d), dtype)
    k = _mk(rng, (b, s, kvh, d), dtype)
    v = _mk(rng, (b, s, kvh, d), dtype)
    lens = jnp.asarray(rng.integers(1, s + 1, size=(b,)), jnp.int32)
    got = decode_attention(q, k, v, lengths=lens, block_s=bs, interpret=True)
    want = ref.decode_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32), lengths=lens)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_decode_partials_combine_matches_monolithic(rng):
    """Sharded-KV partials merged with combine_partials == full attention —
    the invariant CompAir's NoC softmax tree relies on (paper Fig. 10)."""
    b, s, h, d = 2, 96, 4, 16
    q = _mk(rng, (b, h, d), jnp.float32)
    k = _mk(rng, (b, s, h, d), jnp.float32)
    v = _mk(rng, (b, s, h, d), jnp.float32)
    lens = jnp.array([70, 96], jnp.int32)
    want = ref.decode_attention(q, k, v, lengths=lens)
    parts = []
    for i, (lo, hi) in enumerate([(0, 32), (32, 64), (64, 96)]):
        parts.append(decode_attention_partial(
            q, k[:, lo:hi], v[:, lo:hi], lengths=lens, kv_offset=lo,
            block_s=16, interpret=True))
    acc = parts[0]
    for p in parts[1:]:
        acc = ref.combine_partials(acc, p)
    got = acc[0] / jnp.maximum(acc[2], 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@hypothesis.settings(max_examples=20, deadline=None)
@hypothesis.given(
    sq=st.integers(4, 40), h=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]), d=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2 ** 16),
)
def test_flash_property(sq, h, g, d, seed):
    rng = np.random.default_rng(seed)
    q = _mk(rng, (1, sq, h * g, d), jnp.float32)
    k = _mk(rng, (1, sq, h, d), jnp.float32)
    v = _mk(rng, (1, sq, h, d), jnp.float32)
    got = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
    want = ref.plain_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


def test_flash_row_convexity(rng):
    """Attention output rows are convex combinations of V rows: outputs
    are bounded by V's min/max per dim (softmax-weights property)."""
    q = _mk(rng, (1, 16, 2, 8), jnp.float32)
    k = _mk(rng, (1, 16, 2, 8), jnp.float32)
    v = _mk(rng, (1, 16, 2, 8), jnp.float32)
    out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
    assert float(out.max()) <= float(v.max()) + 1e-5
    assert float(out.min()) >= float(v.min()) - 1e-5


def test_dispatch_counts_reference_fallbacks(rng):
    """A call shape with no kernel takes the reference even when kernels are
    selected; ``ops.fallback_stats`` counts it, and nothing else."""
    from repro.kernels import ops
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    ops.reset_fallback_stats()
    with ops.use_mode("interpret"):
        ops.flash_attention(q, q, q, block_q=8, block_k=8)
        assert ops.fallback_stats() == {}
        ops.flash_attention(q, q, q, lengths=jnp.array([5]))
        assert ops.fallback_stats() == {"flash_attention": 1}
    with ops.use_mode("ref"):
        ops.flash_attention(q, q, q, lengths=jnp.array([5]))
    assert ops.fallback_stats() == {"flash_attention": 1}
    ops.reset_fallback_stats()


def test_pallas_mode_compiles_never_interprets():
    """'pallas' compiles for the TPU; off-TPU it fails instead of quietly
    switching to the interpreter."""
    from repro.kernels import ops
    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU 'pallas' compiles")
    with ops.use_mode("pallas"), pytest.raises(ValueError, match="interpret"):
        ops.rmsnorm(jnp.ones((8, 128)), jnp.ones((128,)))
