"""NoC-fused cross-entropy (vocab-sharded, butterfly logsumexp) equals the
single-program reference."""


def test_noc_xent_matches_plain(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.train.step import cross_entropy, cross_entropy_noc

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
rng = np.random.default_rng(0)
B, S, V = 4, 6, 32
logits = jnp.asarray(rng.normal(size=(B, S, V)) * 3, jnp.float32)
labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
want = float(cross_entropy(logits, labels))
got = float(cross_entropy_noc(logits, labels, mesh, ('data',), 'model'))
assert abs(got - want) < 1e-5, (got, want)

mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)
want_m = float(cross_entropy(logits, labels, mask=mask))
got_m = float(cross_entropy_noc(logits, labels, mesh, ('data',), 'model',
                                mask=mask))
assert abs(got_m - want_m) < 1e-5, (got_m, want_m)
print('OK', got, got_m)
""")
    assert "OK" in out


def test_noc_xent_grads_match(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.train.step import cross_entropy, cross_entropy_noc
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
rng = np.random.default_rng(1)
B, S, V = 2, 4, 16
logits = jnp.asarray(rng.normal(size=(B, S, V)), jnp.float32)
labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
g1 = jax.grad(lambda lg: cross_entropy(lg, labels))(logits)
g2 = jax.grad(lambda lg: cross_entropy_noc(lg, labels, mesh, ('data',),
                                           'model'))(logits)
err = float(jnp.abs(g1 - g2).max())
assert err < 1e-6, err
print('OK', err)
""")
    assert "OK" in out
