"""Hypothesis property tests for the NoC tree collectives: equivalence to
reference reductions across axis sizes, dtypes, and payload shapes —
run in one subprocess sweep to amortize process startup."""


def test_tree_properties_sweep(subproc):
    out = subproc("""
import itertools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import noc

rng = np.random.default_rng(42)
failures = []
for n, shape, dtype, comb in itertools.product(
        (2, 4, 8), ((4,), (3, 5), (2, 2, 2)),
        (jnp.float32, jnp.bfloat16), ('add', 'max')):
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((n,), ('x',))
    v = jnp.asarray(rng.normal(size=(n,) + shape), dtype)
    want = (v.astype(jnp.float32).sum(0) if comb == 'add'
            else v.astype(jnp.float32).max(0))
    for fn in (noc.butterfly_all_reduce, noc.tree_all_reduce):
        got = jax.shard_map(lambda a: fn(a, 'x', comb), mesh=mesh,
                            in_specs=P('x'), out_specs=P('x'),
                            check_vma=False)(v)
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want[None]).max())
        tol = 1e-5 if dtype == jnp.float32 else 0.15
        if err > tol:
            failures.append((fn.__name__, n, shape, str(dtype), comb, err))
assert not failures, failures
print('OK all', 3 * 3 * 2 * 2 * 2, 'combos')
""")
    assert "OK" in out


def test_combine_partials_associative(subproc):
    """(a ⊕ b) ⊕ c == a ⊕ (b ⊕ c) for the softmax-partial combine — the
    property that makes ANY reduction-tree shape valid (paper Fig. 14A)."""
    out = subproc("""
import jax.numpy as jnp, numpy as np
from repro.kernels import ref
rng = np.random.default_rng(0)
def mk():
    acc = jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(2, 3)) * 3, jnp.float32)
    l = jnp.asarray(rng.uniform(0.1, 5.0, size=(2, 3)), jnp.float32)
    return acc, m, l
for _ in range(25):
    a, b, c = mk(), mk(), mk()
    left = ref.combine_partials(ref.combine_partials(a, b), c)
    right = ref.combine_partials(a, ref.combine_partials(b, c))
    for x, y in zip(left, right):
        assert float(jnp.abs(x - y).max()) < 1e-4
print('OK')
""", devices=1)
    assert "OK" in out
