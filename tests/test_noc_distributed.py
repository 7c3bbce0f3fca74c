"""CompAir-NoC collectives under shard_map (8 fake devices, subprocess):
tree/butterfly == psum/pmax; fused tree softmax == monolithic softmax;
sequence-sharded decode combine == unsharded decode; int8 butterfly."""


def test_tree_collectives_match_builtins(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import noc
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
rng = np.random.default_rng(0)
v = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
for name, fn, want in [
    ('butterfly_add', lambda a: noc.butterfly_all_reduce(a, 'x'), v.sum(0)),
    ('butterfly_max', lambda a: noc.butterfly_all_reduce(a, 'x', 'max'), v.max(0)),
    ('tree_add', lambda a: noc.tree_all_reduce(a, 'x'), v.sum(0)),
    ('tree_max', lambda a: noc.tree_all_reduce(a, 'x', 'max'), v.max(0)),
]:
    got = jax.shard_map(fn, mesh=mesh, in_specs=P('x'), out_specs=P('x'),
                        check_vma=False)(v)
    err = float(jnp.abs(got - want[None]).max())
    assert err < 1e-5, (name, err)
print('OK')
""")
    assert "OK" in out


def test_distributed_softmax_and_logsumexp(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import noc
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 64)) * 4, jnp.float32)
ds = jax.shard_map(lambda a: noc.distributed_softmax(a, 'x'), mesh=mesh,
                   in_specs=P(None, 'x'), out_specs=P(None, 'x'), check_vma=False)
assert float(jnp.abs(ds(x) - jax.nn.softmax(x, -1)).max()) < 1e-5
dl = jax.shard_map(lambda a: noc.distributed_logsumexp(a, 'x'), mesh=mesh,
                   in_specs=P(None, 'x'), out_specs=P(None), check_vma=False)
assert float(jnp.abs(dl(x) - jax.nn.logsumexp(x, -1)).max()) < 1e-5
cs = jax.shard_map(lambda a: noc.centralized_softmax(a, 'x'), mesh=mesh,
                   in_specs=P(None, 'x'), out_specs=P(None, 'x'), check_vma=False)
assert float(jnp.abs(cs(x) - jax.nn.softmax(x, -1)).max()) < 1e-5
print('OK')
""")
    assert "OK" in out


def test_seq_sharded_decode_combine(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import noc
from repro.kernels import ref
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
rng = np.random.default_rng(0)
B,H,D,S = 2,4,16,64
q = jnp.asarray(rng.normal(size=(B,H,D)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B,S,H,D)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B,S,H,D)), jnp.float32)
want = ref.decode_attention(q, k, v)
for combiner in (noc.tree_softmax_combine, noc.centralized_softmax_combine):
    got = jax.shard_map(
        lambda a,b,c: combiner(*ref.decode_attention_partial(a,b,c), 'x').astype(a.dtype),
        mesh=mesh, in_specs=(P(), P(None,'x'), P(None,'x')), out_specs=P(),
        check_vma=False)(q, k, v)
    assert float(jnp.abs(got - want).max()) < 1e-5, combiner.__name__
print('OK')
""")
    assert "OK" in out


def test_int8_butterfly_allreduce(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train import compress
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
got = jax.shard_map(lambda a: compress.butterfly_allreduce_int8(a[0], 'x')[None],
                    mesh=mesh, in_specs=P('x'), out_specs=P('x'),
                    check_vma=False)(g)
want = g.mean(0)
rel = float(jnp.abs(got - want[None]).max() / (jnp.abs(want).max() + 1e-9))
assert rel < 0.05, rel  # int8 quantization noise bound
print('OK rel', rel)
""")
    assert "OK" in out


def test_grad_compression_error_feedback(subproc):
    """Error feedback keeps compressed-SGD convergent on a quadratic."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train import compress
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
target = jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)

def step(w, err, xs):
    # per-shard quadratic grads (different data per shard)
    def body(w, e, x):
        g = {'w': (w - target) * (1.0 + 0.1 * x)}
        synced, e2 = compress.compressed_grad_sync(g, 'x', {'w': e})
        return synced['w'], e2['w']
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P('x'), P('x')), out_specs=(P(), P('x')),
                         check_vma=False)(w, err, xs)

w = jnp.zeros((64,))
err = jnp.zeros((8, 64))
xs = jnp.asarray(np.random.default_rng(1).normal(size=(8, 64)), jnp.float32)
for i in range(60):
    g, err = step(w, err, xs)
    w = w - 0.3 * g[0] if g.ndim > 1 else w - 0.3 * g
loss = float(jnp.abs(w - target).max())
assert loss < 0.05, loss
print('OK', loss)
""")
    assert "OK" in out
