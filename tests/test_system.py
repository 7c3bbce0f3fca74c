"""End-to-end behaviour of the full system:
train -> checkpoint -> restore -> serve with the trained weights; plus a
miniature dry-run (lower+compile with shardings on a 2x2x2 fake mesh) and
the elastic-rescale path (restore onto a different mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced
from repro.data import SyntheticLM
from repro.models import model
from repro.serve import ServeEngine
from repro.train import init_state, make_train_step


def test_train_checkpoint_serve_cycle(tmp_path):
    cfg = reduced(get_config("stablelm-1.6b"))
    state = init_state(cfg, jax.random.key(0))
    step = jax.jit(make_train_step(cfg, base_lr=3e-3, warmup=2,
                                   total_steps=40))
    ds = SyntheticLM(cfg.vocab_size, 24, 4, seed=7)
    for i in range(12):
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in ds.batch(i).items()})
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(11, state)
    step_no, restored = mgr.restore(jax.eval_shape(lambda: state))
    assert step_no == 11

    eng = ServeEngine(cfg, restored.params, max_seq=48, slots=2)
    eng.submit([1, 2, 3, 4], max_new_tokens=5)
    done = eng.run_until_drained()
    assert len(done) == 1 and len(done[0].out_tokens) == 5
    # restored params serve identically to live params
    eng2 = ServeEngine(cfg, state.params, max_seq=48, slots=2)
    eng2.submit([1, 2, 3, 4], max_new_tokens=5)
    assert eng2.run_until_drained()[0].out_tokens == done[0].out_tokens


def test_mini_dryrun_with_shardings(subproc):
    """The dry-run machinery end to end on a small mesh: sharded
    train_step + decode_step lower AND compile for a reduced arch."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.configs.base import ShapeSpec
from repro.core import mapping, shardhints
from repro.launch import dryrun as D
from repro.models import model
from repro.train import step as ts

cfg = reduced(get_config('granite-3-2b')).replace(vocab_size=256)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
shape = ShapeSpec('mini_train', 16, 8, 'train')
fn, args, in_sh, out_sh, donate, plan = D.build_cell(cfg, shape, mesh)
with mesh:
    c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=donate).lower(*args).compile()
assert c.memory_analysis().temp_size_in_bytes >= 0

shape_d = ShapeSpec('mini_decode', 64, 8, 'decode')
fn, args, in_sh, out_sh, donate, plan = D.build_cell(cfg, shape_d, mesh)
with mesh:
    c2 = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=donate).lower(*args).compile()
shardhints.set_policy(None)
ca = c.cost_analysis()
if isinstance(ca, (list, tuple)):  # older jax: one dict per program
    ca = ca[0]
print('OK', ca['flops'] > 0)
""")
    assert "OK True" in out


def test_elastic_restore_other_mesh(subproc):
    """Save on a 4-device data mesh, restore onto a 2x2 (data, model)
    mesh with resharding — the elastic-rescale path."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import save, restore
from repro.runtime.elastic import rescale_from_checkpoint

tree = {'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        'b': jnp.ones((8,), jnp.float32)}
d = tempfile.mkdtemp()
from repro.launch.mesh import make_mesh
mesh1 = make_mesh((4,), ('data',))
t1 = jax.device_put(tree, NamedSharding(mesh1, P()))
save(d, 3, t1)

mesh2 = make_mesh((2, 2), ('data', 'model'))
sh = {'w': NamedSharding(mesh2, P('data', 'model')),
      'b': NamedSharding(mesh2, P('model'))}
step, t2 = rescale_from_checkpoint(d, jax.eval_shape(lambda: tree), sh)
assert step == 3
np.testing.assert_array_equal(np.asarray(t2['w']), np.asarray(tree['w']))
assert t2['w'].sharding.spec == P('data', 'model')
print('OK')
""")
    assert "OK" in out
