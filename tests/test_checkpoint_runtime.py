"""Checkpoint atomicity/restore/reshard + fault-tolerant driver + straggler
detection + elastic rescale + data pipeline determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.configs import get_config, reduced
from repro.data import Prefetcher, SyntheticLM
from repro.runtime import SimulatedFailure, StragglerDetector, TrainDriver
from repro.runtime.elastic import validate_rescale
from repro.train import init_state, make_train_step


def _tree(rng):
    return {"a": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
            "nested": {"b": jnp.arange(6, dtype=jnp.int32)}}


def test_save_restore_roundtrip(tmp_path, rng):
    t = _tree(rng)
    save(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    back = restore(str(tmp_path), 7, jax.eval_shape(lambda: t))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                            np.asarray(b)),
                 t, back)


def test_no_tmp_dirs_left(tmp_path, rng):
    save(str(tmp_path), 1, _tree(rng))
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_manager_gc_and_async(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(rng))
    mgr.wait()
    steps = sorted(os.listdir(tmp_path))
    assert steps == ["step_0000000003", "step_0000000004"]


def test_restore_shape_mismatch_raises(tmp_path, rng):
    save(str(tmp_path), 0, _tree(rng))
    bad = {"a": jnp.zeros((3, 3)), "nested": {"b": jnp.zeros((6,), jnp.int32)}}
    with pytest.raises(ValueError):
        restore(str(tmp_path), 0, jax.eval_shape(lambda: bad))


def test_driver_failure_and_resume(tmp_path):
    """Inject a crash, restart the driver, verify bit-exact continuation."""
    cfg = reduced(get_config("stablelm-1.6b"))
    ds = SyntheticLM(cfg.vocab_size, 16, 4, seed=3)
    step = jax.jit(make_train_step(cfg, base_lr=1e-3))

    def mk(inject=None):
        return TrainDriver(
            train_step=step,
            init_state=lambda: init_state(cfg, jax.random.key(0)),
            dataset=ds, ckpt_dir=str(tmp_path), ckpt_every=3,
            put_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
            inject_failure_at=inject)

    with pytest.raises(SimulatedFailure):
        mk(inject=5).run(total_steps=10, log_fn=lambda *a: None)
    assert latest_step(str(tmp_path)) == 5
    out = mk().run(total_steps=10, log_fn=lambda *a: None)
    assert out["last_step"] == 9

    # bit-exactness: uninterrupted run == crashed+resumed run
    import shutil
    shutil.rmtree(tmp_path)
    out2 = mk().run(total_steps=10, log_fn=lambda *a: None)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out["state"].params,
        out2["state"].params)


def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(n_hosts=4, threshold=1.5, patience=2)
    flagged = set()
    for step in range(6):
        times = {0: 1.0, 1: 1.05, 2: 0.95, 3: 1.0 if step < 2 else 3.0}
        flagged = det.observe(times)
    assert flagged == {3}
    det.reset_host(3)
    assert det.strikes[3] == 0


def test_straggler_no_false_positive():
    det = StragglerDetector(n_hosts=4)
    for step in range(10):
        assert det.observe({h: 1.0 + 0.02 * h for h in range(4)}) == set()


def test_elastic_validate(subproc):
    out = subproc("""
import jax
from repro.launch.mesh import make_mesh
from repro.runtime.elastic import validate_rescale
old = make_mesh((4, 2), ('data', 'model'))
new = make_mesh((2, 4), ('data', 'model'))
assert validate_rescale(old, old, global_batch=256) == []
assert validate_rescale(old, old, global_batch=255) != []   # 255 % 4 != 0
assert validate_rescale(old, new, global_batch=256) != []   # TP changed
print('OK')
""")
    assert "OK" in out


def test_data_determinism_and_resume():
    ds = SyntheticLM(101, 8, 4, seed=9)
    b1 = ds.batch(5)
    b2 = ds.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    pf = Prefetcher(ds, start_step=3, depth=2)
    step, batch = next(pf)
    assert step == 3
    np.testing.assert_array_equal(batch["tokens"], ds.batch(3)["tokens"])
    pf.close()


def test_data_sharding():
    ds = SyntheticLM(101, 8, 8, seed=9)
    b = ds.batch(0)
    sh0 = ds.shard(b, 0, 4)
    sh3 = ds.shard(b, 3, 4)
    assert sh0["tokens"].shape == (2, 8)
    np.testing.assert_array_equal(sh3["tokens"], b["tokens"][6:])
