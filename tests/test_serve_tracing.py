"""What the engine tells a profiler and an operator about a tick: stable
names for its two step programs, and the queue-wait counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model as M
from repro.serve import ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("granite-3-2b"))
    params = M.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    return cfg, params


def test_queue_wait_counts_each_fresh_admission(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_seq=64, slots=2)
    for i in range(5):
        eng.submit(list(range(1, 5 + i)), max_new_tokens=4)
    eng.run_until_drained()
    assert eng.stats["admissions"] == 5
    # three of the five waited for a slot, ticks long
    assert eng.stats["queue_wait_s"] > 0.0
    eng.reset_stats()
    assert eng.stats["admissions"] == 0 and eng.stats["queue_wait_s"] == 0


@pytest.mark.parametrize("policy", ["swap", "recompute"])
def test_restore_after_preemption_is_no_admission(setup, policy):
    """Two decoders that deadlock the pool mid-decode: the victim's restore
    re-enters a slot, but only the two submissions are admissions."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_seq=64, slots=2, block_size=8,
                      prefill_buckets=(16, 64), num_blocks=11,
                      preempt_policy=policy)
    for p in (list(range(1, 13)), list(range(5, 17))):
        eng.submit(p, max_new_tokens=40)
    eng.run_until_drained(max_ticks=400)
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["admissions"] == 2
    assert eng.stats["queue_wait_s"] >= 0.0


def test_step_programs_have_stable_names(setup):
    """The profiler's ``XLA Modules`` line names a program by its jitted
    function: ``jit_serve_decode_step`` and ``jit_serve_prefill_chunk``."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_seq=64, slots=2, block_size=8,
                      prefill_buckets=(16, 64))
    eng.submit(list(range(1, 13)), max_new_tokens=2)
    eng.run_until_drained()
    i32 = lambda *a: jnp.asarray(np.zeros(a, np.int32))        # noqa: E731
    dec = eng._decode.lower(
        eng.params, eng.state, i32(2), i32(2),
        jnp.asarray(eng.alloc.table), jnp.zeros((2,), bool)).as_text()
    assert "jit_serve_decode_step" in dec
    pre = eng._prefill_fn(16).lower(
        eng.params, eng.state, i32(1, 16), jnp.int32(12), jnp.int32(0),
        i32(2), jnp.int32(0)).as_text()
    assert "jit_serve_prefill_chunk" in pre
