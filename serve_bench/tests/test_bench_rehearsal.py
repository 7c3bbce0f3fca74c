"""The harness end to end at a tiny size on the CPU, with the look for a
chip skipped: a sound run is ``correct``, and each fault the timed path
can have makes ``correct`` false."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from serve_bench import harness, reference, spec, weights  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512)


def tiny_cell():
    with open(os.path.join(BENCH, "configs", "granite-3-2b.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY, block_size=8)
    mix = {"arrival": "poisson",
           "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                      "min": 8, "max": 40},
           "output": {"dist": "uniform", "min": 8, "max": 23}}
    # load that keeps the slots about as full as a real cell's, so that
    # every slot serves requests the check samples
    w = {"engine": {"slots": 4, "max_seq": 64}, "rate_per_s": 150.0,
         "lead_in_s": 0.3, "drain_s": 30, "preload": 4}
    # a real cell's limits
    with open(os.path.join(BENCH, "workloads",
                           "granite-3-2b.decode.json")) as f:
        limits = json.load(f)["check"]["limits"]
    w["check"] = {"requests": 8, "limits": limits}
    bench = spec.load_benchmark()
    e2e = [m for m in bench["end_to_end"]
           if spec.reports(m, "stablelm-1.6b.chat")]
    return spec.Cell("tiny", 1, cfg, mix, w, e2e, [])


def rehearse(cell, seconds=1.5, seed=2 ** 31 + 3):
    peaks = spec.load_peaks("TPU v5 lite")
    return harness.run(cell, seed, seconds, False, peaks, time.perf_counter(),
                       {"platform": "cpu", "kind": "cpu", "count": 1})


def test_sound_run_is_correct():
    out = rehearse(tiny_cell())
    assert out["correct"] is True
    assert out["attempted"] >= 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "itl_p50_ms", "itl_p99_ms",
                                   "ttft_p50_ms"}
    assert list(out)[-1] == "check"
    assert set(out["check"]) == {"mean_gap"}
    assert all(c["value"] <= c["limit"] for c in out["check"].values())


def test_closed_loop_checks_the_tokens_of_unfinished_requests():
    """A closed loop stops at the window's close with every client's
    request in flight, as the decode cell's requests outlive its run: the
    check reads the tokens that those unfinished requests served."""
    from serve_bench import check

    cell = tiny_cell()
    cell.mix["arrival"] = "closed"
    cell.workload = {"engine": {"slots": 4, "max_seq": 64}, "clients": 4,
                     "lead_in_s": 0.3, "drain_s": 0,
                     "check": cell.workload["check"]}
    seed = 2 ** 31 + 11
    r = harness.Run(cell, seed, 0.1, False, spec.load_peaks("TPU v5 lite"),
                    time.perf_counter())
    r.build()
    r.warm_up()
    r.drive()
    e2e, s = r.end_to_end(), r.summary
    assert e2e["output_tok_s"] == s["rate_tokens"] / s["rate_s"] > 0
    # the rate's window holds whole ticks: it opens at the end of the tick
    # running as the window opens and closes at the end of the last tick
    # begun before the close
    r0, r1 = s["rate_edges"]
    assert r.t_open <= r0 <= r.ticks[0].t0
    assert r.ticks[-1].t0 < r.t_close <= r.ticks[-1].t1 == r1
    served = r.served()
    r.release_engine()
    assert any(not x.done for x in served)
    unfinished = [x for x in served if not x.done]
    ok, compared, _, n = check.compare(cell, seed, served, r.params)
    assert ok is True and n > 0, compared
    assert check.served_gaps(cell.config, r.params, unfinished).size == sum(
        len(x.out) for x in unfinished)


def _alter_tokens(monkeypatch):
    from repro.serve.engine import ServeEngine
    real = ServeEngine._sample

    def sample(self, logits, req):
        return (real(self, logits, req) + 1) % self.cfg.vocab_size
    monkeypatch.setattr(ServeEngine, "_sample", sample)


def _state_unchanged(monkeypatch):
    from repro.models.runner import ModelRunner
    real = ModelRunner.decode

    def decode(self, params, state, *a, **kw):
        out = real(self, params, state, *a, **kw)
        return (out[0], state) + tuple(out[2:])
    monkeypatch.setattr(ModelRunner, "decode", decode)


def _half_batch(monkeypatch):
    from repro.models.runner import ModelRunner
    real = ModelRunner.decode

    def decode(self, params, state, *a, **kw):
        out = real(self, params, state, *a, **kw)
        lg = out[0]
        h = lg.shape[0] // 2
        return (jnp.concatenate([lg[:h], lg[:h]])[:lg.shape[0]],) + out[1:]
    monkeypatch.setattr(ModelRunner, "decode", decode)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_faults_are_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = rehearse(tiny_cell())
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["check"].values())


def test_control_reads_above_the_program():
    """The control (the reference computed in int8, W8A8, in the program's
    place) goes through the harness's comparison and comes out not
    correct, on every seed, where the bf16 program comes out correct:
    width 256, 4 layers, 4096 tokens, so there are near-ties.  The limit
    is this size's own, set between its readings as a cell's is (program
    0.00015-0.00027, control 0.0022-0.0048 over four seeds); the cells'
    limits are held against the control at their own sizes on the chip."""
    from serve_bench import check

    cell = tiny_cell()
    cell.config.update(hidden_size=256, intermediate_size=512,
                       num_hidden_layers=4, head_dim=64, vocab_size=4096)
    cell.mix["output"] = {"dist": "uniform", "min": 16, "max": 40}
    cell.workload["engine"]["max_seq"] = 96
    cell.workload["check"]["limits"] = {"mean_gap": 0.001}
    peaks = spec.load_peaks("TPU v5 lite")
    for seed in (1, 2, 3):
        r = harness.Run(cell, seed, 1.5, False, peaks, time.perf_counter())
        r.build()
        r.warm_up()
        r.drive()
        served = r.served()
        r.release_engine()
        ok, prog, _, n = check.compare(cell, seed, served, r.params)
        ok_c, ctrl, _, n_c = check.compare(cell, seed, served, r.params,
                                           control=True)
        assert n == n_c > 0
        assert ok is True and ok_c is False, (seed, prog, ctrl)
        assert all(ctrl[k][0] > prog[k][0] for k in prog)


def test_reference_matches_the_model_forward_in_float32():
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import model as M

    for tie in (False, True):
        cfg = dict(TINY, rope_theta=10000.0, norm_eps=1e-5,
                   tie_word_embeddings=tie)
        mc = get_config("granite-3-2b").replace(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
            vocab_size=512, head_dim=16, tie_embeddings=tie)
        p = weights.make(cfg, 2 ** 31 + 7, dtype=jnp.float32)
        shaped = M.init_params_shaped(mc)
        assert jax.tree.structure(shaped) == jax.tree.structure(p)
        assert [a.shape for a in jax.tree.leaves(shaped)] == \
            [a.shape for a in jax.tree.leaves(p)]
        toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
        tgt = np.roll(toks, -1)
        with ops.use_mode("ref"), jax.default_matmul_precision("highest"):
            lg = np.asarray(M.forward(mc, p, tokens=jnp.asarray(toks[None]))
                            [0][0])
        best, got, arg = reference.score(cfg, p, toks, tgt)
        np.testing.assert_allclose(best, lg.max(-1), atol=1e-4)
        np.testing.assert_allclose(
            got, np.take_along_axis(lg, tgt[:, None], -1)[:, 0], atol=1e-4)
        assert (arg == lg.argmax(-1)).all()


def test_weights_depend_on_the_seed_only():
    cfg = dict(TINY, tie_word_embeddings=False)
    a = weights.make(cfg, 2 ** 31 + 1)
    b = weights.make(cfg, 2 ** 31 + 1)
    c = weights.make(cfg, 2 ** 31 + 2)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(c)):
        assert x.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))
        assert not np.array_equal(np.asarray(x, np.float32),
                                  np.asarray(z, np.float32))


def test_command_refuses_a_machine_without_a_tpu(tmp_path):
    """Run as the benchmark command is run, from a checkout holding only
    BENCHMARK.json and the benchmark's directory: a non-zero exit and no
    result line."""
    import shutil

    shutil.copytree(BENCH, tmp_path / "serve_bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "serve_bench/run.py", "--workload",
         "stablelm-1.6b.chat", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
