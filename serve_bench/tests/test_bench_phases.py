"""Device idle time put down to the engine's phases: the spans
``ServeEngine.step`` opens, read beside the harness's own."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from serve_bench import phases, trace  # noqa: E402

MS = 1_000_000
OP = "%fusion.1 = bf16[12,2048] fusion()"


def _synthetic():
    """One chip, two ticks.  Tick 1 (harness 0-15 ms, engine 1-14 ms)
    decodes: the program runs 3-10 ms, two sampling reads 11-11.5 and
    12-12.5 ms, a request retires inside the sampling loop.  Tick 2
    (harness 18-35, engine 19-34) runs a prefill chunk, 21-30 ms, whose
    first token is sampled inside it."""
    ops = [(3 * MS, 10 * MS, OP), (11 * MS, 11.5 * MS, OP),
           (12 * MS, 12.5 * MS, OP), (21 * MS, 30 * MS, OP),
           (31 * MS, 31.5 * MS, OP)]
    host = [(0, 15 * MS, "engine.step"), (15 * MS, 18 * MS, "generator.wait"),
            (18 * MS, 35 * MS, "engine.step")]
    program = [
        (1 * MS, 14 * MS, "serve.step", {"tick": 1}),
        (1 * MS, 2 * MS, "serve.admit", {"queued": 0}),
        (2 * MS, 3 * MS, "serve.decode", {"rows": 2}),
        (3 * MS, 13 * MS, "serve.sample", {"rows": 2}),
        (12.6 * MS, 12.9 * MS, "serve.retire", {"rid": 4}),
        (19 * MS, 34 * MS, "serve.step", {"tick": 2}),
        (20 * MS, 33 * MS, "serve.prefill",
         {"rid": 5, "bucket": 128, "tokens": 100, "offset": 0}),
        (30 * MS, 32 * MS, "serve.sample", {"rows": 1}),
    ]
    return {"devices": [("/device:TPU:0", ops, [])], "host": host,
            "program": program}


def test_idle_goes_to_the_innermost_span():
    r = phases.reduce_loaded(_synthetic())
    assert r["program_ticks"] == 2
    assert r["idle_by_span"] == pytest.approx({
        "serve.admit": 0.001,
        "serve.decode": 0.001,
        # 10-11, 11.5-12, 12.5-12.6, 12.9-13; 30-31, 31.5-32
        "serve.sample": 0.0017 + 0.0015,
        "serve.retire": 0.0003,
        # tick 1: 13-14; tick 2: 19-20, 33-34
        "serve.step": 0.003,
        # 20-21, 32-33
        "serve.prefill": 0.002,
    })
    # engine.step under no serve.* span: 0-1, 14-15, 18-19, 34-35 ms
    assert r["uncovered_s"] == pytest.approx(0.004)
    assert r["step_idle_s"] == pytest.approx(
        sum(r["idle_by_span"].values()) + r["uncovered_s"])
    # 17.5 ms busy; the generator's wait is idle, but not inside a step
    assert r["step_idle_s"] == pytest.approx(0.035 - 0.0175 - 0.003)
    names = dict((round(d * 1e4), n) for n, d in r["idle_gaps"])
    assert names == {85: "generator.wait", 35: "serve.step",
                     30: "serve.admit", 10: "serve.sample",
                     5: "serve.sample"}


def test_the_three_groups_sum_to_the_idle_inside_serve_step():
    r = phases.reduce_loaded(_synthetic())
    ms = phases.per_tick_ms(r)
    assert set(ms) == set(phases.GROUPS)
    assert sum(ms.values()) * r["program_ticks"] / 1e3 == pytest.approx(
        r["step_idle_s"] - r["uncovered_s"])
    assert ms["sample_idle_ms"] == pytest.approx(3.2 / 2)
    line = phases.summary(r)
    assert "serve.sample 3.200 ms" in line and "(27.59 %)" in line


def test_without_program_spans_nothing_is_read():
    """A program that opens no spans (the benchmark laid over an older
    tree) reads no phase and names the gaps as ``trace.py`` does."""
    data = _synthetic()
    del data["program"]
    r = phases.reduce_loaded(data)
    assert r["idle_by_span"] == {} and r["program_ticks"] == 0
    assert phases.per_tick_ms(r) == {}
    assert r["idle_gaps"] == trace.reduce_loaded(data)["breakdown"][
        "idle_gaps"]


def test_segments_nest_spans_that_open_together():
    segs = phases.segments([(0, 10, "engine.step"), (0, 10, "serve.step"),
                            (0, 4, "serve.admit"), (6, 6, "empty")])
    assert segs == [(0, 4, "serve.admit", True),
                    (4, 10, "serve.step", True)]


def test_the_old_recorded_window_reads_no_phase():
    d = phases.load(os.path.join(HERE, "data", "decode_window"), chips=1)
    assert d["program"] == []
    r = phases.reduce_loaded(d)
    assert phases.per_tick_ms(r) == {}
    assert r["idle_gaps"] == trace.reduce_loaded(d)["breakdown"]["idle_gaps"]


def test_engine_spans_reach_the_trace(tmp_path):
    """A tiny engine stepped under the profiler on the CPU: every span its
    ticks reach is on the host plane, nested as the tick runs them."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.models import model as M
    from repro.serve import ServeEngine
    from repro.serve.engine import SPANS

    cfg = reduced(get_config("granite-3-2b"))
    params = M.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    eng = ServeEngine(cfg, params, max_seq=64, slots=2)
    rid = int(eng.submit([1, 2, 3, 4, 5], max_new_tokens=3))
    eng.step()                       # compile outside the trace
    eng.submit([6, 7, 8], max_new_tokens=2)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("engine.step"):
            for _ in range(3):
                eng.step()
    d = phases.load(str(tmp_path), chips=1)
    spans = d["program"]
    assert {n for _, _, n, _ in spans} == set(SPANS)
    steps = [(s, e) for s, e, n, _ in spans if n == "serve.step"]
    assert len(steps) == 3
    for s, e, n, _ in spans:
        if n in ("serve.sample", "serve.decode"):
            assert any(a <= s and e <= b for a, b in steps)
    pre = [(s, e, m) for s, e, n, m in spans if n == "serve.prefill"]
    assert len(pre) == 1 and pre[0][2]["rid"] == rid + 1
    assert set(pre[0][2]) == {"rid", "bucket", "tokens", "offset"}
    # the first token of the chunk is sampled inside it
    assert any(pre[0][0] <= s and e <= pre[0][1]
               for s, e, n, m in spans if n == "serve.sample")
    retired = {m["rid"] for _, _, n, m in spans if n == "serve.retire"}
    assert retired == {rid, rid + 1}


def test_a_phase_window_recorded_on_the_chip():
    """A 1.1 s window of stablelm-1.6b decode steps (chat cell, 6 of 12
    slots live) recorded on one TPU v5 lite with the engine's spans and
    names: three ticks, each a ``jit_serve_decode_step`` program whose
    ``paged_decode_attention`` kernel runs once per layer and is still
    found by its signature, then one slice, squeeze and argmax program
    per slot sampled."""
    d = phases.load(os.path.join(HERE, "data", "phase_window"), chips=1)
    _, ops, mods = d["devices"][0]
    names = [x.split("(")[0] for _, _, x in mods]
    assert names.count("jit_serve_decode_step") == 3
    assert names.count("jit__argmax") == 3 * 6
    kernels = [x for _, _, x in ops if trace.kernel_of(x) == "paged_decode"]
    assert len(kernels) == 3 * 24
    assert all(x.startswith("%paged_decode_attention.") for x in kernels)
    t = trace.reduce_loaded(d)
    assert t["window_s"] == pytest.approx(1.126689041)
    assert t["kernels_s"] == pytest.approx({"paged_decode": 0.780633047})
    r = phases.reduce_loaded(d)
    assert r["program_ticks"] == 3
    assert r["idle_by_span"] == pytest.approx({
        "serve.admit": 0.0002086, "serve.decode": 0.00272751,
        "serve.sample": 0.043309886, "serve.step": 0.000521971})
    # the spans cover all but 0.1 % of the idle time inside engine.step
    assert r["uncovered_s"] < 1e-3 * r["step_idle_s"]
    # nearly all of the window's idle time is inside a step
    idle = t["window_s"] - t["busy_s"]
    assert 0.99 * idle < r["step_idle_s"] < idle
    assert {n for n, _ in r["idle_gaps"]} == {"serve.sample"}
