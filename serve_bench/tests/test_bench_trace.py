"""The trace reduction: busy and idle time, programs classified by the
kernel inside them, kernel time, and idle gaps named by the host span."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from serve_bench import trace  # noqa: E402

MS = 1_000_000
# op texts as the TPU profiler names them (HLO text, layouts shortened)
DECODE_KERNEL = (
    "%closed_call.61 = (bf16[12,32,1,64]{3,2,1,0:T(2,128)(2,1)S(1)}, "
    "f32[12,32,1,1]{3,2,1,0:T(1,128)}, f32[12,32,1,1]{3,2,1,0:T(1,128)}) "
    "custom-call(s32[12,80]{1,0:T(8,128)} %get-tuple-element.619, "
    "s32[12]{0:T(128)S(1)} %copy-done.4), "
    'custom_call_target="tpu_custom_call"')
PREFILL_KERNEL = (
    "%closed_call.55 = (bf16[32,1280,64]{2,1,0:T(8,128)(2,1)S(1)}, "
    "f32[32,1280,1]{2,1,0:T(8,128)}, f32[32,1280,1]{2,1,0:T(8,128)}) "
    "custom-call(s32[64]{0:T(128)} %get-tuple-element.742, "
    "s32[2]{0:T(128)S(1)} %copy-done.12), "
    'custom_call_target="tpu_custom_call"')
RMSNORM = (
    "%closed_call.58 = bf16[12,2048]{1,0:T(8,128)(2,1)S(1)} custom-call("
    "bf16[12,2048]{1,0:T(8,128)(2,1)} %bitcast.141, bf16[2048]{0} %d), "
    'custom_call_target="tpu_custom_call"')


def _synthetic():
    """One chip; a decode program at 0-10 ms (its kernel at 2-4 ms), a
    prefill program at 20-40 ms (kernel at 25-30 ms), host spans around
    both and a generator wait between them."""
    ops = [(0, 2 * MS, RMSNORM), (2 * MS, 4 * MS, DECODE_KERNEL),
           (4 * MS, 10 * MS, "%fusion.2 = bf16[12,2048] fusion()"),
           (20 * MS, 25 * MS, RMSNORM),
           (25 * MS, 30 * MS, PREFILL_KERNEL),
           (30 * MS, 40 * MS, "%fusion.3 = bf16[1,1280,2048] fusion()"),
           # a while loop holding the prefill ops: counted once
           (20 * MS, 40 * MS, "%while.5 = (s32[]) while()")]
    mods = [(0, 10 * MS, "jit_f(1)"), (20 * MS, 40 * MS, "jit_f(2)")]
    host = [(0, 11 * MS, "engine.step"), (11 * MS, 19 * MS, "generator.wait"),
            (19 * MS, 41 * MS, "engine.step"), (41 * MS, 52 * MS, "submit")]
    return {"devices": [("/device:TPU:0", ops, mods)], "host": host}


def test_reduce_synthetic():
    r = trace.reduce_loaded(_synthetic())
    assert r["window_s"] == pytest.approx(0.052)
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["modules_s"] == pytest.approx({"decode": 0.010,
                                            "prefill": 0.020})
    assert r["kernels_s"] == pytest.approx({"paged_decode": 0.002,
                                            "paged_prefill": 0.005})
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "submit" and gaps[0][1] == pytest.approx(0.012)
    assert gaps[1][0] == "generator.wait"
    assert gaps[1][1] == pytest.approx(0.010)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["prefill: %fusion.3 = bf16[1,1280,2048] fusion()"] == \
        pytest.approx(0.010)
    assert ops["prefill: paged_prefill"] == pytest.approx(0.005)
    assert ops["decode: paged_decode"] == pytest.approx(0.002)
    assert ops["prefill: %while.5 = (s32[]) while()"] == pytest.approx(0.0)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_kernel_signatures():
    assert trace.kernel_of(DECODE_KERNEL) == "paged_decode"
    assert trace.kernel_of(PREFILL_KERNEL) == "paged_prefill"
    assert trace.kernel_of(RMSNORM) is None
    assert trace.kernel_of("%copy.26 = bf16[24,32,961,16,64] copy()") is None


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_loaded({"devices": [], "host": []})


def test_reduce_a_trace_recorded_on_the_chip():
    """A 1.2 s window of stablelm-1.6b decode steps (12 slots x 1280
    positions) recorded on one TPU v5 lite: three decode steps, each a
    program whose paged decode kernel runs once per layer."""
    d = trace.load(os.path.join(HERE, "data", "decode_window"), chips=1)
    assert len(d["devices"]) == 1
    assert [n for _, _, n in d["host"]].count("engine.step") == 3
    r = trace.reduce_loaded(d)
    assert r["window_s"] == pytest.approx(1.19008748)
    assert r["busy_s"] == pytest.approx(1.10424327)
    assert r["modules_s"]["decode"] == pytest.approx(1.103885247)
    assert "prefill" not in r["modules_s"]
    assert r["kernels_s"] == pytest.approx({"paged_decode": 0.804963369})
    n_kernels = sum(1 for _, _, x in d["devices"][0][1]
                    if trace.kernel_of(x) == "paged_decode")
    assert n_kernels == 3 * 24
    ops = r["breakdown"]["device_ops"]
    assert ops[0][0] == "decode: paged_decode"
    assert sum(v for _, v in ops) <= r["busy_s"]
    assert all(name == "engine.step" or name == "submit"
               for name, _ in r["breakdown"]["idle_gaps"])
