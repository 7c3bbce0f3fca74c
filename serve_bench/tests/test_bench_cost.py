"""cost.py against sizes and operation counts worked by hand."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from serve_bench import cost  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_weight_and_kv_bytes():
    s, g = _cfg("stablelm-1.6b"), _cfg("granite-3-2b")
    # stablelm: 24 x (4 x 2048^2 + 3 x 2048 x 5632 + 2 x 2048) + 2048
    #           + 2 x 100352 x 2048 (untied) parameters, 2 bytes each
    assert cost.weight_bytes(s) == 2 * (
        24 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 2 * 2048) + 2048
        + 2 * 100352 * 2048) == 3_288_535_040
    # granite: q and o 2048^2, k and v 2048 x 512, tied embedding
    assert cost.weight_bytes(g) == 2 * (
        40 * (2 * 2048 ** 2 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048)
        + 2048 + 49155 * 2048) == 5_067_063_296
    assert cost.kv_bytes_per_token(s) == 24 * 32 * 64 * 2 * 2 == 196_608
    assert cost.kv_bytes_per_token(g) == 40 * 8 * 64 * 2 * 2 == 81_920


def test_decode_step_by_hand():
    g = _cfg("granite-3-2b")
    rows, ctx = 3, 100 + 200 + 300
    c = cost.decode_step(g, rows, ctx)
    per_layer = 2 * 2048 ** 2 + 2 * 2048 * 512 + 3 * 2048 * 8192
    mm = 2 * rows * (40 * per_layer + 2048 * 49155)
    attn = 40 * 4 * 32 * 64 * ctx                  # QK^T and PV
    assert c.flops == mm + attn
    weights = cost.weight_bytes(g)                 # tied: table read whole
    kv_read = 40 * 2 * 8 * 64 * 2 * ctx
    qo = 40 * 2 * rows * 32 * 64 * 2
    assert c.bytes == (weights + kv_read + qo + rows * 81_920
                       + rows * 49155 * 2)
    # decode at this size is bound by memory, far below the compute peak
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert c.bound(peaks) == "memory"
    assert c.least_s(peaks) == c.bytes / 819e9


def test_untied_embedding_reads_only_looked_up_rows():
    s = _cfg("stablelm-1.6b")
    assert (cost.step_weight_bytes(s, 4)
            == cost.weight_bytes(s) - (100352 - 4) * 2048 * 2)


def test_prefill_chunk_by_hand():
    s = _cfg("stablelm-1.6b")
    n, off = 512, 1024
    c = cost.prefill_chunk(s, n, off)
    per_layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    pairs = n * off + n * (n + 1) // 2             # causal (row, key) pairs
    assert c.flops == (2 * n * 24 * per_layer + 2 * 2048 * 100352
                       + 24 * 4 * 32 * 64 * pairs)
    k = cost.paged_prefill_call(s, n, off)
    assert k.flops == 4 * 32 * 64 * pairs
    assert k.bytes == 2 * 32 * 64 * 2 * (off + n) + 2 * n * 32 * 64 * 2
    # a 512-row chunk of a 1.6B model is bound by compute
    assert c.bound({"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}) \
        == "compute"
