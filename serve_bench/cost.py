"""Operations and required bytes of the serving steps, from shapes alone.

This is the benchmark's yardstick.  Required bytes are what the algorithm
must move: every weight read once per step, the live KV read, the new KV
written, the tokens' logits written.  A copy of the page pool is waste, not
required work, and is not counted.  FLOPs count a multiply-add as two and
only the rows that carry a request: padding rows of a prefill bucket and
idle slots of a decode batch are not work.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass(frozen=True)
class Cost:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Cost") -> "Cost":
        return Cost(self.flops + o.flops, self.bytes + o.bytes)

    def scale(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k)

    def least_s(self, peaks: dict) -> float:
        """Least time on the chip: the larger of the two bounds."""
        return max(self.flops / peaks["bf16_flops"],
                   self.bytes / peaks["hbm_bytes_per_s"])

    def bound(self, peaks: dict) -> str:
        return ("compute" if self.flops / peaks["bf16_flops"]
                >= self.bytes / peaks["hbm_bytes_per_s"] else "memory")


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def layer_matmul_params(cfg) -> int:
    """Weights one token multiplies through in one layer."""
    d, _, h, kvh, hd, f, _ = _dims(cfg)
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f


def weight_bytes(cfg, itemsize: int = BF16) -> int:
    """All weights: layers (matrices and two norms), final norm, embedding,
    and the head unless it is tied to the embedding."""
    d, L, _, _, _, _, V = _dims(cfg)
    n = L * (layer_matmul_params(cfg) + 2 * d) + d + V * d
    if not cfg["tie_word_embeddings"]:
        n += d * V
    return n * itemsize


def step_weight_bytes(cfg, rows: int) -> int:
    """Weights one step must read: every layer, the final norm and the
    head in full; of an untied embedding only the ``rows`` looked up."""
    d = cfg["hidden_size"]
    if cfg["tie_word_embeddings"]:
        return weight_bytes(cfg)
    return weight_bytes(cfg) - (cfg["vocab_size"] - rows) * d * BF16


def kv_bytes_per_token(cfg, itemsize: int = BF16) -> int:
    """K and V of one token over all layers."""
    _, L, _, kvh, hd, _, _ = _dims(cfg)
    return 2 * L * kvh * hd * itemsize


def _attn_flops(cfg, q_rows_ctx: float) -> float:
    """QK^T and PV over ``q_rows_ctx`` (query row, key position) pairs, one
    layer."""
    _, _, h, _, hd, _, _ = _dims(cfg)
    return 4.0 * h * hd * q_rows_ctx


def paged_decode_call(cfg, rows: int, ctx_sum: int) -> Cost:
    """One layer's paged decode attention: ``rows`` queries attending to
    ``ctx_sum`` positions in all (each row's cached tokens plus its own)."""
    _, _, h, kvh, hd, _, _ = _dims(cfg)
    kv = 2 * kvh * hd * BF16 * ctx_sum
    qo = 2 * rows * h * hd * BF16
    return Cost(_attn_flops(cfg, ctx_sum), kv + qo)


def paged_prefill_call(cfg, n: int, offset: int) -> Cost:
    """One layer's paged prefill attention: ``n`` new rows at positions
    ``offset .. offset + n - 1``, each attending causally to the cached
    ``offset`` tokens and the chunk up to itself."""
    _, _, h, kvh, hd, _, _ = _dims(cfg)
    pairs = n * offset + n * (n + 1) / 2
    kv = 2 * kvh * hd * BF16 * (offset + n)
    qo = 2 * n * h * hd * BF16
    return Cost(_attn_flops(cfg, pairs), kv + qo)


def decode_step(cfg, rows: int, ctx_sum: int) -> Cost:
    """One batched decode step over ``rows`` requests (``ctx_sum`` as in
    :func:`paged_decode_call`): weights once (:func:`step_weight_bytes`),
    live KV read, one new KV row per request written, logits written."""
    d, L, _, _, _, _, V = _dims(cfg)
    mm = 2.0 * rows * (L * layer_matmul_params(cfg) + d * V)
    attn = paged_decode_call(cfg, rows, ctx_sum).scale(L)
    new_kv = rows * kv_bytes_per_token(cfg)
    logits = rows * V * BF16
    return Cost(mm + attn.flops,
                step_weight_bytes(cfg, rows) + attn.bytes + new_kv + logits)


def prefill_chunk(cfg, n: int, offset: int) -> Cost:
    """One prefill chunk of ``n`` tokens after ``offset`` cached ones:
    weights once, the prefix's KV read, the chunk's KV written, the last
    row's logits (the only row the step projects)."""
    d, L, _, _, _, _, V = _dims(cfg)
    mm = 2.0 * n * L * layer_matmul_params(cfg) + 2.0 * d * V
    attn = paged_prefill_call(cfg, n, offset).scale(L)
    new_kv = n * kv_bytes_per_token(cfg)
    return Cost(mm + attn.flops,
                step_weight_bytes(cfg, n) + attn.bytes + new_kv + V * BF16)
