"""Plain reference of the dense family, in float32, from the layer equations.

Per layer (pre-norm residual):

    h = rmsnorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv
    q, k = rope(q), rope(k)          # interleaved pairs (x0, x1), (x2, x3) ...
    a = softmax(q k^T / sqrt(head_dim) + causal) v   # KV head j serves the
                                                      # query heads j*G .. j*G+G-1
    x = x + a Wo
    h = rmsnorm(x) * ln2;  x = x + (silu(h Wg) * h Wu) Wd

then ``logits = rmsnorm(x) * final_norm`` times ``lm_head`` (or the
embedding table, tied).  It imports nothing of the program and reads only
the configuration file's numbers and the benchmark's weights.  Matrix
products run at ``Precision.HIGHEST``; attention and the logits are
computed in blocks of query rows so that long sequences fit the chip.

``precision="int8"`` is the control: the same equations with every weight
matrix quantized per output channel and every activation entering a matrix
product quantized per row, both symmetric int8 (W8A8), as a later change to
int8 serving would compute them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
ROWS = 512            # query rows per attention / logits block


def _q8(x, axis):
    """Symmetric int8 quantize-dequantize along ``axis`` (the reduced one)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, int8: bool):
    """x [..., k] @ w [k, n] in float32."""
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, H, D]; rotate the pairs (x[2i], x[2i+1]) by pos * theta^(-i/(D/2))."""
    s, h, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, sn = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * c - xo * sn, xe * sn + xo * c], -1).reshape(s, h, d)


def _attention(q, k, v):
    """Causal attention of q [S, H, D] over k, v [S, KvH, D], in row blocks."""
    s, h, d = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    rows = min(ROWS, s)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(d)
        qpos = i * rows + jnp.arange(rows)
        sc = jnp.where(jnp.arange(s)[None, None] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    return lax.map(block, jnp.arange(s // rows)).reshape(s, h, d)


def hidden(cfg: dict, params, tokens, int8: bool = False):
    """Final normed hidden states [S, d] of one sequence (S a multiple of
    ``ROWS`` or smaller than it)."""
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    table = f32(params["embed"]["table"])
    if int8:
        table = _q8(table, -1)
    x = table[tokens]
    s = x.shape[0]
    pos = jnp.arange(s)

    def layer(x, lp):
        lp = jax.tree.map(f32, lp)
        a = lp["attn"]
        y = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _rope(_mm(y, a["wq"]["w"], int8).reshape(s, h, hd), pos, theta)
        k = _rope(_mm(y, a["wk"]["w"], int8).reshape(s, kvh, hd), pos, theta)
        v = _mm(y, a["wv"]["w"], int8).reshape(s, kvh, hd)
        x = x + _mm(_attention(q, k, v).reshape(s, h * hd), a["wo"]["w"], int8)
        f = lp["ffn"]
        y = _rmsnorm(x, lp["ln2"]["scale"], eps)
        gate = _mm(y, f["gate"]["w"], int8)
        up = _mm(y, f["up"]["w"], int8)
        return x + _mm(jax.nn.silu(gate) * up, f["down"]["w"], int8), None

    x, _ = lax.scan(layer, x, params["layers"])
    return _rmsnorm(x, f32(params["final_norm"]["scale"]), eps)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _score(cfg_items, params, tokens, targets, int8):
    """Per position: the reference's best logit, the reference's logit of
    ``targets``, and the token that this precision puts first."""
    cfg = dict(cfg_items)
    x = hidden(cfg, params, tokens, int8)
    s = x.shape[0]
    rows = min(ROWS, s)
    if cfg["tie_word_embeddings"]:
        head = params["embed"]["table"].astype(jnp.float32).T
    else:
        head = params["lm_head"]["w"].astype(jnp.float32)
    headq = _q8(head, 0) if int8 else head

    def block(i):
        xb = lax.dynamic_slice_in_dim(x, i * rows, rows)
        lg = _mm(xb, headq, int8) if int8 else jnp.einsum(
            "qd,dv->qv", xb, head, precision=HI)
        tb = lax.dynamic_slice_in_dim(targets, i * rows, rows)
        return (lg.max(-1), jnp.take_along_axis(lg, tb[:, None], -1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    best, got, arg = lax.map(block, jnp.arange(s // rows))
    return best.reshape(s), got.reshape(s), arg.reshape(s)


def score(cfg: dict, params, tokens, targets, int8: bool = False):
    """tokens, targets int32 [n]; padded up to a power of two below
    ``ROWS`` and to a multiple of it above (causal: padding after the
    sequence changes nothing before it).
    Returns numpy (best, got, arg) over the first S positions."""
    n = len(tokens)
    s = 1 << (n - 1).bit_length() if n <= ROWS else -(-n // ROWS) * ROWS
    tok = np.zeros(s, np.int32)
    tgt = np.zeros(s, np.int32)
    tok[:n], tgt[:n] = tokens, targets
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "norm_eps", "tie_word_embeddings")
    items = tuple((k, cfg[k]) for k in keys)
    best, got, arg = _score(items, params, jnp.asarray(tok), jnp.asarray(tgt),
                            bool(int8))
    return (np.asarray(best)[:n], np.asarray(got)[:n], np.asarray(arg)[:n])
