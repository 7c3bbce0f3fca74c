"""Random weights from the seed, made by the benchmark in one jitted call.

The tree has the dense family's serving layout (stacked ``[L, ...]`` layer
leaves) so the engine can serve it as is; the plain reference reads the
same arrays.  Matrices are normal(0, fan_in^-1/2), the embedding
normal(0, hidden^-1/2), norm scales 1 + normal(0, 0.1), all made on the
device in the served dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict):
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return d, L, h, kvh, hd, cfg["intermediate_size"], cfg["vocab_size"]


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the tree, keyed like the serving layout."""
    d, L, h, kvh, hd, f, V = dims(cfg)
    lin = lambda i, o: {"w": (L, i, o)}                       # noqa: E731
    tree = {
        "embed": {"table": (V, d)},
        "final_norm": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (L, d)},
            "attn": {"wq": lin(d, h * hd), "wk": lin(d, kvh * hd),
                     "wv": lin(d, kvh * hd), "wo": lin(h * hd, d)},
            "ln2": {"scale": (L, d)},
            "ffn": {"gate": lin(d, f), "up": lin(d, f), "down": lin(f, d)},
        },
    }
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = {"w": (d, V)}
    return tree


def jax_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit key seed from any whole number, however large."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64, salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def make(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The whole tree on the default device, in one jitted call."""
    tree = shapes(cfg)
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0])
    names = [jax.tree_util.keystr(p, simple=True, separator="/")
             for p in paths]
    d = cfg["hidden_size"]

    def init(key):
        out = []
        for i, (name, shape) in enumerate(zip(names, leaves)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith("scale"):
                x = 1.0 + 0.1 * z
            elif name == "embed/table":
                x = z * d ** -0.5
            else:
                x = z * shape[-2] ** -0.5
            out.append(x.astype(dtype))
        return out

    flat = jax.jit(init)(jax.random.key(jax_seed(seed)))
    treedef = jax.tree_util.tree_structure(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree_util.tree_unflatten(treedef, flat)
