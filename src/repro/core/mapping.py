"""Mapping: the paper's §3.3 output-split vs input-split analysis, realized
as TP/FSDP PartitionSpec selection with a bytes-moved cost model.

Paper findings reproduced here:
  * DRAM-PIM prefers **output-split** (no inter-bank reduction, but the
    input vector must be broadcast and per-bank FC shapes become extremely
    imbalanced — long inputs, short outputs);
  * with an efficient inter-bank reduction (CompAir-NoC), **input-split**
    often wins because balanced shapes minimize data movement for a fixed
    MAC budget (mean-value inequality);
  * the classic Megatron FFN pairing (up/gate output-split + down
    input-split, one reduction per block) is exactly this theorem applied
    twice, and is our default 'compair' mode.

``choose_fc_split`` is the quantitative rule; ``sharding_plan`` applies it
across a model's parameter tree (with divisibility fallbacks so reduced
smoke configs shard trivially), plus batch/cache/state specs.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.planner import HWParams, TPU_V5E


# ---------------------------------------------------------------------------
# §3.3 cost model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SplitChoice:
    split: str            # 'output' | 'input'
    comm_bytes: float     # per-device collective payload
    alt_bytes: float      # the rejected option's payload
    collective: str       # which collective it implies


def choose_fc_split(m: int, k: int, n: int, tp: int,
                    dtype_bytes: int = 2, input_sharded: bool = False,
                    hw: HWParams = TPU_V5E) -> SplitChoice:
    """Cost of sharding an [m,k]@[k,n] FC over ``tp`` devices.

    output-split: W columns sharded; requires the activation replicated
        (all-gather m*k if it arrives reduce-scattered), output stays local.
    input-split:  W rows sharded; activation arrives k-sharded for free,
        partial [m,n] outputs need an all-reduce (2x ring payload).
    """
    frac = (tp - 1) / tp
    ag = m * k * dtype_bytes * frac if input_sharded else 0.0
    out_bytes = ag
    in_bytes = 2.0 * m * n * dtype_bytes * frac
    if in_bytes < out_bytes:
        return SplitChoice("input", in_bytes, out_bytes, "all-reduce")
    return SplitChoice("output", out_bytes, in_bytes, "all-gather")


def megatron_block_bytes(m: int, d: int, ff: int, tp: int,
                         dtype_bytes: int = 2) -> Dict[str, float]:
    """Fig. 8-style comparison: pure output-split vs the mixed mapping for
    a SwiGLU FFN block (per device, bytes moved)."""
    frac = (tp - 1) / tp
    # pure output-split: all three FCs column-sharded; activations must be
    # re-gathered between up/gate and down (down's input is ff-wide)
    pure_output = (m * d * dtype_bytes * frac          # gather x for up/gate
                   + m * ff * dtype_bytes * frac)      # gather h for down
    # mixed (paper/Megatron): up/gate output-split, down input-split:
    # one all-reduce of the [m, d] output
    mixed = 2.0 * m * d * dtype_bytes * frac
    return {"pure_output_split": pure_output, "mixed_input_split": mixed,
            "speedup": pure_output / max(mixed, 1.0)}


# ---------------------------------------------------------------------------
# sharding plan
# ---------------------------------------------------------------------------

@dataclass
class Plan:
    """All PartitionSpecs for one (arch × shape × mesh) cell."""
    params: dict                      # pytree matching params
    batch_spec: P                     # for [B, S] token arrays
    embeds_spec: P                    # for [B, S, d] stub embeddings
    state_specs: Optional[dict]       # decode cache/state pytree specs
    dp_axes: Tuple[str, ...]
    tp_axis: str
    fsdp_axis: Optional[str] = None
    notes: List[str] = field(default_factory=list)


def _divides(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


def _first_fit(shape, candidates, axis_sizes, taken=()):
    """candidates: list of (dim_index, axis_name). Returns a P(...) that
    assigns the first divisible candidate per dim (axes used once)."""
    spec = [None] * len(shape)
    used = set(taken)
    for dim, axis in candidates:
        if axis in used or dim >= len(shape) or spec[dim] is not None:
            continue
        if _divides(shape[dim], axis_sizes.get(axis, 0)):
            spec[dim] = axis
            used.add(axis)
    return P(*spec)


_PARAM_RULES: Sequence[Tuple[str, str]] = (
    # (path regex, rule name);  first match wins
    (r"embed.*table", "vocab_row"),
    (r"lm_head.*w$", "col"),
    (r"lm_head.*b$", "col_bias"),
    (r"(wq|wk|wv|gate|up)\.w$", "col"),
    (r"(wq|wk|wv|gate|up)\.b$", "col_bias"),
    (r"(wo|down|out_proj)\.w$", "row"),
    (r"(wo|down|out_proj)\.b$", "rep"),
    (r"moe.*router", "rep"),
    (r"moe.*w_(gate|up)$", "expert_col"),
    (r"moe.*w_down$", "expert_row"),
    (r"in_proj\.w$", "col"),
    (r"in_proj\.b$", "col_bias"),
    (r"conv_w$", "conv"),
    (r"(A_log|D|dt_bias)$", "rep"),
    (r"tm\.(wr|wk|wv|wg)\.w$", "col"),
    (r"tm\.wo\.w$", "row"),
    (r"cm\.wk\.w$", "col"),
    (r"cm\.wv\.w$", "row"),
    (r"cm\.wr\.w$", "col"),
    (r"(w_a|w_b|w0|mix|u)$", "rep"),
    (r"(ln|ln1|ln2|norm|final_norm).*scale$", "rep"),
)


def _param_spec(rule: str, shape, ax, fsdp_axis):
    """Trailing-2D semantic rules; leading stack dims get the FSDP axis if
    divisible (ZeRO-style sharding of the stacked-layer dim is avoided —
    scan slices it — so FSDP lands on a feature dim instead)."""
    nd = len(shape)
    if rule == "rep":
        return P()
    if rule in ("col", "vocab_row", "row", "expert_col", "expert_row"):
        if rule == "vocab_row":
            cands = [(nd - 2, "model"), (nd - 1, fsdp_axis)]
        elif rule == "col":
            cands = [(nd - 1, "model"), (nd - 2, fsdp_axis)]
        elif rule == "row":
            cands = [(nd - 2, "model"), (nd - 1, fsdp_axis)]
        elif rule == "expert_col":   # [*, E, din, dout]
            cands = [(nd - 3, "model"), (nd - 1, fsdp_axis)]
        else:                        # expert_row [*, E, din, dout]
            cands = [(nd - 3, "model"), (nd - 2, fsdp_axis)]
        return _first_fit(shape, [c for c in cands if c[1]], _AXIS_SIZES)
    if rule == "col_bias":
        return _first_fit(shape, [(nd - 1, "model")], _AXIS_SIZES)
    if rule == "conv":               # [*, W, channels]
        return _first_fit(shape, [(nd - 1, "model")], _AXIS_SIZES)
    raise ValueError(rule)


_AXIS_SIZES: Dict[str, int] = {}


def sharding_plan(cfg: ModelConfig, mesh, shape: ShapeSpec, *,
                  params_shape=None, state_shape=None,
                  fsdp: Optional[bool] = None,
                  decode_seq_shard: bool = False) -> Plan:
    """Build all PartitionSpecs for a cell.

    mesh: jax Mesh with axes ('data','model') or ('pod','data','model').
    fsdp: shard params over the data axis too (default: only for train).
    """
    global _AXIS_SIZES
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    _AXIS_SIZES = axis_sizes
    dp_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    if fsdp is None:
        fsdp = shape.kind == "train"
    fsdp_axis = "data" if (fsdp and "data" in axis_sizes) else None
    notes: List[str] = []

    # ---- params ----
    param_specs = None
    if params_shape is not None:
        def assign(path, leaf):
            pstr = jax.tree_util.keystr(path, simple=True, separator=".")
            for rx, rule in _PARAM_RULES:
                if re.search(rx, pstr):
                    return _param_spec(rule, leaf.shape, axis_sizes, fsdp_axis)
            notes.append(f"unmatched param path replicated: {pstr}")
            return P()

        param_specs = jax.tree_util.tree_map_with_path(assign, params_shape)

    # ---- batch ----
    b = shape.global_batch
    dp_for_batch = [a for a in dp_axes if a in axis_sizes]
    # use the largest prefix of dp axes that divides the batch
    chosen: List[str] = []
    prod = 1
    for a in dp_for_batch:
        if b % (prod * axis_sizes[a]) == 0:
            chosen.append(a)
            prod *= axis_sizes[a]
    if not chosen:
        notes.append(f"batch={b} unsharded (does not divide dp axes)")
    batch_spec = P(tuple(chosen) if chosen else None, None)
    embeds_spec = P(tuple(chosen) if chosen else None, None, None)

    # ---- decode cache / state ----
    state_specs = None
    if state_shape is not None:
        seq_shard = shape.name == "long_500k"

        def cache_spec(path, leaf):
            pstr = jax.tree_util.keystr(path, simple=True, separator=".")
            shp = leaf.shape
            nd = len(shp)
            if re.search(r"attn\.(k|v)$", pstr):
                # [slots, B, S, KvH, hd]
                spec = [None] * nd
                if chosen and _divides(shp[nd - 4], prod):
                    spec[nd - 4] = tuple(chosen)
                if decode_seq_shard and _divides(shp[nd - 3],
                                                 axis_sizes.get("model", 0)):
                    # §Perf iteration 3: sequence-sharded cache over the TP
                    # axis; flash-decoding partials combined by the NoC
                    # tree softmax (paper Fig. 10).
                    spec[nd - 3] = "model"
                    notes.append("KV cache sequence-sharded over 'model'; "
                                 "NoC tree-softmax combine")
                    return P(*spec)
                if seq_shard and _divides(shp[nd - 3], axis_sizes.get("data", 0)):
                    spec[nd - 3] = "data"
                    notes.append("KV cache sequence-sharded over 'data' "
                                 "(long_500k): partials combined via NoC tree softmax")
                if _divides(shp[nd - 2], axis_sizes.get("model", 0)):
                    spec[nd - 2] = "model"
                elif _divides(shp[nd - 1], axis_sizes.get("model", 0)):
                    spec[nd - 1] = "model"   # paper input-split: shard head_dim
                    notes.append("KV heads < TP: head_dim (contraction) sharded "
                                 "= paper input-split mapping")
                return P(*spec)
            # generic states: [L(, K), B, ...trailing feature dims]
            spec = [None] * nd
            # find the batch dim: first dim equal to global batch
            for i, s in enumerate(shp):
                if s == b and chosen and _divides(s, prod):
                    spec[i] = tuple(chosen)
                    break
            # shard the largest trailing dim on model if divisible
            best = None
            for i in range(nd - 1, max(nd - 3, 0), -1):
                if spec[i] is None and _divides(shp[i], axis_sizes.get("model", 0)):
                    if best is None or shp[i] > shp[best]:
                        best = i
            if best is not None:
                spec[best] = "model"
            return P(*spec)

        state_specs = jax.tree_util.tree_map_with_path(cache_spec, state_shape)

    return Plan(params=param_specs, batch_spec=batch_spec,
                embeds_spec=embeds_spec, state_specs=state_specs,
                dp_axes=dp_axes, tp_axis="model", fsdp_axis=fsdp_axis,
                notes=notes)


def named_shardings(plan_tree, mesh):
    return jax.tree.map(
        lambda spec: jax.sharding.NamedSharding(mesh, spec), plan_tree,
        is_leaf=lambda x: isinstance(x, P))
