"""CompAir-NoC on ICI: in-transit collective computation.

The paper embeds a Curry ALU in every NoC router so that reductions /
broadcasts *compute while data moves* (Fig. 10: the Softmax sum rides the
reduce tree; Fig. 14A: NoC_Reduce lowers to a binary tree over banks).

TPU adaptation: the mesh axis plays the bank-grid role and
``lax.ppermute`` hops play router-to-router flits.  Each hop is followed
by the pending combine op on the receiving shard — compute-during-
movement with log2(n) depth and every node busy, the same schedule as the
paper's 2^N-1-node reduction tree.

Everything here must run inside ``shard_map`` (it uses collectives with
an ``axis_name``).  ``centralized_*`` are the paper's *baselines* (the
CXL-controller NLU round trip) used for HLO/latency comparisons.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ref
from repro.pimsim import params as _pimparams

Combiner = Callable  # (tree, tree) -> tree


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _max(a, b):
    return jax.tree.map(jnp.maximum, a, b)


COMBINERS = {"add": _add, "max": _max}


def _is_pow2(n: int) -> bool:
    return n & (n - 1) == 0


def butterfly_all_reduce(tree, axis_name: str, combiner="add"):
    """Hypercube (butterfly) all-reduce: log2(n) ppermute hops, the combine
    op applied at every hop — the NoC reduce+broadcast tree collapsed into
    one recursive-doubling schedule.  Falls back to psum for non-pow2 axes
    when the combiner is 'add'."""
    comb = COMBINERS.get(combiner, combiner)
    n = lax.axis_size(axis_name)
    if not _is_pow2(n):
        if combiner == "add":
            return jax.tree.map(lambda a: lax.psum(a, axis_name), tree)
        if combiner == "max":
            return jax.tree.map(lambda a: lax.pmax(a, axis_name), tree)
        raise ValueError("non-pow2 axis needs builtin combiner")
    k = 1
    while k < n:
        perm = [(i, i ^ k) for i in range(n)]
        other = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), tree)
        tree = comb(tree, other)
        k *= 2
    return tree


def tree_reduce(tree, axis_name: str, combiner="add", root: int = 0):
    """Binary-tree reduction to ``root`` (paper Fig. 14A).  log2(n) hops;
    at step k, nodes at odd multiples of 2^k forward their partial to the
    node 2^k below.  Only the root's value is meaningful afterwards."""
    comb = COMBINERS.get(combiner, combiner)
    n = lax.axis_size(axis_name)
    assert _is_pow2(n), n
    assert root == 0, "rotate indices for non-zero roots"
    k = 1
    while k < n:
        # senders: idx % 2k == k -> receiver idx - k (non-participants get 0)
        perm = [(i, i - k) for i in range(n) if i % (2 * k) == k]
        moved = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), tree)
        idx = lax.axis_index(axis_name)
        is_recv = (idx % (2 * k)) == 0
        combined = comb(tree, moved)
        tree = jax.tree.map(
            lambda old, newv: jnp.where(is_recv, newv, old), tree, combined)
        k *= 2
    return tree


def tree_broadcast(tree, axis_name: str, root: int = 0):
    """Binary-tree broadcast from ``root`` — the reduce tree run backwards."""
    n = lax.axis_size(axis_name)
    assert _is_pow2(n) and root == 0
    k = n // 2
    while k >= 1:
        perm = [(i, i + k) for i in range(n) if i % (2 * k) == 0]
        moved = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), tree)
        idx = lax.axis_index(axis_name)
        is_recv = (idx % (2 * k)) == k
        tree = jax.tree.map(
            lambda old, newv: jnp.where(is_recv, newv, old), tree, moved)
        k //= 2
    return tree


def tree_all_reduce(tree, axis_name: str, combiner="add"):
    """Reduce-to-root + broadcast — the literal paper schedule (two trees).
    Prefer ``butterfly_all_reduce`` (same depth, no idle nodes)."""
    return tree_broadcast(tree_reduce(tree, axis_name, combiner), axis_name)


# ---------------------------------------------------------------------------
# fused non-linear collectives (the Curry-ALU payloads)
# ---------------------------------------------------------------------------

def tree_softmax_combine(acc, m, l, axis_name: str):
    """Combine per-shard attention/softmax partials (acc, m, l) across a
    sequence-sharded axis — paper Fig. 10's in-transit Softmax: the exp
    renormalization happens at every tree hop, never at a central NLU.

    acc [..., D] fp32, m [...], l [...] -> normalized output [..., D]."""
    def comb(a, b):
        return ref.combine_partials(a, b)

    acc, m, l = butterfly_all_reduce((acc, m, l), axis_name, comb)
    return acc / jnp.maximum(l, 1e-30)[..., None]


# hop/energy accounting for the serve path -------------------------------
#
# ``tree_softmax_combine`` runs inside jit'd shard_map bodies, so the host
# cannot count flits at execution time; instead the serving engine calls
# this cost model once per dispatched combine and accumulates the totals in
# its stats.  Constants mirror ``pimsim.params`` (NoCParams: 0.1 pJ/bit for
# an on-chip link+router traversal) so serve-path numbers are comparable
# with the pimsim figures.

E_HOP_PJ_PER_BIT = 0.1


def softmax_combine_cost(rows: int, heads: int, head_dim: int,
                         n_shards: int, itemsize: int = 4) -> dict:
    """Traffic/energy of ONE tree_softmax_combine over ``n_shards``.

    The butterfly moves the full (acc [rows, heads, head_dim],
    m [rows, heads], l [rows, heads]) payload per hop, log2(n) hops, every
    node active — per-device bytes therefore hops * payload.  ``itemsize``
    is the partials' element width in bytes (default 4: the (acc, m, l)
    contract carries fp32 partials regardless of how the KV *pool* is
    stored — an int8 pool dequantizes inside the kernel, before the
    combine).  Returns ``{"hops", "bytes", "energy_pj"}`` (bytes/energy
    are per device)."""
    assert _is_pow2(n_shards), n_shards
    hops = max(n_shards - 1, 0).bit_length()         # log2 for pow2 n
    payload = rows * heads * (head_dim + 2) * itemsize   # acc + m + l
    total = hops * payload
    return {"hops": hops, "bytes": total,
            "energy_pj": total * 8 * E_HOP_PJ_PER_BIT}


# swap-vs-recompute preemption cost model --------------------------------
#
# Under page-pool pressure the serving engine must evict a victim's KV
# state; heterogeneous-PIM schedulers (HPIM, Sangam) model the same binary
# choice this decides: park the state in a slower tier (bytes over the
# host link, paid twice — out now, back at restore) or drop it and re-run
# prefill in the fast tier (FLOPs).  Constants mirror ``pimsim.params``:
# the swap link is the CXL point-to-point hop a CXL-attached pool would
# traverse, the recompute rate is the SRAM-PIM compute lane (prefill is
# GEMM-shaped work and lands there).  Module-level so tests and operators
# can re-point them at measured hardware.

_CXL = _pimparams.Cxl()
_SRAM = _pimparams.SramPim()
_DRAM = _pimparams.DramPim()

SWAP_LINK_BYTES_PER_S = _CXL.p2p_bw
SWAP_E_PJ_PER_BIT = _CXL.e_pj_per_bit
RECOMPUTE_FLOPS_PER_S = _SRAM.bank_flops() * _DRAM.banks
RECOMPUTE_E_PJ_PER_FLOP = _SRAM.e_mac_pj / 2.0   # one MAC = two FLOPs


def swap_cost(n_pages: int, page_bytes: int, state_bytes: int = 0) -> dict:
    """Round-trip cost of parking ``n_pages`` KV pages host-side.

    ``page_bytes`` counts K **and** V for one page *at the pool's storage
    width* — the engine passes ``ServeEngine._page_kv_bytes()``, which
    prices an int8 pool at 1 byte per value plus its per-page scales, so a
    quantized pool's cheaper link traffic shifts the swap-vs-recompute
    crossover accordingly.  ``state_bytes`` adds a family's fixed-size
    recurrent slot state (hybrid Mamba2 conv/SSM — rides the same link
    both ways); the factor 2 is the two link traversals (swap-out now,
    swap-in at restore).  Returns ``{"bytes", "seconds", "energy_pj"}``."""
    b = 2 * (n_pages * page_bytes + state_bytes)
    return {"bytes": b, "seconds": b / SWAP_LINK_BYTES_PER_S,
            "energy_pj": b * 8 * SWAP_E_PJ_PER_BIT}


def recompute_cost(tokens: int, flops_per_token: float) -> dict:
    """Cost of re-running prefill over ``tokens`` dropped KV tokens.

    An upper bound: prefix-cache hits at re-admission can re-attach pages
    by reference and skip part of the replay.  Returns
    ``{"flops", "seconds", "energy_pj"}``."""
    f = tokens * flops_per_token
    return {"flops": f, "seconds": f / RECOMPUTE_FLOPS_PER_S,
            "energy_pj": f * RECOMPUTE_E_PJ_PER_FLOP}


def preempt_decision(n_pages: int, page_bytes: int, tokens: int,
                     flops_per_token: float, state_bytes: int = 0) -> str:
    """Pick the cheaper eviction arm for one victim: ``"swap"`` when moving
    the KV bytes (pages plus any fixed-size recurrent ``state_bytes``)
    over the link costs less time than re-running the prefill FLOPs, else
    ``"recompute"``.  Big models (high FLOPs/token vs bytes/token) swap;
    tiny models recompute — the crossover the HPIM/Sangam schedulers
    exploit."""
    s = swap_cost(n_pages, page_bytes, state_bytes)["seconds"]
    r = recompute_cost(tokens, flops_per_token)["seconds"]
    return "swap" if s <= r else "recompute"


def restore_cost_seconds(n_pages: int, page_bytes: int, tokens: int,
                         flops_per_token: float, state_bytes: int = 0,
                         policy: str = "auto") -> float:
    """Seconds to bring one preemption victim back: the swap arm's link
    round trip, the recompute arm's prefill replay, or (``"auto"``) the
    cheaper of the two — the same comparison :func:`preempt_decision`
    makes, exposed as a *magnitude* so schedulers can rank victims by how
    expensive each would be to evict, not just pick an arm.  For
    ``policy="auto"`` the returned value is always the cost of the arm
    ``preempt_decision`` would take."""
    s = swap_cost(n_pages, page_bytes, state_bytes)["seconds"]
    r = recompute_cost(tokens, flops_per_token)["seconds"]
    if policy == "swap":
        return s
    if policy == "recompute":
        return r
    return min(s, r)


# prefill -> decode handoff cost model (disaggregated serving) -----------
#
# Role-disaggregated serving (serve/disagg.py) streams a finished
# prefill's KV page chain + recurrent slot state from the prefill worker
# to the decode worker over the same CXL-class link the swap arena
# models — Sangam's CXL-attached KV movement, one way instead of the
# swap round trip.  Two effects make a handoff cheaper than its naive
# byte count: int8 pools transfer at storage width (``page_bytes`` is
# priced by the caller at the pool's width, as for ``swap_cost``), and
# pages whose digests the decode pool already holds registered
# (prefix-cached chains) never ride the link at all.

def handoff_cost(n_pages: int, page_bytes: int, state_bytes: int = 0,
                 cached_pages: int = 0, n_hops: int = 1) -> dict:
    """One-way cost of streaming one finished prefill to the decode role.

    ``n_pages`` is the full KV chain; ``cached_pages`` leading pages are
    already resident in the decode pool's prefix registry and transfer
    zero bytes (they re-attach by reference at admission).
    ``page_bytes`` is one page's K+V at the pool's *storage* width
    (``ServeEngine._page_kv_bytes()`` — int8 pools move 1-byte values
    plus per-page scales).  ``state_bytes`` adds the family's fixed-size
    recurrent slot state (ssm/rwkv/hybrid), which always transfers.
    ``n_hops`` counts link traversals between the two workers (1 for a
    point-to-point CXL pair; mesh-slice pairs may sit further apart —
    each extra hop adds router energy, not serialized bandwidth).
    Returns ``{"bytes", "hops", "seconds", "energy_pj"}``."""
    moved = max(n_pages - cached_pages, 0)
    b = moved * page_bytes + state_bytes
    return {"bytes": b, "hops": n_hops,
            "seconds": b / SWAP_LINK_BYTES_PER_S,
            "energy_pj": b * 8 * (SWAP_E_PJ_PER_BIT
                                  + max(n_hops - 1, 0) * E_HOP_PJ_PER_BIT)}


def handoff_admission_cost(n_pages: int, page_bytes: int, free_pages: int,
                           state_bytes: int = 0,
                           cached_pages: int = 0) -> dict:
    """The decode-pool admission arm: price admitting one staged handoff
    into a decode pool with ``free_pages`` grantable pages *right now*.

    The link cost is :func:`handoff_cost`'s one-way transfer of the
    uncached remainder; ``deferred`` flags a pool that cannot grant the
    remainder yet — the handoff stays staged in the arena (backpressure,
    never failure) and the decode engine retries next tick.  Returns
    ``handoff_cost(...)`` plus ``{"need_pages", "deferred"}``."""
    c = handoff_cost(n_pages, page_bytes, state_bytes, cached_pages)
    need = max(n_pages - cached_pages, 0)
    c["need_pages"] = need
    c["deferred"] = free_pages < need
    return c


# hot/cold expert placement cost model --------------------------------
#
# CompAir's hybrid premise for MoE: hot experts live in the sub-10ns
# SRAM-PIM tier, cold ones in the high-capacity DRAM-PIM tier, and every
# promotion moves the expert's weights over the CXL/NoC link (the
# NeuPIMs/DynaNDE line models the same decision cycle-accurately).  The
# serving-side expert cache (``serve/expert_cache.py``) prices its
# promotions with this arm.  Per-bank stream rates: the SRAM tier feeds
# weights over hybrid bonds (~6.4x the GDDR6 bank read-out), so a hot
# expert's dispatch is proportionally cheaper — worth a migration once
# its predicted traffic amortizes the link transfer.  Module-level
# constants so tests and operators can re-point them at measured hardware
# (same pattern as the swap/recompute model above).

EXPERT_SRAM_BYTES_PER_S = _SRAM.hb_bw_per_bank
EXPERT_SRAM_E_PJ_PER_BIT = _SRAM.e_access_pj_per_bit
EXPERT_DRAM_BYTES_PER_S = _DRAM.bank_bw
EXPERT_DRAM_E_PJ_PER_BIT = _DRAM.e_access_pj_per_bit
EXPERT_LINK_BYTES_PER_S = _CXL.p2p_bw
EXPERT_LINK_E_PJ_PER_BIT = _CXL.e_pj_per_bit + E_HOP_PJ_PER_BIT


def expert_placement_cost(expert_bytes: int, accesses: float = 1.0) -> dict:
    """Price serving ``accesses`` routed-token dispatches of ONE expert
    from each placement arm.

    ``expert_bytes`` is the routed expert's weight footprint (gate + up +
    down projections); ``accesses`` the number of token dispatches that
    stream it (each dispatch re-reads the weights from its tier — the
    worst-case, un-batched bound the placement decision conservatively
    prices).  Returns three arms::

        {"sram":    {"seconds", "energy_pj"},   # resident hit, per tier
         "dram":    {"seconds", "energy_pj"},   # cold access in DRAM-PIM
         "migrate": {"seconds", "bytes", "energy_pj"}}  # one link move

    The migrate arm is a one-time DRAM->SRAM transfer over the CXL/NoC
    link; with the default constants the sram-vs-dram gap scales with
    ``accesses`` while the migration does not, so the crossover is a pure
    access-count threshold (independent of ``expert_bytes``)."""
    bits = 8.0 * expert_bytes
    return {
        "sram": {"seconds": accesses * expert_bytes / EXPERT_SRAM_BYTES_PER_S,
                 "energy_pj": accesses * bits * EXPERT_SRAM_E_PJ_PER_BIT},
        "dram": {"seconds": accesses * expert_bytes / EXPERT_DRAM_BYTES_PER_S,
                 "energy_pj": accesses * bits * EXPERT_DRAM_E_PJ_PER_BIT},
        "migrate": {"seconds": expert_bytes / EXPERT_LINK_BYTES_PER_S,
                    "bytes": expert_bytes,
                    "energy_pj": bits * EXPERT_LINK_E_PJ_PER_BIT},
    }


def expert_promotion_worthwhile(expert_bytes: int,
                                predicted_accesses: float) -> bool:
    """Should a cold expert migrate to the SRAM-PIM tier?  True when the
    one-time link transfer plus its predicted SRAM-resident serving time
    beats leaving it in DRAM-PIM — the promotion gate the expert cache
    applies to its EMA-predicted hot candidates (an anti-thrash guard:
    experts whose predicted traffic cannot amortize the migration stay
    cold)."""
    c = expert_placement_cost(expert_bytes, predicted_accesses)
    return (c["migrate"]["seconds"] + c["sram"]["seconds"]
            < c["dram"]["seconds"])


def distributed_softmax(x, axis_name: str):
    """Softmax over a feature axis sharded across ``axis_name`` (e.g. the
    vocab-sharded LM head).  max and sum statistics ride the butterfly."""
    m_loc = x.max(axis=-1)
    m = butterfly_all_reduce(m_loc, axis_name, "max")
    e = jnp.exp(x - m[..., None])
    s = butterfly_all_reduce(e.sum(axis=-1), axis_name, "add")
    return e / s[..., None]


def distributed_logsumexp(x, axis_name: str):
    m = butterfly_all_reduce(x.max(axis=-1), axis_name, "max")
    s = butterfly_all_reduce(
        jnp.exp(x - m[..., None]).sum(axis=-1), axis_name, "add")
    return m + jnp.log(s)


# ---------------------------------------------------------------------------
# centralized-NLU baselines (what CompAir-NoC replaces)
# ---------------------------------------------------------------------------

def centralized_softmax(x, axis_name: str):
    """Baseline: gather the full vector to every shard (the NLU round
    trip), compute softmax locally, keep the local slice.  This is the
    all-gather + broadcast traffic pattern of Fig. 5A."""
    n = lax.axis_size(axis_name)
    full = lax.all_gather(x, axis_name, axis=-1, tiled=True)
    y = jax.nn.softmax(full.astype(jnp.float32), axis=-1).astype(x.dtype)
    idx = lax.axis_index(axis_name)
    size = x.shape[-1]
    return lax.dynamic_slice_in_dim(y, idx * size, size, axis=-1)


def centralized_softmax_combine(acc, m, l, axis_name: str):
    """Baseline for the decode-attention combine: all-gather all partials,
    reduce locally."""
    accs = lax.all_gather(acc, axis_name)            # [n, ..., D]
    ms = lax.all_gather(m, axis_name)
    ls = lax.all_gather(l, axis_name)
    n = accs.shape[0]
    part = (accs[0], ms[0], ls[0])
    for i in range(1, n):
        part = ref.combine_partials(part, (accs[i], ms[i], ls[i]))
    acc, m, l = part
    return acc / jnp.maximum(l, 1e-30)[..., None]
