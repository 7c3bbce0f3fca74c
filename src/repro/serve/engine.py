"""Paged-KV continuous-batching serving engine.

The paper's decode phase is bandwidth-bound: the DRAM-PIM lane streams the
KV cache past bank-level MACs, so host-side serving must keep every bank
busy with many concurrent sequences.  This engine does that with the three
standard production mechanisms:

* **Paged KV cache** — physical pages ``[L, KvH, NB, BS, hd]`` shared by
  all slots, addressed through per-slot block tables (vLLM-style).  Pages
  are allocated on demand and recycled at retirement, so peak KV memory
  follows *live tokens*, not ``slots x max_seq``.  Physical page 0 is a
  null sink for padding/retired-slot writes.
* **Continuous batching** — multiple requests are admitted per tick under
  a token budget; one jit'd ``decode_step_paged`` serves all slots every
  tick, so a retiring sequence's slot is refilled without draining the
  batch.
* **Chunked prefill** — prompts are split into bucket-sized chunks under
  the per-tick token budget, each chunk attending to the already-paged
  prefix (exact — verified token-for-token against monolithic prefill).
  Decode tokens are reserved from the budget *before* prefill every tick.
  Note: the default budget (``slots + largest bucket``) admits a full
  largest-bucket prefill per tick; pass a smaller ``max_tokens_per_tick``
  to force chunking and bound per-tick prefill latency for long prompts.

* **Prefix caching** — full prompt pages are published under a chained
  content hash; a new prompt's longest cached page-prefix is attached by
  reference at admission (refcounted pages, copy-on-write when the match
  ends mid-page) and its chunked prefill starts at the first uncached
  token.  Cold cached pages are evicted LRU only under pool pressure.
* **Paged prefill fast path** — each chunk's attention runs directly on
  the pages (``ops.paged_prefill_attention``); the engine passes a
  prefix-length-bucketed slice of the block table, so per-chunk work is
  bounded by ``ceil(cached_len/BS)`` pages instead of the pool size.
* **Progress-preserving preemption** — allocation deadlocks under page-pool
  pressure are broken by evicting the slot with the least live KV, but its
  progress *survives*: pages are either **swapped** to a host-side arena
  (``serve/swap.py``) and copied back verbatim at restore, or **dropped and
  recomputed** — full pages republished through the prefix cache (the
  digest chain extends over decoded tokens) and the remainder re-prefilled
  from ``prompt + out_tokens``.  ``preempt_policy={"swap","recompute",
  "auto"}``; ``auto`` weighs link bytes against prefill FLOPs per victim
  (``core.noc.preempt_decision``).  Preempted requests re-admit with
  priority over new work; no decoded token is ever replayed or re-sampled,
  so greedy outputs are token-identical to an unpressured run.
* **SLO-aware scheduling** — every request carries a *latency class*
  (``submit(..., priority="interactive"|"batch")``); fresh admissions
  interleave classes by **deficit-weighted round-robin** over
  ``class_weights`` (weight-proportional goodput shares under sustained
  contention; no positive-weight class is ever fully starved), restores
  re-admit first with class barriers, preemption-victim selection scores
  candidates by ``pages held x restore cost x class weight`` (restore
  cost priced by ``core.noc.restore_cost_seconds`` — the same
  swap-vs-recompute model ``preempt_decision`` uses), and with
  ``proactive_horizon > 0`` the engine preempts on *predicted* page-pool
  exhaustion (free + reclaimable pages vs the next-K-ticks page demand
  of active slots) instead of waiting for a fully stalled tick.
  Deadlines (``submit(deadline_ms=...)`` or per-class
  ``class_deadlines_ms``) are checked at finish on the wall clock;
  misses land in ``stats["slo_violations"]`` and per-class in
  ``class_stats``.  Per-request TTFT/TPOT (wall and tick clocks) ride
  the :class:`Request`.
* **Async submission** — ``submit()`` returns a :class:`RequestFuture`
  (an ``int`` subclass, so rid-keyed callers are unchanged):
  ``done()``/``tokens()`` poll without stepping, ``result()`` steps the
  engine to completion, ``stream()`` yields tokens as ticks produce
  them.  The same future API fronts the disaggregated
  ``serve.disagg.DisaggServer``, so harnesses drive both shapes
  identically.
* **Prefill/decode disaggregation** (``role="prefill"|"decode"``) — the
  serving analogue of the paper's SRAM-PIM/DRAM-PIM split: a
  prefill-role engine terminates at handoff (first token sampled, slot
  parked until ``stage_handoff()`` streams its page chain + recurrent
  slot state into a shared pinned arena), a decode-role engine admits
  exclusively from staged :class:`~repro.serve.swap.HandoffHandle`s
  (``submit_handoff()``), re-attaching prefix-cached chains by reference
  so only the uncached remainder rides the link —
  ``core.noc.handoff_cost`` prices each transfer at storage width.
  ``serve/disagg.py`` owns the pairing, staging loop and accounting.
* **Sequence-sharded page pool** (``seq_shards=N``) — the physical pool is
  split over an N-device ``seq`` mesh axis; ``BlockAllocator`` places a
  slot's pages round-robin across shards (fill-local under pressure), and
  decode/prefill dispatch wraps the paged kernels in ``jax.shard_map``:
  each shard attends only its local pages (foreign entries map to its
  null page and are skipped) and emits ``(acc, m, l)`` partials that
  ``core.noc.tree_softmax_combine`` merges in transit over the ``seq``
  axis — the paper's NoC-ALU softmax reduction, with hop/energy totals in
  ``stats["noc_*"]``.  Greedy outputs are token-identical to 1 shard.

Prefill functions are jit'd **once per bucket** (x O(log MB) block-table
buckets) and cached (``stats["prefill_traces"]`` counts actual traces; it
stays flat across admissions).

**Family-agnostic cache contract.**  The engine never branches on
``cfg.family``: every family is described by a
``models.runner.CacheSpec`` — which cache components are *paged*
(transformer KV; the hybrid family's shared-attention KV, one block table
per sequence serving all G applications) and which are *fixed-size slot
state* (Mamba2 conv/SSM, RWKV6 shift/wkv) — and driven through
``models.runner.ModelRunner``'s init/prefill/decode/extract/insert entry
points.  Consequences the scheduler derives from the spec alone:

* dense/moe: paged KV, prefix caching, page-pressure preemption — as
  before.
* hybrid: real paged attention KV for the shared block **plus** slot
  state; swap preemption parks *pages and state together* (registered
  prefix-chain pages are re-attached by reference at restore and only the
  unregistered remainder rides the arena), recompute replays through the
  family's chunked prefill (padding rows are state-neutral).
* ssm/rwkv: slot-state-only continuous batching — same token budget,
  chunked prefill and batched decode, no page pressure at all.  Batched
  decode masks slot-state updates for non-runnable slots so a
  mid-prefill neighbour's recurrent state is never clobbered.

Prefix caching stays attention-KV-only: families with slot state publish
and pin page digests (that is what makes the swap-restore re-attach
sound — the parked state blob covers the same tokens) but never skip
prefill compute at admission, because cached pages cannot reconstruct the
recurrent state that must advance through those tokens.

``paged=False`` keeps the legacy dense ``[slots, max_seq]`` slab path
(monolithic prefill, no paging) for every family as the A/B baseline of
``benchmarks/serve_throughput.py``.
"""
from __future__ import annotations

import hashlib
import itertools
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import noc
from repro.kernels import ops
from repro.kernels import prefill_attention as pf_kernel
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.runner import ModelRunner
from repro.serve.expert_cache import ExpertCache

# Latency classes and their default preemption weights.  A victim's
# eviction score is ``pages x restore_cost x weight``, so a heavier class
# is proportionally harder to evict; admission drains heavier classes
# first (age-ordered within a class).  Override / extend via the engine's
# ``class_weights`` ctor arg.
LATENCY_CLASSES = ("interactive", "batch")
CLASS_WEIGHTS = {"interactive": 8.0, "batch": 1.0}


@dataclass
class Request:
    """One in-flight generation request (engine-internal mutable record).

    ``out_tokens`` grows by sampling; ``prefill_pos`` tracks chunked-prefill
    progress; the ``resume_*`` fields carry preserved progress across a
    preemption (see :meth:`ServeEngine.step`'s deadlock breaking): after a
    preempt, ``resume_len`` is the number of KV tokens (prompt *and*
    decoded) that must be restored — by swap-in or recompute — before
    decode can continue, and ``_resume_tokens`` is that token sequence
    (``prompt[:plen] + out_tokens[:-1]`` truncated to ``resume_len``)."""
    rid: int
    prompt: np.ndarray                  # [len] int32
    max_new_tokens: int = 32
    temperature: float = 0.0            # 0 => greedy
    eos_id: Optional[int] = None
    priority: str = "interactive"       # latency class (LATENCY_CLASSES)
    deadline_ms: Optional[float] = None  # SLO deadline, submit -> finish
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    prefill_pos: int = 0                # tokens already prefilled (chunked)
    cached_len: int = 0                 # prefix tokens served from cache
    ttft: Optional[float] = None        # submit -> first token (seconds)
    tpot: Optional[float] = None        # seconds per decode token (mean)
    submit_tick: int = 0                # virtual clock: tick at submit
    first_tick: Optional[int] = None    # tick the first token landed
    finish_tick: Optional[int] = None   # tick the request retired
    resume_len: int = 0                 # preempted: KV tokens to restore
    _preempted_live: int = 0            # KV tokens live at last eviction
    _t_submit: float = 0.0
    _t_first: float = 0.0               # wall clock of the first token
    _digests: List[bytes] = field(default_factory=list)  # per-full-page chain
    _published: int = 0                 # this slot's pages already registered
    _resume_tokens: Optional[np.ndarray] = None  # [resume_len] int32
    _swap: Optional[object] = None      # swap.SwapHandle while parked
    _await_handoff: bool = False        # prefill role: parked post-prefill
    _handoff: Optional[object] = None   # decode role: staged HandoffHandle


class RequestFuture(int):
    """Async handle returned by ``submit()`` — the engine API the
    disaggregated server forced onto the single-role engine too.

    It subclasses ``int`` and *is* the request id, so every existing
    rid-keyed consumer (dict keys, equality, formatting) is untouched;
    on top of that it carries future/stream semantics over the owning
    driver (a :class:`ServeEngine` or ``serve.disagg.DisaggServer`` —
    anything with the ``_future_done/_future_tokens/_future_step``
    protocol).  ``result()``/``stream()`` *drive* the server loop: each
    wait tick advances every in-flight request (continuous batching), so
    awaiting one future never idles the engine."""

    def __new__(cls, rid: int, driver):
        self = super().__new__(cls, rid)
        self._driver = driver
        return self

    @property
    def rid(self) -> int:
        return int(self)

    def done(self) -> bool:
        return self._driver._future_done(int(self))

    def tokens(self) -> List[int]:
        """Tokens produced so far (a snapshot; grows until ``done()``)."""
        return list(self._driver._future_tokens(int(self)))

    def result(self, max_ticks: int = 10_000) -> List[int]:
        """Block (stepping the driver) until this request finishes;
        returns its completed token list."""
        for _ in range(max_ticks):
            if self.done():
                return self.tokens()
            self._driver._future_step()
        raise RuntimeError(
            f"request {int(self)} unfinished after {max_ticks} ticks")

    def stream(self, max_ticks: int = 10_000):
        """Yield tokens as they are produced, stepping the driver while
        the request is unfinished (the streaming half of the async API)."""
        sent = 0
        for _ in range(max_ticks):
            toks = self._driver._future_tokens(int(self))
            while sent < len(toks):
                yield toks[sent]
                sent += 1
            if self.done():
                return
            self._driver._future_step()
        raise RuntimeError(
            f"request {int(self)} unfinished after {max_ticks} ticks")


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _page_digests(prompt: np.ndarray, block_size: int, n_pages: int,
                  seed: bytes = b"\x00" * 16) -> List[bytes]:
    """Chained (rolling) content hash per full prompt page: page i's digest
    commits to every token in [0, (i+1)*BS), so equal digests <=> equal
    page *prefix* — exactly the sharing condition for causal KV.  ``seed``
    starts the chain; the engine folds ``kv_dtype`` into it so pages stored
    in different formats can never alias in the prefix registry."""
    digests, parent = [], seed
    for i in range(n_pages):
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(np.ascontiguousarray(
            prompt[i * block_size:(i + 1) * block_size], np.int32).tobytes())
        parent = h.digest()
        digests.append(parent)
    return digests


class BlockAllocator:
    """Host-side refcounted physical-page pool, per-slot block tables, and
    the prefix-cache registry.

    Page 0 is reserved as the null sink (never handed out), so an all-zero
    block-table row is always safe to pass to the device.

    Pages are refcounted so full prompt-prefix pages can be *shared* across
    slots (vLLM-style prefix caching).  A page whose refcount drops to zero
    is parked in an LRU instead of freed when it is registered in the hash
    map — still matchable by future prompts, reclaimed (oldest first) only
    when the free list runs dry.

    With ``num_shards > 1`` the pool is *sequence-sharded*: shard ``s``
    owns global page ids ``[s*nb_local, (s+1)*nb_local)`` and reserves its
    local page 0 (global ``s*nb_local``) as that shard's null sink.  A
    slot's logical block ``j`` prefers shard ``j % num_shards``
    (round-robin, so one sequence's KV spreads across every shard's
    bandwidth lane), falling back to any shard with a free page
    (fill-local).  The prefix-cache registry keys on content digests,
    which are shard-agnostic — a cached chain attaches by reference no
    matter which shards hold its pages."""

    def __init__(self, num_blocks: int, block_size: int, slots: int,
                 max_blocks_per_slot: int, num_shards: int = 1):
        if num_blocks % num_shards:
            raise ValueError(f"num_blocks={num_blocks} not divisible by "
                             f"num_shards={num_shards}")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.num_shards = num_shards
        self.nb_local = num_blocks // num_shards
        if self.nb_local < 2:
            raise ValueError("each shard needs >= 1 usable page beyond its "
                             f"null page (nb_local={self.nb_local})")
        # per-shard free lists, popped lowest-id first (shard-0/S=1 order is
        # identical to the unsharded allocator: 1, 2, 3, ...)
        self._free_by_shard = [
            list(range(s * self.nb_local + self.nb_local - 1,
                       s * self.nb_local, -1))
            for s in range(num_shards)]
        self.refcount = np.zeros((num_blocks,), np.int32)
        self.table = np.zeros((slots, max_blocks_per_slot), np.int32)
        self.used = np.zeros((slots,), np.int32)
        self._hash_to_page: Dict[bytes, int] = {}
        self._page_hash: Dict[int, bytes] = {}     # registered pages only
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # refcount-0 cached
        self.pages_allocated = 0
        self.pages_freed = 0
        self.pages_shared = 0
        self.pages_evicted = 0

    @property
    def _free(self) -> List[int]:
        """Flat read-only view of the per-shard free lists."""
        return [p for fl in self._free_by_shard for p in fl]

    @property
    def free_blocks(self) -> int:
        """Pages grantable right now: truly free + reclaimable cached."""
        return sum(len(fl) for fl in self._free_by_shard) + len(self._lru)

    @property
    def usable_blocks(self) -> int:
        """Pool capacity minus the per-shard null pages."""
        return self.num_blocks - self.num_shards

    @property
    def cached_blocks(self) -> int:
        return len(self._lru)

    def owner(self, page: int) -> int:
        return page // self.nb_local

    def shard_local(self, table: np.ndarray) -> np.ndarray:
        """Global-id block table [..., MB] -> per-shard local tables
        [S, ..., MB]: entries owned by shard ``s`` keep their local index
        in ``s``'s row; everything else maps to that shard's null page 0
        (the device-side skip/scatter-sink contract).  S=1 returns the
        table unchanged under a leading unit axis."""
        t = np.asarray(table, np.int64)
        owner = t // self.nb_local
        local = (t % self.nb_local).astype(np.int32)
        out = np.zeros((self.num_shards,) + t.shape, np.int32)
        for s in range(self.num_shards):
            np.copyto(out[s], local, where=owner == s)
        return out

    def reset_counters(self) -> None:
        self.pages_allocated = self.pages_freed = 0
        self.pages_shared = self.pages_evicted = 0

    def _reclaim(self, preferred: int = 0) -> Optional[int]:
        for i in range(self.num_shards):
            fl = self._free_by_shard[(preferred + i) % self.num_shards]
            if fl:
                return fl.pop()
        if self._lru:                      # evict the coldest cached page
            page, _ = self._lru.popitem(last=False)
            del self._hash_to_page[self._page_hash.pop(page)]
            self.pages_evicted += 1
            return page
        return None

    def alloc_page(self, slot: int) -> Optional[int]:
        """Grant one exclusive page to ``slot`` (evicting cold cached pages
        under pressure); None if every page is referenced.  The slot's next
        logical block prefers its round-robin shard, so a sequence's pages
        spread across the sharded pool."""
        if self.used[slot] >= self.table.shape[1]:
            return None
        page = self._reclaim(int(self.used[slot]) % self.num_shards)
        if page is None:
            return None
        self.refcount[page] = 1
        self.table[slot, self.used[slot]] = page
        self.used[slot] += 1
        self.pages_allocated += 1
        return page

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s table to cover ``n_tokens``; False if the pool is
        exhausted (the caller stalls the slot until pages are recycled)."""
        need = -(-n_tokens // self.block_size)
        if need > self.table.shape[1]:
            return False
        while self.used[slot] < need:
            if self.alloc_page(slot) is None:
                return False
        return True

    def share(self, slot: int, page: int) -> bool:
        """Append a cache-hit page to ``slot``'s table (refcount bump; a
        parked page is resurrected out of the LRU)."""
        if self.used[slot] >= self.table.shape[1]:
            return False
        if self.refcount[page] == 0:
            self._lru.pop(page, None)
        self.refcount[page] += 1
        self.table[slot, self.used[slot]] = page
        self.used[slot] += 1
        self.pages_shared += 1
        return True

    def release(self, slot: int) -> None:
        """Drop every page reference the slot holds (tail block first, so
        registered pages park in the LRU tail-before-head and pool pressure
        evicts a cached chain's *suffix* first — a chain missing its head
        page can never be matched again, a chain missing its tail still
        serves a shorter prefix)."""
        for i in reversed(range(int(self.used[slot]))):
            self._unref(int(self.table[slot, i]))
        self.table[slot] = 0
        self.used[slot] = 0

    def _unref(self, page: int) -> None:
        if self.refcount[page] <= 0:
            raise RuntimeError(f"double free of physical page {page}")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self.pages_freed += 1
            if page in self._page_hash:
                self._lru[page] = None     # park: matchable until evicted
            else:
                self._free_by_shard[self.owner(page)].append(page)

    # -- prefix-cache registry -----------------------------------------
    def register(self, page: int, digest: bytes) -> bool:
        """Publish a completed full prompt page.  First writer wins: a
        duplicate digest (two requests racing the same prompt) keeps the
        original mapping and the newcomer's page stays private."""
        if digest in self._hash_to_page or page in self._page_hash:
            return False
        self._hash_to_page[digest] = page
        self._page_hash[page] = digest
        return True

    def lookup(self, digest: bytes) -> Optional[int]:
        return self._hash_to_page.get(digest)

    def page_digest(self, page: int) -> Optional[bytes]:
        """The digest ``page`` is registered under (None if unregistered)."""
        return self._page_hash.get(page)

    # -- out-of-table references (swap-handle pins) --------------------
    def pin(self, page: int) -> None:
        """Hold a reference to ``page`` without a table slot — a swap
        handle pins its registered prefix-chain pages so LRU eviction can
        never reclaim them while the victim is parked."""
        if self.refcount[page] <= 0:
            raise RuntimeError(f"pin of unreferenced physical page {page}")
        self.refcount[page] += 1

    def acquire(self, page: int) -> None:
        """Like :meth:`pin`, but may resurrect a *parked* (refcount-0,
        LRU-registered) page — handoff staging acquires the decode pool's
        cached chain prefix so LRU eviction cannot invalidate the match
        between staging and admission.  Only registered pages may be
        acquired from refcount 0 (an unregistered refcount-0 page lives on
        the free list and could be granted to anyone)."""
        if self.refcount[page] == 0:
            if page not in self._page_hash:
                raise RuntimeError(
                    f"acquire of free unregistered physical page {page}")
            self._lru.pop(page, None)
        self.refcount[page] += 1

    def unpin(self, page: int) -> None:
        self._unref(page)


def _gini(x: np.ndarray) -> float:
    """Gini coefficient of a nonnegative load vector (0 = perfectly
    balanced, -> 1 = all load on one expert)."""
    x = np.sort(np.asarray(x, np.float64))
    n, tot = x.size, float(x.sum())
    if n < 2 or tot <= 0.0:
        return 0.0
    i = np.arange(1, n + 1)
    return float(2.0 * (i * x).sum() / (n * tot) - (n + 1.0) / n)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_seq: int = 512,
                 slots: int = 8, seed: int = 0,
                 prefill_buckets=(32, 128, 512, 2048),
                 paged: Optional[bool] = None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 max_tokens_per_tick: Optional[int] = None,
                 prefix_caching: Optional[bool] = None,
                 seq_shards: int = 1, preempt_policy: str = "auto",
                 swap_pages: Optional[int] = None,
                 class_weights: Optional[Dict[str, float]] = None,
                 class_deadlines_ms: Optional[Dict[str, float]] = None,
                 proactive_horizon: int = 0,
                 role: Optional[str] = None,
                 q_tile: Optional[int] = None,
                 kv_dtype: str = "fp16",
                 expert_parallel: Optional[int] = None,
                 expert_cache_size: Optional[int] = None,
                 expert_prefetch: bool = True,
                 expert_placement: str = "adaptive"):
        """Stand up a serving engine over ``params``.

        Args:
          cfg: model architecture (``repro.configs``); attention families
            (``dense``/``moe``) default to the paged KV cache.
          params: parameter pytree (its leaf dtype sets the KV dtype).
          max_seq: per-sequence cap, prompt + generated tokens.
          slots: concurrent sequences in the batched decode.
          seed: RNG seed for temperature sampling.
          prefill_buckets: chunk sizes for chunked prefill; each bucket is
            jit-compiled once and cached (``max_seq`` is always included).
            Buckets above 512 are fine — the q-tiled prefill kernel's
            VMEM scratch is sized by ``q_tile``, not the chunk — and are
            validated against the kernel's VMEM budget at construction.
          paged: None (default) serves through the family-agnostic
            CacheSpec runner — paged KV where the family has attention
            KV components (dense/moe/hybrid), slot-state-only continuous
            batching otherwise (ssm/rwkv).  True additionally *requires*
            a paged component (raises for slot-state-only families).
            False forces the legacy dense ``[slots, max_seq]`` slab
            baseline (monolithic prefill) for any family — the A/B
            reference of ``benchmarks/serve_throughput.py``.
          block_size: tokens per KV page.
          num_blocks: physical page-pool size (default: full capacity,
            ``slots * ceil(max_seq/block_size)`` + null pages).  Smaller
            pools oversubscribe — the engine then stalls, preempts, and
            restores under pressure rather than failing.
          max_tokens_per_tick: padded-token budget per tick shared by
            decode (reserved first) and chunked prefill.
          prefix_caching: share full prompt pages across requests via a
            chained content hash (default: on when paged).
          seq_shards: sequence-shard the page pool over an N-device
            ``seq`` mesh axis (power of two); per-shard attention partials
            merge in transit via ``core.noc.tree_softmax_combine``.
          preempt_policy: how a preemption victim's KV progress is
            preserved — ``"swap"`` parks live pages in the host arena
            (``serve/swap.py``), ``"recompute"`` drops them and replays
            prefill over prompt + decoded tokens at restore (prefix-cache
            hits skip most of the replay), ``"auto"`` (default) picks per
            victim via ``core.noc.preempt_decision`` (link bytes vs
            prefill FLOPs).  Greedy outputs are token-identical to an
            unpressured run under every policy.
          swap_pages: host swap-arena capacity in pages (default: one full
            pool's worth).  A full arena degrades ``swap`` to
            ``recompute`` for that victim instead of failing.
          class_weights: latency-class name -> weight map (default
            ``CLASS_WEIGHTS``: interactive=8, batch=1).  Admission is a
            deficit-weighted round-robin over the classes — each class
            earns quantum proportional to its weight, so goodput shares
            converge to the weight ratio under sustained load and no
            class is ever fully starved (age-ordered within a class) —
            and a victim's eviction score scales with its weight, so
            heavier classes are admitted sooner and evicted later.
          class_deadlines_ms: latency-class name -> default SLO deadline
            (milliseconds, submit -> finish, wall clock).  A request may
            override with ``submit(..., deadline_ms=)``; a finished
            request past its deadline counts into
            ``stats["slo_violations"]`` and its class's
            ``class_stats[cls]["slo_violations"]``.  None (default):
            no deadline for classes not in the map.
          proactive_horizon: look-ahead in ticks for *proactive*
            preemption (0 = off, the deadlock-only legacy behavior).
            When the active slots' predicted page demand over the next
            ``proactive_horizon`` ticks exceeds the grantable pool
            (free + LRU-reclaimable pages), the cheapest victim by
            ``pages x restore cost x class weight`` is preempted *before*
            anything stalls — progress-preserving, so greedy outputs stay
            token-identical either way.
          role: restrict the engine to one half of a disaggregated
            prefill/decode pair (``serve/disagg.py`` owns the pairing).
            ``"prefill"`` runs admission + chunked prefill but
            *terminates at handoff*: a finished prefill samples its first
            token, then parks awaiting ``stage_handoff()`` instead of
            decoding.  ``"decode"`` admits only staged
            :class:`~repro.serve.swap.HandoffHandle`s
            (``submit_handoff()``; plain ``submit()`` raises) and runs
            batched decode — restores/preemption work as usual.  None
            (default): the monolithic engine, both phases.
          q_tile: prefill-kernel query-tile size in chunk positions
            (default None = auto: largest power of two whose scratch fits
            the kernel's VMEM budget, so big buckets tile and small ones
            run single-tile).  Never changes results — only the kernel's
            VMEM footprint and dispatch granularity.
          kv_dtype: KV-page storage format.  ``"fp16"`` (default) stores
            pages in the engine dtype — bit-exact with the historical
            behavior.  ``"int8"`` stores quantized pages plus a
            per-page-per-head f32 scale for each of K and V: ~half (vs
            bf16 params) the pool bytes per page, so the same byte budget
            holds about twice the concurrent sequences, at a bounded
            logit divergence.  The paged kernels dequantize in their
            inner page loop; requires a paged KV component.
          expert_parallel: shard the routed experts of a MoE family over
            an N-way ``expert`` mesh axis (each shard applies its local
            expert bank, outputs merge with one ``psum``).  Composes with
            ``seq_shards`` as a ``(seq, expert)`` mesh — the device count
            must cover the product.  ``expert_parallel=1`` runs the EP
            dispatch on a 1-shard mesh (useful for parity testing).
            Requires ``cfg.n_experts > 0`` and the runner path; padded
            expert count must divide evenly.  Greedy outputs are
            token-identical to the unsharded engine.
          expert_cache_size: SRAM-PIM-resident experts per layer for the
            placement-aware hot/cold expert cache
            (``serve/expert_cache.py``); None (default) disables
            placement accounting.  The cache is a host-side model driven
            by per-tick expert-load telemetry — it never changes device
            results, only the ``expert_*`` stats.
          expert_prefetch: double-buffered promotion staging (promoted
            experts land one tick later, never served mid-flight); False
            commits promotions at end of tick.
          expert_placement: ``"adaptive"`` (default) migrates hot experts
            into SRAM residency per ``core.noc.expert_placement_cost``;
            ``"static"`` freezes the initial placement — the A/B baseline
            of ``benchmarks/serve_throughput.py run_moe_skew``.
        """
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.slots = slots
        self.rng = jax.random.key(seed)
        self.dtype = jax.tree.leaves(params)[0].dtype
        if kv_dtype not in ("fp16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp16' or 'int8', got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        # Family behavior is fully described by the CacheSpec contract —
        # cfg.family is never consulted past this constructor.
        self.q_tile = None if q_tile is None else int(q_tile)
        self.runner = ModelRunner(cfg, slots, max_seq, q_tile=self.q_tile,
                                  kv_dtype=kv_dtype)
        spec = self.runner.spec
        if paged and not spec.has_paged:
            raise ValueError(
                f"family {cfg.family!r} has no paged cache components "
                f"(CacheSpec: slot state only) — serve it with paged=None "
                f"(slot-state continuous batching) or paged=False (the "
                f"dense-slab A/B baseline)")
        # paged=None/True -> the CacheSpec runner path (paged components
        # block-table-addressed, slot state batched over engine slots);
        # paged=False -> the legacy dense [slots, max_seq] slab baseline
        # (monolithic prefill) kept for benchmark A/Bs.
        self.dense_baseline = paged is False
        self.paged = (not self.dense_baseline) and spec.has_paged
        self.has_slot_state = ((not self.dense_baseline)
                               and spec.has_slot_state)
        if self.kv_dtype == "int8" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' quantizes the paged page pool — serve "
                "with a paged KV component (or keep kv_dtype='fp16')")
        if prefix_caching and not self.paged:
            raise ValueError("prefix_caching requires a paged KV component")
        self.prefix_caching = self.paged if prefix_caching is None \
            else bool(prefix_caching)
        # Slot-state families publish/pin page digests (swap restores
        # re-attach registered chains by reference) but can never *skip*
        # prefill compute at admission: cached pages cannot reconstruct
        # the recurrent state that must advance through those tokens.
        self.prefix_attach = self.prefix_caching and not self.has_slot_state
        # kv_dtype-salted digest-chain seed: int8 and fp16 pages can never
        # alias in the prefix registry (their stored bytes differ even for
        # identical token prefixes)
        self._digest_seed = hashlib.blake2b(
            b"kv_dtype:" + self.kv_dtype.encode(), digest_size=16).digest()

        self.seq_shards = int(seq_shards)
        if self.seq_shards < 1 or (self.seq_shards & (self.seq_shards - 1)):
            raise ValueError(
                f"seq_shards must be a power of two, got {seq_shards} "
                "(the NoC butterfly combine is a recursive-doubling tree)")
        if self.seq_shards > 1 and not self.paged:
            raise ValueError("seq_shards > 1 requires the paged KV cache")

        # expert parallelism + placement-aware expert cache (MoE serving)
        if expert_placement not in ("adaptive", "static"):
            raise ValueError(
                f"expert_placement must be 'adaptive' or 'static', got "
                f"{expert_placement!r}")
        self.expert_parallel = (None if expert_parallel is None
                                else int(expert_parallel))
        if self.expert_parallel is not None:
            if self.expert_parallel < 1:
                raise ValueError(
                    f"expert_parallel must be >= 1, got {expert_parallel}")
            if cfg.n_experts <= 0:
                raise ValueError(
                    f"expert_parallel requires a MoE family "
                    f"(cfg.n_experts > 0); {cfg.family!r} has none")
            if self.dense_baseline:
                raise ValueError(
                    "expert_parallel shards the runner dispatch — it is "
                    "incompatible with the dense-slab baseline "
                    "(paged=False)")
            e_pad = self.runner.padded_experts()
            if e_pad % self.expert_parallel:
                raise ValueError(
                    f"expert_parallel={self.expert_parallel} must divide "
                    f"the padded expert count ({e_pad})")
        ep = self.expert_parallel or 1
        ndev = jax.device_count()
        if self.seq_shards * ep > ndev:
            platform = jax.devices()[0].platform
            hint = ("set XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{self.seq_shards * ep} before importing jax"
                    if platform == "cpu" else
                    f"run on a host with {self.seq_shards * ep} chips")
            raise ValueError(
                f"seq_shards={self.seq_shards} x expert_parallel={ep} "
                f"needs {self.seq_shards * ep} devices but only {ndev} "
                f"{platform} device(s) are visible — {hint}, or shard "
                f"less")
        if self.seq_shards > 1 and ep > 1:
            self.mesh = make_mesh((self.seq_shards, ep), ("seq", "expert"))
        elif self.seq_shards > 1:
            self.mesh = make_mesh((self.seq_shards,), ("seq",))
        elif self.expert_parallel is not None:
            self.mesh = make_mesh((ep,), ("expert",))
        else:
            self.mesh = None
        self._expert_axis = ("expert" if self.expert_parallel is not None
                             else None)

        self.expert_cache: Optional[ExpertCache] = None
        if expert_cache_size is not None:
            if cfg.n_experts <= 0:
                raise ValueError(
                    f"expert_cache_size requires a MoE family "
                    f"(cfg.n_experts > 0); {cfg.family!r} has none")
            if self.dense_baseline:
                raise ValueError(
                    "expert_cache_size needs the runner path's expert "
                    "telemetry — incompatible with paged=False")
            self.expert_cache = ExpertCache(
                cfg.n_layers, self.runner.padded_experts(),
                int(expert_cache_size),
                self.runner.expert_weight_bytes(
                    jnp.dtype(self.dtype).itemsize),
                prefetch=expert_prefetch,
                adaptive=(expert_placement == "adaptive"))
        # telemetry is opt-in: it adds a third output to the jitted
        # dispatch, so engines without EP or a cache keep the 2-tuple
        self._moe_stats = ((not self.dense_baseline) and cfg.n_experts > 0
                           and (self._expert_axis is not None
                                or self.expert_cache is not None))

        # prefill chunk buckets; always include max_seq so any admissible
        # prompt fits some bucket
        bks = sorted({min(b, max_seq) for b in prefill_buckets} | {max_seq})
        self.prefill_buckets = tuple(bks)
        if self.paged:
            # price every bucket against the q-tiled kernel's VMEM scratch
            # budget NOW — an oversized tile would otherwise OOM only on
            # TPU, deep inside the first prefill dispatch
            g = max(1, cfg.n_heads // max(1, cfg.n_kv_heads))
            for b in self.prefill_buckets:
                t = pf_kernel.resolve_q_tile(b, g, cfg.hd, block_size,
                                             self.q_tile)
                need = pf_kernel.q_tile_vmem_bytes(t, g, cfg.hd, block_size)
                if need > pf_kernel.DEFAULT_VMEM_BUDGET:
                    raise ValueError(
                        f"prefill bucket {b} needs a [{t}*{g}, {cfg.hd}] "
                        f"query tile = {need} VMEM bytes, over the kernel "
                        f"budget ({pf_kernel.DEFAULT_VMEM_BUDGET}); shrink "
                        f"the q_tile knob (or leave it None for the "
                        f"VMEM-budget auto tile) or drop the bucket from "
                        f"prefill_buckets")
        self.max_tokens_per_tick = (max_tokens_per_tick if max_tokens_per_tick
                                    else slots + self.prefill_buckets[-1])
        if self.max_tokens_per_tick < self.prefill_buckets[0]:
            # a decode-role engine under swap-only preemption never runs a
            # prefill chunk (handoff admission and swap restores insert
            # pages directly), so its budget only has to cover decodes
            if not (role == "decode" and preempt_policy == "swap"):
                raise ValueError(
                    f"max_tokens_per_tick={self.max_tokens_per_tick} can "
                    f"never afford the smallest prefill bucket "
                    f"({self.prefill_buckets[0]}); no request could ever "
                    f"start (role='decode' with preempt_policy='swap' is "
                    f"exempt: it admits handoffs, never prefill chunks)")

        if preempt_policy not in ("swap", "recompute", "auto"):
            raise ValueError(
                f"preempt_policy must be 'swap', 'recompute' or 'auto', "
                f"got {preempt_policy!r}")
        self.preempt_policy = preempt_policy

        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"role must be None, 'prefill' or 'decode', got {role!r}")
        if role is not None and self.dense_baseline:
            raise ValueError(
                "role-restricted engines hand KV progress across workers "
                "— the dense-slab baseline (paged=False) has no "
                "extract/insert page path; serve it monolithic")
        self.role = role

        self.class_weights = dict(CLASS_WEIGHTS)
        if class_weights:
            self.class_weights.update(class_weights)
        if any(w <= 0 for w in self.class_weights.values()):
            raise ValueError(f"class weights must be positive: "
                             f"{self.class_weights}")
        # admission order: heaviest class first, name-stable on ties
        self.class_order = tuple(sorted(
            self.class_weights, key=lambda c: (-self.class_weights[c], c)))
        self.class_deadlines_ms = dict(class_deadlines_ms or {})
        unknown = set(self.class_deadlines_ms) - set(self.class_weights)
        if unknown:
            raise ValueError(
                f"class_deadlines_ms names unknown classes {sorted(unknown)}"
                f"; this engine serves {sorted(self.class_weights)}")
        # deficit-weighted round-robin credit per class (fresh admissions;
        # restores bypass it — they outrank all fresh work of their class)
        self._deficit: Dict[str, float] = {
            cls: 0.0 for cls in self.class_order}
        self.proactive_horizon = int(proactive_horizon)
        if self.proactive_horizon < 0:
            raise ValueError(
                f"proactive_horizon must be >= 0, got {proactive_horizon}")

        if self.paged:
            self.block_size = block_size
            self.blocks_per_slot = -(-max_seq // block_size)
            S = self.seq_shards
            if num_blocks is None:
                # +1 null page per shard; usable capacity is identical for
                # every shard count (slots * blocks_per_slot)
                num_blocks = S + slots * self.blocks_per_slot
                num_blocks = S * (-(-num_blocks // S))
            elif num_blocks % S:
                num_blocks = S * (-(-num_blocks // S))   # round up to shards
            self.alloc = BlockAllocator(num_blocks, block_size, slots,
                                        self.blocks_per_slot, num_shards=S)
            self.state = self.runner.init_state(num_blocks, block_size,
                                                self.dtype)
        elif not self.dense_baseline:
            # slot-state-only runner path: no page pool at all
            self.state = self.runner.init_state(0, block_size, self.dtype)
        else:
            self.state = self.runner.init_dense_state(self.dtype)
        if self.mesh is not None:
            # commit pages and params to the mesh once, as the shard_map
            # dispatch lays them out: each seq shard's pages on its own
            # device (not gathered on device 0 and resharded every call)
            from jax.sharding import NamedSharding, PartitionSpec as P
            pspec, sspec, _, _ = self._shard_specs()

            def place(tree, spec):
                return jax.device_put(tree, jax.tree.map(
                    lambda sp: NamedSharding(self.mesh, sp), spec,
                    is_leaf=lambda sp: isinstance(sp, P)))

            self.state = place(self.state, sspec)
            self.params = place(self.params, pspec)
        self._slot_state_bytes = (self.runner.slot_state_bytes(self.state)
                                  if self.has_slot_state else 0)
        self._n_apps = self.runner.attn_applications if self.paged else 0

        self.lengths = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        # one FIFO deque per latency class (O(1) admission pops even under
        # thousand-request arrival streams; the old list.pop(0) was O(n));
        # admission drains them in class_order, age-ordered within a class
        self._queues: Dict[str, Deque[Request]] = {
            cls: deque() for cls in self.class_order}
        # preempted requests await re-admission here with priority over
        # same-or-lower-class submissions (no starvation: a victim can
        # never be queue-jumped by equal work competing for the pages it
        # was evicted to free; a strictly heavier class may jump a parked
        # lighter victim — that is the SLO contract)
        self.restore_queue: Deque[Request] = deque()
        self.swap_pages = (swap_pages if swap_pages is not None
                           else (slots * self.blocks_per_slot
                                 if self.paged else 0))
        self._arena = None              # serve.swap.SwapArena, lazily built
        self._rid = itertools.count()
        # rid -> Request for the async future API (futures poll by rid;
        # entries persist after finish so .result() works post-drain)
        self._reqs: Dict[int, Request] = {}
        self._tick = 0
        self._stalled_this_tick = False
        self.class_stats: Dict[str, Dict[str, float]] = {
            cls: self._zero_class_stats() for cls in self.class_order}
        self.stats: Dict[str, float] = {
            "prefill_traces": 0, "decode_traces": 0, "ticks": 0,
            "prefill_tokens": 0, "decode_tokens": 0, "occupancy_sum": 0.0,
            # stall_events counts per-slot waits (a tick can log several);
            # stalled_ticks is the once-per-tick roll-up, so
            # stalled_ticks <= ticks always holds.  padded_tokens is the
            # per-tick budget actually charged (prefill buckets + decode
            # tokens) — its per-tick delta never exceeds
            # max_tokens_per_tick on the paged path.
            # prefill_dispatches counts chunk launches (dense: whole-prompt
            # prefills) — the fewer-fatter-dispatches win of big buckets
            # shows up here while prefill_tokens stays identical
            "stalled_ticks": 0, "stall_events": 0, "padded_tokens": 0,
            "prefill_dispatches": 0,
            "preemptions": 0, "preempt_proactive": 0,
            # progress-preserving preemption: every preemption is a swap or
            # a recompute (restart-preemptions are gone); preempted_tokens
            # counts KV tokens live at eviction, restored_tokens the part
            # re-attached without replay (swap-in or prefix-cache hit)
            "preempt_swaps": 0, "preempt_recomputes": 0, "swap_bytes": 0,
            "swap_demotions": 0,
            "preempted_tokens": 0, "restored_tokens": 0,
            # prefix caching + page-gather accounting (paged mode)
            "prefix_hits": 0, "prefix_hit_tokens": 0, "cow_copies": 0,
            "pages_allocated": 0, "pages_freed": 0, "pages_shared": 0,
            "pages_evicted": 0,
            "gather_pages_calls": 0, "gather_page_volume": 0,
            # in-transit NoC combine accounting (sequence-sharded serving):
            # one tree_softmax_combine per attention application per
            # dispatched decode tick / prefill chunk, costed by
            # core.noc.softmax_combine_cost
            "noc_combines": 0, "noc_hops": 0, "noc_bytes": 0,
            "noc_energy_pj": 0.0,
            # expert-placement telemetry (MoE, opt-in via expert_parallel
            # or expert_cache_size): expert_load is the cumulative routed
            # token count per padded expert (summed over layers);
            # expert_skew = max load / mean load, expert_gini the Gini
            # coefficient of the per-expert loads; the expert_* cache
            # counters mirror serve/expert_cache.py's accounting
            "expert_load": (np.zeros(self.runner.padded_experts())
                            if self._moe_stats else 0.0),
            "expert_routed_tokens": 0, "expert_dropped_tokens": 0.0,
            "expert_skew": 0.0, "expert_gini": 0.0,
            "expert_hits": 0.0, "expert_misses": 0.0,
            "expert_sram_hit_rate": 0.0,
            "expert_migrations": 0, "expert_migration_bytes": 0,
            "expert_prefetches": 0,
            # capacity accounting: kv_bytes_per_page is the static cost of
            # ONE physical page at the engine's kv_dtype (int8: 1-byte
            # values + per-page scales); peak_active is the high-water mark
            # of concurrently occupied slots — the behavioral concurrency a
            # byte-budgeted pool sustains
            "kv_bytes_per_page": self._page_kv_bytes() if self.paged else 0,
            "peak_active": 0,
            # disaggregated serving (role-restricted engines): handoffs is
            # decode-side admissions from a HandoffHandle; handoff_stalls
            # counts admission attempts deferred by decode-pool pressure
            # (the backpressure arm of noc.handoff_admission_cost).
            # slo_violations counts finished requests that missed their
            # effective deadline (per-request deadline_ms, else the
            # class_deadlines_ms entry for their class)
            "handoffs": 0, "handoff_stalls": 0, "slo_violations": 0,
        }
        self._prefill_fns: Dict[int, object] = {}
        self._decode = self._make_decode_fn()
        self._copy_page = (jax.jit(self.runner.copy_page)
                           if self.paged else None)
        # page-swap device halves; page-id args are padded to power-of-two
        # buckets so each jit specializes O(log max_pages) times
        self._extract_pages = (jax.jit(self.runner.extract_pages)
                               if self.paged else None)
        self._insert_pages = (jax.jit(self.runner.insert_pages)
                              if self.paged else None)
        # slot-state lifecycle half of the contract: a fresh admission (or
        # a recompute restore) zeroes its slot's recurrent state rows
        self._reset_slot = (jax.jit(self.runner.reset_slot)
                            if self.has_slot_state else None)

    @staticmethod
    def _zero_class_stats() -> Dict[str, float]:
        return {"submitted": 0, "finished": 0, "finished_tokens": 0,
                "preemptions": 0, "slo_violations": 0}

    @property
    def queue(self) -> List[Request]:
        """Queued-but-unadmitted requests, class-major and age-ordered
        within a class.  A read-only snapshot for introspection — actual
        admission interleaves classes by deficit-weighted round-robin
        (see :meth:`_admit`), so this listing is not the admission
        order under contention."""
        return [r for cls in self.class_order for r in self._queues[cls]]

    @property
    def queued(self) -> int:
        """Number of queued-but-unadmitted requests (O(#classes))."""
        return sum(len(q) for q in self._queues.values())

    # -- jit caches ----------------------------------------------------
    def _shard_specs(self):
        """(param, state, table, estats) partition specs for the engine
        mesh.  State and block tables shard over ``seq`` only (every
        expert shard holds the full KV slice of its seq shard); expert
        params shard their leading expert axis over ``expert``; routing
        is replicated so the telemetry comes back replicated (``P()``)."""
        from jax.sharding import PartitionSpec as P
        seq = self.seq_shards > 1
        sspec = self.runner.state_partition_specs("seq") if seq else P()
        pspec = (self.runner.expert_param_specs(self.params,
                                                self._expert_axis)
                 if self._expert_axis else P())
        tspec = P("seq") if seq else P()
        return pspec, sspec, tspec, P()

    def _make_decode_fn(self):
        cfg, runner = self.cfg, self.runner
        estats, eax = self._moe_stats, self._expert_axis

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P
            seq = self.seq_shards > 1
            pspec, sspec, tspec, espec = self._shard_specs()

            def body(params, state, toks, lens, tables, mask):
                # seq-sharded tables arrive [1, B, MB] (this shard's slice)
                return runner.decode(params, state, toks, lens,
                                     tables[0] if seq else tables, mask,
                                     seq_axis="seq" if seq else None,
                                     expert_axis=eax, expert_stats=estats)

            smapped = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(pspec, sspec, P(), P(), tspec, P()),
                out_specs=(espec, sspec) + ((espec,) if estats else ()),
                check_vma=False)

            def f(params, state, toks, lens, tables, mask):
                self.stats["decode_traces"] += 1
                return smapped(params, state, toks, lens, tables, mask)
        elif not self.dense_baseline:
            # runner path, unsharded: tables is None for slot-state-only
            # families (no paged component to address)
            def f(params, state, toks, lens, tables, mask):
                self.stats["decode_traces"] += 1
                return runner.decode(params, state, toks, lens, tables, mask,
                                     expert_stats=estats)
        else:
            def f(params, state, toks, lens, tables, mask):
                self.stats["decode_traces"] += 1
                return M.decode_step(cfg, params, state, toks, lens)
        return jax.jit(f)

    def _prefill_fn(self, bucket: int):
        """One compiled prefill per bucket, cached for the engine lifetime
        (the seed engine re-traced ``jax.jit(lambda ...)`` on every
        admission)."""
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        cfg, dtype, max_seq = self.cfg, self.dtype, self.max_seq
        runner = self.runner

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P
            seq = self.seq_shards > 1
            estats, eax = self._moe_stats, self._expert_axis
            pspec, sspec, tspec, espec = self._shard_specs()

            def body(params, state, toks, length, q_offset, bt, slot):
                return runner.prefill_chunk(params, state, toks, length,
                                            q_offset,
                                            bt[0] if seq else bt, slot,
                                            seq_axis="seq" if seq else None,
                                            expert_axis=eax,
                                            expert_stats=estats)

            smapped = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(pspec, sspec, P(), P(), P(), tspec, P()),
                out_specs=(espec, sspec) + ((espec,) if estats else ()),
                check_vma=False)

            def f(params, state, toks, length, q_offset, bt_row, slot):
                self.stats["prefill_traces"] += 1
                return smapped(params, state, toks, length, q_offset, bt_row,
                               slot)
        elif not self.dense_baseline:
            estats = self._moe_stats

            def f(params, state, toks, length, q_offset, bt_row, slot):
                self.stats["prefill_traces"] += 1
                return runner.prefill_chunk(params, state, toks, length,
                                            q_offset, bt_row, slot,
                                            expert_stats=estats)
        else:
            def f(params, toks, lens):
                self.stats["prefill_traces"] += 1
                one = M.init_decode_state(cfg, 1, max_seq, dtype=dtype)
                return M.prefill(cfg, params, one, tokens=toks, lengths=lens)
        fn = jax.jit(f)
        self._prefill_fns[bucket] = fn
        return fn

    # -- submission ----------------------------------------------------
    def submit(self, prompt, **kw) -> "RequestFuture":
        """Queue one generation request; returns a :class:`RequestFuture`
        (an ``int`` subclass carrying the request id, so legacy callers
        that treat the return value as a rid keep working unchanged).

        ``prompt`` is a sequence of token ids in ``[0, vocab_size)``;
        keyword args fill the :class:`Request` fields (``max_new_tokens``,
        ``temperature``, ``eos_id``, ``priority`` — the latency class,
        one of the engine's ``class_weights`` keys — and ``deadline_ms``,
        a per-request SLO deadline overriding the class default).
        Validation is up-front and loud: empty or out-of-vocab prompts
        raise (out-of-vocab ids would embed as NaN and poison recycled
        pages), as do unknown latency classes and a request that could
        never fit the page pool even alone (it would stall the engine
        forever).  With prefix caching on, the chained page digests are
        computed here so admission can pin the longest cached prefix.

        A ``role="decode"`` engine refuses plain submissions — it admits
        work exclusively through :meth:`submit_handoff`."""
        if self.role == "decode":
            raise RuntimeError(
                "decode-role engine admits handoffs only; submit prompts "
                "to the prefill role (or the DisaggServer front door)")
        # defensive copy: np.asarray is zero-copy for an int32 ndarray, so
        # caller-side mutation after submit would silently corrupt the
        # queued prompt, its page digests, and the chunked-prefill source
        prompt = np.array(prompt, np.int32, copy=True)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab_size:
            # out-of-vocab ids would embed as NaN (jnp OOB gather fills),
            # and NaN in recycled pages poisons later occupants' masked
            # attention sums (0 * NaN) — fail loudly instead
            raise ValueError(
                f"token ids must be in [0, {self.cfg.vocab_size}); got "
                f"range [{prompt.min()}, {prompt.max()}]")
        req = Request(next(self._rid), prompt, **kw)
        if req.priority not in self.class_weights:
            raise ValueError(
                f"unknown latency class {req.priority!r}; this engine "
                f"serves {sorted(self.class_weights)}")
        req._t_submit = time.perf_counter()
        req.submit_tick = self._tick
        if self.paged:
            # a request that cannot ever fit the pool alone would cycle
            # through preemption forever — reject it loudly up front
            pages = -(-min(self._plen(req) + req.max_new_tokens,
                           self.max_seq) // self.block_size)
            usable = self.alloc.usable_blocks
            if pages > usable:
                raise ValueError(
                    f"request needs up to {pages} KV pages but the pool has "
                    f"only {usable}; raise num_blocks or shrink the request")
            if self.prefix_caching:
                # chained digest per full prompt page; the longest cached
                # chain is matched (and its pages pinned) at admission time,
                # so a hit can never dangle across an eviction while queued
                req._digests = _page_digests(
                    prompt, self.block_size,
                    self._plen(req) // self.block_size,
                    seed=self._digest_seed)
        self.class_stats[req.priority]["submitted"] += 1
        self._reqs[req.rid] = req
        self._queues[req.priority].append(req)
        return RequestFuture(req.rid, self)

    def submit_handoff(self, handle) -> "RequestFuture":
        """Enqueue one staged prefill (a :class:`serve.swap.HandoffHandle`)
        for decode-side admission.  Decode-role engines admit exclusively
        through this door; a monolithic engine accepts handoffs too (used
        by tests to exercise the round trip in isolation).

        The handle's rid is **adopted** — the decode-role engine's own rid
        counter is never consumed (``submit()`` raises), so prefill-side
        rids stay globally unique and the future returned here is
        interchangeable with the one the DisaggServer front door returned
        at submission time.  No token is sampled or replayed here: the
        handle's ``out_tokens`` already hold everything the prefill side
        sampled, and decode resumes by feeding the last of them."""
        if self.role == "prefill":
            raise RuntimeError("prefill-role engine cannot admit handoffs")
        req = Request(int(handle.rid), np.array(handle.prompt, np.int32),
                      max_new_tokens=handle.max_new_tokens,
                      temperature=handle.temperature,
                      eos_id=handle.eos_id, priority=handle.priority,
                      deadline_ms=handle.deadline_ms)
        if req.priority not in self.class_weights:
            raise ValueError(
                f"unknown latency class {req.priority!r}; this engine "
                f"serves {sorted(self.class_weights)}")
        req.out_tokens = list(handle.out_tokens)
        req._digests = list(handle.digests)
        req._handoff = handle
        req._t_submit = handle.t_submit or time.perf_counter()
        req.ttft = handle.ttft
        req.submit_tick = self._tick
        self.class_stats[req.priority]["submitted"] += 1
        self._reqs[req.rid] = req
        self._queues[req.priority].append(req)
        return RequestFuture(req.rid, self)

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _plen(self, req: Request) -> int:
        return max(1, min(len(req.prompt),
                          self.max_seq - req.max_new_tokens - 1))

    def _prefill_target(self, req: Request) -> int:
        """Tokens that must be in the KV cache before ``req`` can decode.
        Normally the clamped prompt length; for a decode-phase preemption
        victim it is ``resume_len`` (prompt + already-decoded tokens)."""
        if req.out_tokens and req.resume_len:
            return req.resume_len
        return self._plen(req)

    def _prefill_source(self, req: Request) -> np.ndarray:
        """Token sequence chunked prefill reads from: the prompt, or for a
        decode-phase restore the preserved ``prompt + out_tokens[:-1]``."""
        if req.out_tokens and req.resume_len:
            return req._resume_tokens
        return req.prompt

    # -- scheduling ----------------------------------------------------
    def _admit(self) -> None:
        """Move queued requests into free slots (no token cost; the prefill
        work is budgeted separately in _prefill_tick).

        Two phases.  **Restores first**, class-ordered: for each latency
        class in descending weight, preempted requests of that class
        re-admit FIFO among themselves.  A restore that cannot be placed
        yet (swap-in waiting for enough free pages) blocks everything of
        its own and every lighter class behind it — equal-or-lower work
        must not grab the pages a victim was evicted to free, or the
        victim starves — while a strictly heavier class may still jump a
        parked lighter victim (the SLO contract).

        **Fresh submissions** then admit by deficit-weighted round-robin
        over ``class_weights``: each class accrues credit proportional to
        its weight and spends one credit per admission, so sustained
        contention converges to weight-proportional goodput shares
        (weights 8:1 admit ~8 interactive per batch) and no positive-
        weight class is ever fully starved — unlike strict class-then-age
        order, where an unbroken heavy-class arrival stream starves
        lighter classes forever.  When queues drain between bursts a
        class's credit resets, so an idle engine still admits in plain
        class-then-age order (burst arrivals into an idle engine see the
        heaviest class go first).  Classes at or below the restore
        barrier are excluded from the rotation.

        A queue head carrying a :class:`~serve.swap.HandoffHandle` admits
        through :meth:`_admit_handoff`; if the decode pool cannot take it
        yet, the head stays put (age order within the class is
        preserved), the class barriers like a blocked restore, and
        ``stats["handoff_stalls"]`` counts the deferral — that is the
        backpressure arm priced by ``noc.handoff_admission_cost``.  With
        prefix caching the prompt's longest cached page-prefix is
        attached here and the chunked prefill starts at the first
        uncached token."""
        barrier = 0.0          # classes with weight <= barrier are blocked
        for cls in self.class_order:
            w = self.class_weights[cls]
            if w <= barrier:
                continue
            for req in [r for r in self.restore_queue if r.priority == cls]:
                slot = self._free_slot()
                if slot is None:
                    return
                if not self._restore(slot, req):
                    # this victim (and everything lighter) waits for pages
                    barrier = max(barrier, w)
                    break
                self.restore_queue.remove(req)
        # deficit round-robin over classes with queued fresh work
        for cls in self.class_order:
            if not self._queues[cls]:
                self._deficit[cls] = 0.0    # credit does not accrue idle
        while True:
            cand = [c for c in self.class_order
                    if self._queues[c] and self.class_weights[c] > barrier]
            if not cand:
                return
            slot = self._free_slot()
            if slot is None:
                return
            if all(self._deficit[c] < 1.0 for c in cand):
                for c in cand:
                    self._deficit[c] += self.class_weights[c]
            cls = max(cand, key=lambda c: (self._deficit[c],
                                           self.class_weights[c]))
            q = self._queues[cls]
            req = q[0]
            if req._handoff is not None:
                if not self._admit_handoff(slot, req):
                    # decode pool full: head waits (keeping class age
                    # order), nothing lighter may take its pages
                    self.stats["handoff_stalls"] += 1
                    barrier = max(barrier, self.class_weights[cls])
                    continue
                q.popleft()
            else:
                q.popleft()
                req.prefill_pos = 0
                req.cached_len = 0
                req._published = 0
                self.active[slot] = req
                self.lengths[slot] = 0
                if self.has_slot_state:
                    # the previous occupant's state must not leak
                    self.state = self._reset_slot(self.state,
                                                  jnp.int32(slot))
                if self.prefix_attach:
                    self._attach_prefix(slot, req)
            self._deficit[cls] -= 1.0

    def _admit_handoff(self, slot: int, req: Request) -> bool:
        """Adopt one staged prefill into ``slot``: share its prefix-cached
        pages by reference, allocate device pages for the transferred
        remainder, copy the remainder (and any recurrent slot-state blob)
        out of the staging arena, and resume decode at exactly the staged
        position.  False if the pool cannot take it yet — all-or-nothing,
        like a swap restore: a half-adopted handoff could neither decode
        nor release the arena.  On success the transferred full pages are
        registered under their digests, so a later handoff of the same
        prompt prefix transfers only its uncached remainder."""
        handle = req._handoff
        n_pub = 0
        if self.paged:
            # need enough pages for the chain remainder now AND at least
            # one decode step of headroom (mirrors _restore_swapped)
            need = handle.n_pages
            grow = -(-(handle.tokens + 1) // self.block_size)
            if self.alloc.free_blocks < max(need, grow - len(handle.cached)):
                return False
            self.active[slot] = req
            for page in handle.cached:
                self.alloc.share(slot, page)
            fresh: List[int] = []
            for _ in range(need):
                page = self.alloc.alloc_page(slot)
                if page is None:
                    # raced with nothing (single-threaded) but shard
                    # rounding can strand pages: roll back whole
                    self.alloc.release(slot)
                    self.active[slot] = None
                    return False
                fresh.append(page)
            if fresh:
                if self.kv_dtype == "int8":
                    k, v, ks, vs = handle.arena.read(handle.slots)
                else:
                    k, v = handle.arena.read(handle.slots)
                for sh, idx in self._by_shard(fresh):
                    ids = self._pad_pow2([fresh[i] for i in idx])
                    args = [jnp.asarray(ids),
                            jnp.asarray(self._pad_pages(
                                np.moveaxis(k[idx], 0, 2))),
                            jnp.asarray(self._pad_pages(
                                np.moveaxis(v[idx], 0, 2)))]
                    if self.kv_dtype == "int8":
                        args += [jnp.asarray(self._pad_pages(
                                     np.moveaxis(ks[idx], 0, 2))),
                                 jnp.asarray(self._pad_pages(
                                     np.moveaxis(vs[idx], 0, 2)))]
                    self.state = self._insert_pages(self.state, *args)
            # register transferred FULL pages so the next handoff (or a
            # local prefix hit) of this prompt skips the transfer
            n_pub = len(handle.cached)
            if self.prefix_caching:
                full = handle.tokens // self.block_size
                chain = self.alloc.table[slot]
                for i in range(n_pub, min(full, len(handle.digests))):
                    self.alloc.register(int(chain[i]), handle.digests[i])
                    n_pub = i + 1
            # drop the staging refcounts taken when the match was made
            for page in handle.cached:
                self.alloc.unpin(page)
            handle.arena.free(handle)
        if handle.state is not None:
            # the blob covers every slot-state key, so no reset is needed
            self.state = self.runner.insert_slot_state(
                self.state, slot, handle.state)
        elif self.has_slot_state:
            self.state = self._reset_slot(self.state, jnp.int32(slot))
        self.active[slot] = req
        plen = self._plen(req)
        req.prefill_pos = handle.tokens
        req.cached_len = handle.tokens
        req.resume_len = handle.tokens
        req._resume_tokens = req.prompt[:plen].astype(np.int32)
        req._published = n_pub if self.paged else 0
        req._handoff = None
        self.lengths[slot] = handle.tokens
        req.first_tick = self._tick
        req._t_first = time.perf_counter()
        self.stats["handoffs"] += 1
        return True

    def _attach_prefix(self, slot: int, req: Request) -> None:
        """Pin the longest registered page chain matching ``req``'s prompt.

        Full matched pages are shared by reference.  The match is capped at
        ``plen - 1`` so at least one token is always recomputed (the final
        logits must be produced by a prefill chunk); when that cap lands
        mid-page, the trailing shared page is duplicated copy-on-write and
        its tail re-written by the resuming prefill."""
        plen = self._plen(req)
        pages: List[int] = []
        for dg in req._digests:
            page = self.alloc.lookup(dg)
            if page is None:
                break
            pages.append(page)
        match = min(len(pages) * self.block_size, plen - 1)
        if match <= 0:
            return
        n_full = match // self.block_size
        for page in pages[:n_full]:
            self.alloc.share(slot, page)
        if match > n_full * self.block_size:
            # the cap fell inside pages[n_full]: COW it so the rewrite of
            # position ``match`` cannot corrupt other readers
            dst = self.alloc.alloc_page(slot)
            if dst is None:
                match = n_full * self.block_size     # no room: aligned match
            else:
                self.state = self._copy_page(self.state,
                                             jnp.int32(pages[n_full]),
                                             jnp.int32(dst))
                self.stats["cow_copies"] += 1
        if match <= 0:
            return
        req.prefill_pos = match
        req.cached_len = match
        req._published = match // self.block_size
        self.lengths[slot] = match
        self.stats["prefix_hits"] += 1
        self.stats["prefix_hit_tokens"] += match

    def _restore(self, slot: int, req: Request) -> bool:
        """Re-admit a preempted request into ``slot``, re-attaching its
        preserved progress; False if it cannot be placed yet (swap-in
        short of free pages — the caller retries next tick).

        Swap victims get their exact pages copied back from the host arena
        (all-or-nothing, so a half-restored slot can never join a
        deadlock).  Recompute victims re-enter like a fresh admission
        except (a) the cached chain re-attached may extend over *decoded*
        pages (published at preemption), and (b) any remaining gap is
        re-prefilled from ``prompt + out_tokens`` — so decode resumes at
        the preempted position either way, never replaying a sampled
        token."""
        if req._swap is not None:
            return self._restore_swapped(slot, req)
        self.active[slot] = req
        self.lengths[slot] = 0
        req.prefill_pos = 0
        req.cached_len = 0
        req._published = 0
        if self.has_slot_state:
            # recompute restore replays the family's prefill from token 0
            # — the recurrent state rebuilds from zero alongside the pages
            self.state = self._reset_slot(self.state, jnp.int32(slot))
        if self.prefix_attach:
            hit0 = self.stats["prefix_hit_tokens"]
            if req.out_tokens:
                self._attach_resume(slot, req)
            else:
                self._attach_prefix(slot, req)
            # "restored" = preserved progress that skipped replay, capped
            # at what THIS victim actually held at eviction — an attach can
            # exceed that via pages other requests published (an ordinary
            # prefix hit, not preservation), and a zero-progress victim
            # restores nothing; keeps restored_tokens <= preempted_tokens
            self.stats["restored_tokens"] += min(
                self.stats["prefix_hit_tokens"] - hit0,
                req._preempted_live)
        return True

    def _attach_resume(self, slot: int, req: Request) -> None:
        """Pin the cached page chain of a decode-phase preemption victim.

        Unlike :meth:`_attach_prefix` the chain may cover decoded-token
        pages and there is no ``plen - 1`` cap — the victim's next logits
        come from feeding ``out_tokens[-1]`` through decode, not from a
        prefill chunk — and only *full* pages were published at preemption,
        so the match is always page-aligned (no COW)."""
        attached = 0
        for dg in req._digests[:req.resume_len // self.block_size]:
            page = self.alloc.lookup(dg)
            if page is None or not self.alloc.share(slot, page):
                break
            attached += 1
        match = attached * self.block_size
        req.prefill_pos = match
        req.cached_len = match
        req._published = attached
        self.lengths[slot] = match
        if match:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += match

    def _restore_swapped(self, slot: int, req: Request) -> bool:
        """Swap-in: re-attach the handle's pinned registered prefix-chain
        pages *by reference*, allocate fresh device pages only for the
        arena-parked remainder and copy those back (per-shard batched),
        then re-insert the recurrent slot-state blob (families that carry
        one).  All-or-nothing; False when the pool cannot grant the full
        remainder yet."""
        handle = req._swap
        need = handle.n_pages              # unpinned pages to copy back
        n_pin = len(handle.pinned)
        # demand headroom for the next token (decode or resume-prefill)
        # too: restoring into an instant page stall would only re-enter
        # the preemption loop (pinned pages never touch the free pool)
        if self.alloc.free_blocks < max(
                need, -(-(handle.tokens + 1) // self.block_size) - n_pin):
            return False
        self.active[slot] = req
        # pinned chain first: logical blocks [0, n_pin) re-attach by
        # reference — share() adds the slot's refcount on top of the
        # handle's, so a mid-restore rollback can release() uniformly
        for page in handle.pinned:
            self.alloc.share(slot, page)
        pages: List[int] = []
        for _ in range(need):
            page = self.alloc.alloc_page(slot)
            if page is None:            # raced below free_blocks: roll back
                self.alloc.release(slot)
                self.active[slot] = None
                return False
            pages.append(page)
        if pages:
            if self.kv_dtype == "int8":
                k, v, ks, vs = self._arena.read(handle.slots)
            else:
                k, v = self._arena.read(handle.slots)
            for sh, idx in self._by_shard(pages):
                ids = self._pad_pow2([pages[i] for i in idx])
                args = [jnp.asarray(ids),
                        jnp.asarray(self._pad_pages(np.moveaxis(k[idx], 0, 2))),
                        jnp.asarray(self._pad_pages(np.moveaxis(v[idx], 0, 2)))]
                if self.kv_dtype == "int8":
                    args += [
                        jnp.asarray(self._pad_pages(np.moveaxis(ks[idx], 0, 2))),
                        jnp.asarray(self._pad_pages(np.moveaxis(vs[idx], 0, 2)))]
                self.state = self._insert_pages(self.state, *args)
        if handle.state is not None:
            self.state = self.runner.insert_slot_state(self.state, slot,
                                                       handle.state)
        self.stats["swap_bytes"] += (need * self._page_kv_bytes()
                                     + handle.state_bytes)
        self.stats["restored_tokens"] += handle.tokens
        # the restored coverage is [0, tokens); any gap up to the resume
        # target (possible after a mid-restore re-preemption) is
        # re-prefilled from _resume_tokens like the recompute arm
        req.prefill_pos = handle.tokens
        req.cached_len = handle.tokens
        for page in handle.pinned:
            self.alloc.unpin(page)      # the slot's own reference remains
        handle.pinned = []
        if handle.slots:
            self._arena.free(handle)
        req._swap = None
        # the restored rows hold the same content the digests commit to,
        # so publishing may resume where it left off
        req._published = min(req._published, n_pin + need)
        self.lengths[slot] = req.prefill_pos
        return True

    def _by_shard(self, pages: List[int]):
        """Group positions of ``pages`` by owning shard (swap copies are
        batched per shard so each touches one shard's pool slice)."""
        groups: Dict[int, List[int]] = {}
        for i, p in enumerate(pages):
            groups.setdefault(self.alloc.owner(p), []).append(i)
        return sorted(groups.items())

    @staticmethod
    def _pad_pow2(ids: List[int]) -> np.ndarray:
        """Pad a page-id list to the next power of two with the null page 0
        (gathers of it are discarded; scatters to it are harmless)."""
        out = np.zeros((_next_pow2(len(ids)),), np.int32)
        out[:len(ids)] = ids
        return out

    @staticmethod
    def _pad_pages(kv: np.ndarray) -> np.ndarray:
        """Zero-pad the page axis (2) of ``[L, KvH, P, BS, hd]`` pages (or
        ``[L, KvH, P]`` scales) to pow2 to match :meth:`_pad_pow2`'s id
        padding."""
        p = kv.shape[2]
        b = _next_pow2(p)
        if b == p:
            return kv
        pad = [(0, 0)] * kv.ndim
        pad[2] = (0, b - p)
        return np.pad(kv, pad)

    def _publish_pages(self, slot: int, req: Request) -> None:
        """Register the slot's freshly completed full prompt pages so later
        prompts can share them (idempotent; duplicates are skipped).  After
        a recompute-preemption the digest chain extends over decoded-token
        pages, so replayed pages republish too."""
        n_done = min(req.prefill_pos // self.block_size, len(req._digests))
        while req._published < n_done:
            i = req._published
            self.alloc.register(int(self.alloc.table[slot, i]),
                                req._digests[i])
            req._published += 1

    def _page_bucket(self, n_pages: int) -> int:
        """Round a live page count up to the next power of two (capped at
        the per-slot maximum) — bounds prefill jit specializations to
        O(log max_blocks) block-table shapes."""
        return min(_next_pow2(n_pages), self.blocks_per_slot)

    def _prefill_tick(self, budget: int, finished: List[Request]) -> int:
        """Advance pending prefills under ``budget`` padded tokens.  Runner
        slots (paged *or* slot-state) move chunk-by-chunk and several can
        progress per tick; dense slabs cannot chunk, so that mode keeps
        the seed engine's admission rate (one monolithic prefill per tick
        — the A/B baseline).  Returns the unspent budget."""
        pending = [(slot, req) for slot, req in enumerate(self.active)
                   if req is not None
                   and req.prefill_pos < self._prefill_target(req)]
        if self.dense_baseline:
            for slot, req in pending[:1]:
                plen = self._plen(req)
                bucket = self._bucket(plen)
                logits = self._run_prefill_chunk(slot, req, bucket, plen)
                self.stats["prefill_tokens"] += plen
                self.stats["padded_tokens"] += bucket
                req.prefill_pos = plen
                self.lengths[slot] = plen
                self._finish_prefill(slot, req, logits, finished)
            return budget
        for slot, req in pending:
            plen = self._prefill_target(req)
            while req.prefill_pos < plen:
                remaining = plen - req.prefill_pos
                bucket = self._bucket(min(remaining, max(budget, 1)))
                if bucket > budget:
                    if bucket <= self.max_tokens_per_tick:
                        break                  # affordable on a richer tick
                    # the round-up bucket can NEVER fit the budget (it sits
                    # between two bucket sizes): chunk at the largest
                    # affordable bucket instead of stalling forever
                    afford = [b for b in self.prefill_buckets if b <= budget]
                    if not afford:
                        break                  # not affordable this tick
                    bucket = afford[-1]
                n = min(remaining, bucket)
                if self.paged and not self.alloc.ensure(
                        slot, req.prefill_pos + n):
                    self.stats["stall_events"] += 1
                    self._stalled_this_tick = True
                    break                      # pool exhausted; wait
                logits = self._run_prefill_chunk(slot, req, bucket, n)
                budget -= bucket
                self.stats["padded_tokens"] += bucket
                self.stats["prefill_tokens"] += n
                req.prefill_pos += n
                self.lengths[slot] = req.prefill_pos
                if self.prefix_caching:
                    self._publish_pages(slot, req)
                # a decode-phase restore that just completed discards the
                # chunk's logits: the next decode feeds out_tokens[-1]
                # (a sampled token is never re-sampled)
                if req.prefill_pos >= plen and not req.out_tokens:
                    self._finish_prefill(slot, req, logits, finished)
        return budget

    def _finish_prefill(self, slot: int, req: Request, logits,
                        finished: List[Request]) -> None:
        """Prompt fully cached: sample the first token; retire immediately
        on EOS / single-token requests."""
        first = self._sample(logits[0], req)
        req.out_tokens.append(int(first))
        req._t_first = time.perf_counter()
        req.ttft = req._t_first - req._t_submit
        req.first_tick = self._tick
        hit_eos = req.eos_id is not None and first == req.eos_id
        if hit_eos or req.max_new_tokens <= 1:
            self._finish(slot, req, finished)
        elif self.role == "prefill":
            # disaggregated prefill terminates HERE: the request parks with
            # its KV chain + first sampled token until the DisaggServer
            # stages it across (stage_handoff) — it never decodes locally
            req._await_handoff = True

    def _finish(self, slot: int, req: Request, finished: List[Request],
                ) -> None:
        """Retire a completed request: latency bookkeeping (wall + tick
        clocks), per-class goodput accounting, slot/page recycling."""
        req.done = True
        req.finish_tick = self._tick
        if len(req.out_tokens) > 1 and req._t_first:
            req.tpot = ((time.perf_counter() - req._t_first)
                        / (len(req.out_tokens) - 1))
        cs = self.class_stats[req.priority]
        cs["finished"] += 1
        cs["finished_tokens"] += len(req.out_tokens)
        # SLO accounting: per-request deadline_ms overrides the class
        # default; violations are counted at finish on the wall clock
        # (submit -> last token), the latency the caller actually saw
        dl = (req.deadline_ms if req.deadline_ms is not None
              else self.class_deadlines_ms.get(req.priority))
        if dl is not None and req._t_submit:
            if (time.perf_counter() - req._t_submit) * 1e3 > dl:
                self.stats["slo_violations"] += 1
                cs["slo_violations"] += 1
        finished.append(req)
        self._retire(slot)

    def _run_prefill_chunk(self, slot: int, req: Request, bucket: int,
                           n: int):
        self.stats["prefill_dispatches"] += 1
        padded = np.zeros((bucket,), np.int32)
        src = self._prefill_source(req)
        padded[:n] = src[req.prefill_pos:req.prefill_pos + n]
        fn = self._prefill_fn(bucket)
        if not self.dense_baseline:
            bt = None
            if self.paged:
                # pass only the live prefix of the block table (rounded up
                # to a power-of-two bucket so jit specializations stay
                # O(log MB)): per-chunk attention work is then bounded by
                # the cached length, not the pool size — the old path
                # handed the full MB row to a per-application gather_pages,
                # O(max_blocks) copies per chunk
                n_live = -(-(req.prefill_pos + n) // self.block_size)
                mb = self._page_bucket(n_live)
                bt = np.zeros((mb,), np.int32)
                u = min(int(self.alloc.used[slot]), mb)
                bt[:u] = self.alloc.table[slot, :u]
                S = self.seq_shards
                if S > 1:
                    bt = self.alloc.shard_local(bt)   # [S, mb] local tables
                    self._account_noc_combine(rows=bucket)
                if not ops.using_pallas():
                    # fallback linearizes k+v per attention application per
                    # chunk per shard (kernel: zero)
                    self.stats["gather_pages_calls"] += 2 * self._n_apps * S
                    self.stats["gather_page_volume"] += (2 * self._n_apps
                                                         * mb * S)
                bt = jnp.asarray(bt)
            out = fn(
                self.params, self.state, jnp.asarray(padded[None]),
                jnp.int32(n), jnp.int32(req.prefill_pos), bt,
                jnp.int32(slot))
            if self._moe_stats:
                logits, self.state, est = out
                self._account_expert(est, rows=bucket)
            else:
                logits, self.state = out
            return logits
        # dense baseline: single-sequence prefill scattered into the slab
        logits, one_state = fn(self.params, jnp.asarray(padded[None]),
                               jnp.array([n], jnp.int32))
        self.state = _scatter_slot(self.state, one_state, slot)
        return logits

    def _account_noc_combine(self, rows: int) -> None:
        """Accumulate the in-transit combine traffic one sharded dispatch
        performs: one tree_softmax_combine per attention application (L
        for transformers, G for the hybrid shared block), ``rows`` query
        rows each (slots for decode, the chunk bucket for prefill)."""
        cfg = self.cfg
        c = noc.softmax_combine_cost(rows, cfg.n_heads, cfg.hd,
                                     self.seq_shards)
        self.stats["noc_combines"] += self._n_apps
        self.stats["noc_hops"] += self._n_apps * c["hops"]
        self.stats["noc_bytes"] += self._n_apps * c["bytes"]
        self.stats["noc_energy_pj"] += self._n_apps * c["energy_pj"]

    def _account_expert(self, est, rows: int) -> None:
        """Fold one dispatch's expert telemetry (``est`` from the jitted
        path: per-layer per-expert routed counts + drop fraction) into the
        engine stats and, when configured, the placement cache.  ``rows``
        is the dispatch's token rows (runnable slots for decode, the chunk
        bucket for prefill) — padded rows route too, so they count."""
        load = np.asarray(est["expert_load"], np.float64)   # [L, E_pad]
        cfg = self.cfg
        self.stats["expert_load"] = self.stats["expert_load"] + load.sum(0)
        self.stats["expert_routed_tokens"] += rows
        self.stats["expert_dropped_tokens"] += (float(est["frac_dropped"])
                                                * rows * cfg.top_k)
        cum = self.stats["expert_load"][:cfg.n_experts]
        tot = float(cum.sum())
        if tot > 0.0:
            self.stats["expert_skew"] = float(cum.max() * cum.size / tot)
            self.stats["expert_gini"] = _gini(cum)
        if self.expert_cache is not None:
            tick = self.expert_cache.observe(load)
            self.stats["expert_hits"] += tick["hits"]
            self.stats["expert_misses"] += tick["misses"]
            self.stats["expert_migrations"] += tick["migrations"]
            self.stats["expert_migration_bytes"] += tick["migration_bytes"]
            self.stats["expert_prefetches"] += tick["prefetches"]
            self.stats["expert_sram_hit_rate"] = \
                self.expert_cache.sram_hit_rate

    def _sample(self, logits, req: Request) -> int:
        logits = logits.reshape(-1)
        if req.temperature <= 0.0:
            return int(jnp.argmax(logits))
        self.rng, sub = jax.random.split(self.rng)
        return int(jax.random.categorical(sub, logits / req.temperature))

    # -- engine tick ---------------------------------------------------
    def _decode_ready(self, slot: int) -> bool:
        """Decode may run only once the FULL prefill target is cached —
        for a restore victim that is ``resume_len`` (prompt + decoded
        tokens), not just the prompt: decoding while the resume prefill is
        still mid-gap would feed ``out_tokens[-1]`` at the wrong KV
        position."""
        req = self.active[slot]
        return bool(req is not None and req.out_tokens
                    and not req._await_handoff
                    and req.prefill_pos >= self._prefill_target(req))

    def step(self) -> List[Request]:
        """One engine tick; returns the requests completed this tick.

        Order within a tick: (1) admit — restores first, then new requests
        — into free slots; (2) reserve decode tokens *and* pages for every
        decode-ready slot (decode is never starved by prefill); (3) advance
        chunked prefills under the remaining token budget; (4) one batched
        decode over all runnable slots; (5) retire finished requests,
        recycling their slot and pages.  If the tick made no progress and
        at least one slot stalled on pages, the allocation deadlock is
        broken by preempting the cheapest victim (pages × restore cost ×
        class weight) — its progress is preserved (swap or recompute, per
        ``preempt_policy``) and it re-admits with priority.  With
        ``proactive_horizon > 0`` the same eviction fires *before* the
        stall, off the predicted page demand."""
        self._tick += 1
        self.stats["ticks"] += 1
        self._stalled_this_tick = False
        progress0 = self.stats["prefill_tokens"] + self.stats["decode_tokens"]
        # proactive preemption looks AHEAD: if the next-K-ticks page demand
        # of the active slots exceeds what the pool can grant, evict the
        # cheapest victim now instead of waiting for a fully stalled tick
        if self.paged and self.proactive_horizon > 0:
            self._preempt_proactive()
        # already-active decode slots reserve their next page BEFORE any
        # restore or admission can take it: a swap-in that consumed exactly
        # the pages its own preemption freed would re-starve the survivors
        # and ping-pong the pool forever
        if self.paged:
            for i in range(self.slots):
                if self._decode_ready(i):
                    self.alloc.ensure(i, self.lengths[i] + 1)
        self._admit()
        finished: List[Request] = []
        decode_slots = [i for i in range(self.slots) if self._decode_ready(i)]
        # decode is never starved: its tokens are reserved before prefill,
        # and (paged) so are its pages — otherwise a prefilling slot could
        # snatch the last page a decode needs, every tick, forever
        if self.paged:
            for i in decode_slots:
                self.alloc.ensure(i, self.lengths[i] + 1)
        spare = self._prefill_tick(self.max_tokens_per_tick
                                   - len(decode_slots), finished)
        # a prefill that completed inside this tick made its slot
        # decode-ready mid-tick; its decode token was never reserved above,
        # so it only rides along if the prefill loop left budget — else it
        # waits one tick (the reserved decode_slots always run)
        reserved = set(decode_slots)
        live = []
        for i in range(self.slots):
            if not self._decode_ready(i):
                continue
            if i in reserved:
                live.append(i)
            elif spare >= 1:
                spare -= 1
                live.append(i)
        n_active = sum(r is not None for r in self.active)
        self.stats["occupancy_sum"] += n_active / self.slots
        self.stats["peak_active"] = max(self.stats["peak_active"], n_active)
        if live:
            runnable = []
            for i in live:
                if self.paged and not self.alloc.ensure(i, self.lengths[i] + 1):
                    self.stats["stall_events"] += 1
                    self._stalled_this_tick = True
                    continue                   # stalled: re-decoded later
                runnable.append(i)
            if runnable:
                toks = np.zeros((self.slots,), np.int32)
                mask = np.zeros((self.slots,), bool)
                for i in runnable:
                    toks[i] = self.active[i].out_tokens[-1]
                    mask[i] = True
                # .copy(): jnp.asarray zero-copy-aliases numpy buffers on
                # CPU, and lengths/table are mutated below while the async
                # dispatch may still be reading them (shard_local already
                # builds a fresh array)
                if not self.paged:
                    tables = None
                elif self.seq_shards > 1:
                    tables = jnp.asarray(
                        self.alloc.shard_local(self.alloc.table))
                    self._account_noc_combine(rows=self.slots)
                else:
                    tables = jnp.asarray(self.alloc.table.copy())
                # the mask gates recurrent slot-state updates: batched
                # decode must not advance a mid-prefill neighbour's state
                out = self._decode(
                    self.params, self.state, jnp.asarray(toks),
                    jnp.asarray(self.lengths.copy()), tables,
                    jnp.asarray(mask))
                if self._moe_stats:
                    logits, self.state, est = out
                    # the batched dispatch routes every slot row (masked
                    # neighbours included), so the whole batch counts:
                    # sum(expert_load) == n_layers * top_k * routed_tokens
                    self._account_expert(est, rows=self.slots)
                else:
                    logits, self.state = out
                for i in runnable:
                    req = self.active[i]
                    self.lengths[i] += 1
                    self.stats["decode_tokens"] += 1
                    self.stats["padded_tokens"] += 1
                    nxt = self._sample(logits[i], req)
                    req.out_tokens.append(nxt)
                    hit_eos = req.eos_id is not None and nxt == req.eos_id
                    if (len(req.out_tokens) >= req.max_new_tokens or hit_eos
                            or self.lengths[i] >= self.max_seq - 1):
                        self._finish(i, req, finished)
        if self.paged:
            for k in ("pages_allocated", "pages_freed", "pages_shared",
                      "pages_evicted"):
                self.stats[k] = getattr(self.alloc, k)
        if self._stalled_this_tick:
            self.stats["stalled_ticks"] += 1   # once per tick, ≤ ticks
        made_progress = (self.stats["prefill_tokens"]
                         + self.stats["decode_tokens"] > progress0)
        if (self.paged and not made_progress and not finished
                and self._stalled_this_tick):
            # every live slot is waiting on pages and nothing else moved:
            # a static tick would repeat forever — break the deadlock
            self._preempt_for_deadlock()
        return finished

    def _preempt_for_deadlock(self) -> None:
        """Two+ partially-allocated slots can wait on each other's pages
        (each request fits the pool alone, together they don't).  Preempt
        the cheapest victim by :meth:`_victim_score` (pages × restore cost
        × class weight — least live KV among equal-class candidates) so
        the others can run — its progress is *preserved* (swapped to the
        host arena or recomputed at restore, see :meth:`_preempt`), so
        greedy outputs are unchanged and no decoded token is ever
        replayed."""
        victims = [i for i, r in enumerate(self.active)
                   if r is not None and self.alloc.used[i] > 0
                   and not r._await_handoff]
        if len(victims) < 2:
            # a parked swap restore can itself hold pages hostage (its
            # handle pins shared prefix-chain pages whose co-holders have
            # since retired): demote the first such handle — anywhere in
            # the restore queue, not just its head — to the recompute arm
            # (pins and arena bytes are dropped, the restore replays from
            # _resume_tokens) rather than livelock
            for parked in self.restore_queue:
                if parked._swap is not None:
                    self._demote_swap(parked)
                    break
            return
        self._preempt(min(victims, key=self._victim_score))

    def _restore_seconds(self, req: Request, live_tokens: int) -> float:
        """Price what bringing this victim back would cost — the same
        swap-vs-recompute arms :func:`core.noc.preempt_decision` weighs,
        collapsed to seconds under the engine's ``preempt_policy``."""
        n_pages = -(-live_tokens // self.block_size)
        return noc.restore_cost_seconds(
            n_pages, self._page_kv_bytes(), live_tokens,
            flops_per_token=2.0 * self.cfg.param_count(active_only=True),
            state_bytes=self._slot_state_bytes,
            policy=self.preempt_policy)

    def _victim_score(self, slot: int):
        """Preemption-victim ordering: evict the slot whose loss costs
        least — pages held × restore seconds × latency-class weight, so an
        interactive request only falls when no batch victim exists.  The
        old ``(out_tokens, prefill_pos)`` pair stays as the tie-break
        (score is monotone in live KV, so equal-class picks are
        unchanged); the slot index last keeps it deterministic."""
        req = self.active[slot]
        live = int(self.lengths[slot])
        pages = int(self.alloc.used[slot])
        score = (pages * self._restore_seconds(req, live)
                 * self.class_weights[req.priority])
        return (score, len(req.out_tokens), req.prefill_pos, slot)

    def _page_demand(self, horizon: int) -> int:
        """Pages the active slots will ask for over the next ``horizon``
        ticks beyond what they already hold.  Mid-prefill slots can grow by
        a whole chunk per tick; decode-ready slots by one token per tick —
        both capped at the request's total length."""
        need = 0
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            cur = int(self.lengths[slot])
            total = self._prefill_target(req) + req.max_new_tokens
            if req.prefill_pos < self._prefill_target(req):
                grow = horizon * self.max_tokens_per_tick
            else:
                grow = horizon
            future = min(total, cur + grow)
            need += max(0, -(-future // self.block_size)
                        - int(self.alloc.used[slot]))
        return need

    def _preempt_proactive(self) -> None:
        """Fire a preemption BEFORE the pool stalls: when the predicted
        next-``proactive_horizon``-ticks page demand of the active slots
        exceeds the free pool (parked-LRU pages count as free — the
        allocator reclaims them on demand), evict the cheapest victim by
        :meth:`_victim_score` now, so an interactive admission never waits
        behind a fully stalled tick.  Held while any restore is parked —
        evicting to re-admit would just ping-pong the same pages."""
        if self.restore_queue:
            return
        victims = [i for i, r in enumerate(self.active)
                   if r is not None and self.alloc.used[i] > 0
                   and not r._await_handoff]
        if len(victims) < 2:
            return
        if self._page_demand(self.proactive_horizon) <= self.alloc.free_blocks:
            return
        self.stats["preempt_proactive"] += 1
        self._preempt(min(victims, key=self._victim_score))

    def _demote_swap(self, req: Request) -> None:
        """Convert a parked swap handle into a recompute-arm restore: free
        its pinned references and arena slots (the pool gets every byte
        back) and let ``_restore`` replay the progress from
        ``_resume_tokens``.  Token-identical either way — only the restore
        cost changes."""
        handle = req._swap
        for page in handle.pinned:
            self.alloc.unpin(page)
        handle.pinned = []
        if handle.slots:
            self._arena.free(handle)
        req._swap = None
        self.stats["swap_demotions"] += 1

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` while preserving its generation progress.

        The victim's live KV tokens (``lengths[slot]`` = prompt prefilled
        so far + decoded tokens minus the unprocessed last sample) go down
        one of two arms, chosen by ``preempt_policy``:

        * **swap** — pages copied device -> host into the arena; released
          device pages become grantable immediately; restore copies them
          back verbatim.
        * **recompute** — pages dropped (full ones republished under the
          chained digest first, so the prefix cache can hand them back by
          reference), and the token suffix is re-prefilled at restore from
          ``_resume_tokens`` — decode progress survives as *tokens*, not
          bytes.

        ``auto`` asks ``core.noc.preempt_decision`` per victim: link bytes
        to move vs prefill FLOPs to replay.  Either way the request lands
        in ``restore_queue`` with priority over new admissions."""
        req = self.active[slot]
        L = int(self.lengths[slot])    # KV rows live right now
        self.stats["preemptions"] += 1
        self.stats["preempted_tokens"] += L
        self.class_stats[req.priority]["preemptions"] += 1
        req._preempted_live = L
        if L == 0:                      # nothing cached yet: plain requeue
            req.prefill_pos = 0
            self._retire(slot)
            self.restore_queue.append(req)
            return
        plen = self._plen(req)
        if req.out_tokens:
            # resume target: every decoded token except the still-unfed
            # last sample must be back in KV before decode continues.  L
            # can sit BELOW this (a victim preempted again mid-restore) —
            # the gap is covered by _resume_tokens either way.
            target = plen + len(req.out_tokens) - 1
            kv_seq = np.concatenate([
                req.prompt[:plen].astype(np.int32),
                np.asarray(req.out_tokens[:-1], np.int32)])
        else:
            target = 0                  # plain prompt prefill resumes it
            kv_seq = req.prompt[:plen].astype(np.int32)
        policy = self._preempt_choice(req, L)
        if policy == "swap" and not self._swap_out(slot, L):
            policy = "recompute"        # arena full: degrade, never fail
        if policy == "swap":
            self.stats["preempt_swaps"] += 1
        else:
            self.stats["preempt_recomputes"] += 1
            if self.prefix_caching:
                self._extend_digests(req, kv_seq)
                self._publish_resume_pages(slot, req, L)
        req.resume_len = target
        req._resume_tokens = kv_seq
        req.prefill_pos = 0
        self._retire(slot)
        self.restore_queue.append(req)

    def _preempt_choice(self, req: Request, live_tokens: int) -> str:
        if self.preempt_policy != "auto":
            return self.preempt_policy
        n_pages = -(-live_tokens // self.block_size)
        return noc.preempt_decision(
            n_pages, self._page_kv_bytes(), live_tokens,
            flops_per_token=2.0 * self.cfg.param_count(active_only=True),
            state_bytes=self._slot_state_bytes)

    def _page_shape(self):
        """Per-page array shape ``(A, KvH, BS, hd)`` (A = attention
        applications: L for transformers, G for the hybrid shared block) —
        the ONE definition shared by the swap arena and the cost model,
        from the CacheSpec, so priced and accounted swap bytes can never
        drift apart."""
        return self.runner.page_shape(self.block_size)

    def _page_kv_bytes(self) -> int:
        """Bytes of one physical page across all applications, K and V,
        at the pool's *storage* width — int8 pools count 1-byte values
        plus their per-page scales, so swap/restore link costs and the
        preemption cost model price the bytes actually moved."""
        return self.runner.page_kv_bytes(self.block_size,
                                         jnp.dtype(self.dtype).itemsize)

    def _swap_out(self, slot: int, live_tokens: int) -> bool:
        """Park the victim's progress host-side: registered prefix-chain
        pages are *pinned* (restore re-attaches them by reference — they
        never ride the link), the unregistered remainder is copied into
        the arena (per-shard batched), and families with recurrent state
        park the slot's fixed-size blob alongside.  False when the arena
        cannot hold the remainder."""
        from repro.serve import swap
        req = self.active[slot]
        n_pages = -(-live_tokens // self.block_size)
        pages = [int(p) for p in self.alloc.table[slot, :n_pages]]
        n_pin = 0
        if self.prefix_caching:
            # longest leading run of pages registered under this request's
            # own digest chain: their bytes are already content-addressed
            # in the pool (and often shared with other readers), so
            # copying them would only inflate swap_bytes — the handle pins
            # them instead and restore re-attaches by reference.  If the
            # pins ever starve the survivors, the deadlock breaker demotes
            # this handle to the recompute arm (_demote_swap) rather than
            # livelock.
            for i, p in enumerate(pages):
                if (i < len(req._digests)
                        and self.alloc.page_digest(p) == req._digests[i]):
                    n_pin += 1
                else:
                    break
        rest = pages[n_pin:]
        if rest:
            if self._arena is None:
                if self.swap_pages < 1:
                    return False
                quant = self.kv_dtype == "int8"
                self._arena = swap.SwapArena(
                    self.swap_pages, self._page_shape(),
                    jnp.dtype(jnp.int8) if quant else jnp.dtype(self.dtype),
                    quantized=quant)
            handle = self._arena.alloc(len(rest))
            if handle is None:
                return False
        else:
            handle = swap.SwapHandle([])   # fully covered by pinned pages
        handle.tokens = live_tokens
        handle.pinned = pages[:n_pin]
        for p in handle.pinned:
            self.alloc.pin(p)      # survives release(); LRU can't evict it
        if self.has_slot_state:
            handle.state = self.runner.extract_slot_state(self.state, slot)
            handle.state_bytes = self._slot_state_bytes
        for sh, idx in self._by_shard(rest):
            ids = self._pad_pow2([rest[i] for i in idx])
            k, v, ks, vs = self._extract_pages(self.state, jnp.asarray(ids))
            k = np.moveaxis(np.asarray(k), 2, 0)[:len(idx)]
            v = np.moveaxis(np.asarray(v), 2, 0)[:len(idx)]
            if ks is not None:
                ks = np.moveaxis(np.asarray(ks), 2, 0)[:len(idx)]
                vs = np.moveaxis(np.asarray(vs), 2, 0)[:len(idx)]
            self._arena.write([handle.slots[i] for i in idx], k, v, ks, vs)
        self.stats["swap_bytes"] += (len(rest) * self._page_kv_bytes()
                                     + handle.state_bytes)
        req._swap = handle
        return True

    def _extend_digests(self, req: Request, kv_seq: np.ndarray) -> None:
        """Grow the chained page-digest list over decoded-token pages so
        the decode suffix can be republished (and later re-matched) by the
        prefix cache.  Page ``i`` still commits to every token in
        ``[0, (i+1)*BS)`` — recomputed through :func:`_page_digests` (the
        ONE chain implementation, shared with submit) so resume keys can
        never drift from admission keys."""
        bs = self.block_size
        n_full = len(kv_seq) // bs
        if n_full > len(req._digests):
            req._digests = _page_digests(kv_seq, bs, n_full,
                                         seed=self._digest_seed)

    def _publish_resume_pages(self, slot: int, req: Request,
                              live_tokens: int) -> None:
        """Register every full live page (prompt AND decoded) before the
        drop, so restore can re-attach them by reference if they survive
        in the LRU (eviction only reclaims them under real pressure)."""
        for i in range(live_tokens // self.block_size):
            self.alloc.register(int(self.alloc.table[slot, i]),
                                req._digests[i])

    def _retire(self, slot: int) -> None:
        self.active[slot] = None
        self.lengths[slot] = 0
        if self.paged:
            self.alloc.release(slot)

    # -- disaggregated handoff (prefill side) --------------------------
    def poll_handoffs(self) -> List[int]:
        """Slots parked awaiting handoff (prefill-role engines only park
        after :meth:`_finish_prefill`; empty on other roles)."""
        return [i for i, r in enumerate(self.active)
                if r is not None and r._await_handoff]

    def stage_handoff(self, slot: int, arena, cached=()):
        """Stream one parked prefill out of ``slot`` into ``arena`` and
        retire the slot; returns the :class:`~serve.swap.HandoffHandle`
        or None when the arena cannot hold the chain remainder (the slot
        stays parked and the caller retries next tick — arena
        backpressure propagates into prefill-pool pressure by design).

        ``cached`` is the *decode-pool* page-id list for the leading
        full-page prefix already registered over there (matched by the
        DisaggServer against this request's digest chain, each id
        acquired so it cannot be evicted in flight): those pages never
        ride the link — only the uncached remainder is extracted, which
        is exactly what ``noc.handoff_cost`` prices.  The prefill pool
        keeps its own registered copies parked in the LRU (``_retire`` ->
        ``release``), so a future prompt sharing the prefix still hits
        locally."""
        from repro.serve import swap
        req = self.active[slot]
        if req is None or not req._await_handoff:
            raise RuntimeError(f"slot {slot} holds no handoff-ready request")
        tokens = int(self.lengths[slot])
        handle = swap.HandoffHandle(
            rid=req.rid, prompt=req.prompt,
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature, eos_id=req.eos_id,
            priority=req.priority, deadline_ms=req.deadline_ms,
            out_tokens=list(req.out_tokens), tokens=tokens,
            digests=list(req._digests), cached=list(cached), arena=arena,
            t_submit=req._t_submit, ttft=req.ttft)
        if self.paged:
            n_pages = -(-tokens // self.block_size)
            pages = [int(p) for p in self.alloc.table[slot, :n_pages]]
            rest = pages[len(handle.cached):]
            if rest:
                got = arena.alloc(len(rest))
                if got is None:
                    return None        # arena full: stays parked
                handle.slots = got.slots
                for sh, idx in self._by_shard(rest):
                    ids = self._pad_pow2([rest[i] for i in idx])
                    k, v, ks, vs = self._extract_pages(self.state,
                                                       jnp.asarray(ids))
                    k = np.moveaxis(np.asarray(k), 2, 0)[:len(idx)]
                    v = np.moveaxis(np.asarray(v), 2, 0)[:len(idx)]
                    if ks is not None:
                        ks = np.moveaxis(np.asarray(ks), 2, 0)[:len(idx)]
                        vs = np.moveaxis(np.asarray(vs), 2, 0)[:len(idx)]
                    arena.write([handle.slots[i] for i in idx], k, v, ks, vs)
        if self.has_slot_state:
            handle.state = self.runner.extract_slot_state(self.state, slot)
            handle.state_bytes = self._slot_state_bytes
        req._await_handoff = False
        self._retire(slot)
        return handle

    # -- async future driver protocol ----------------------------------
    def _future_done(self, rid: int) -> bool:
        return self._reqs[rid].done

    def _future_tokens(self, rid: int) -> List[int]:
        return self._reqs[rid].out_tokens

    def _future_step(self) -> None:
        self.step()

    def run_until_drained(self, max_ticks: int = 10_000,
                          strict: bool = True) -> List[Request]:
        """Step until the queues (including preempted requests awaiting
        restore) and slots are all empty; returns every finished request.
        With ``strict`` (default) an engine that cannot drain within
        ``max_ticks`` raises instead of silently returning a partial
        result set — the error distinguishes swap from recompute
        preemptions (restart-preemptions no longer exist) so a wedged
        pool-pressure workload is diagnosable from the message alone."""
        done: List[Request] = []
        for _ in range(max_ticks):
            done.extend(self.step())
            if (not self.queued and not self.restore_queue
                    and all(r is None for r in self.active)):
                return done
        if strict:
            live = [r.rid for r in self.active if r is not None]
            raise RuntimeError(
                f"engine not drained after {max_ticks} ticks "
                f"(queued={self.queued}, "
                f"awaiting_restore={len(self.restore_queue)}, "
                f"active rids={live}, "
                f"stalled_ticks={self.stats['stalled_ticks']:.0f}, "
                f"preemptions={self.stats['preemptions']:.0f}, "
                f"preempt_swaps={self.stats['preempt_swaps']:.0f}, "
                f"preempt_recomputes="
                f"{self.stats['preempt_recomputes']:.0f}, "
                f"restored_tokens={self.stats['restored_tokens']:.0f})")
        return done

    # -- introspection -------------------------------------------------
    def reset_stats(self) -> None:
        """Zero the counters (jit caches and the prefix-cache registry are
        kept) — benchmarks call this after a warmup drain so compile time
        stays out of the timed run."""
        for k in self.stats:
            self.stats[k] = 0
        self.stats["kv_bytes_per_page"] = (self._page_kv_bytes()
                                           if self.paged else 0)
        if self._moe_stats:
            self.stats["expert_load"] = np.zeros(
                self.runner.padded_experts())
        self.class_stats = {cls: self._zero_class_stats()
                            for cls in self.class_order}
        if self.paged:
            self.alloc.reset_counters()
        if self.expert_cache is not None:
            # counters only — residency, staging and the hotness EMA are
            # placement state, not statistics
            self.expert_cache.reset_counters()

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prefill-eligible prompt tokens served from cache."""
        tot = self.stats["prefix_hit_tokens"] + self.stats["prefill_tokens"]
        return self.stats["prefix_hit_tokens"] / tot if tot else 0.0

    @property
    def mean_occupancy(self) -> float:
        t = self.stats["ticks"]
        return self.stats["occupancy_sum"] / t if t else 0.0

    def kv_cache_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(self.state))


def _scatter_slot(state, one_state, slot: int):
    """Write a batch-of-1 prefill state into batch slot ``slot``.

    The batch dim is the first axis where one_state has extent 1 and the
    engine state differs (batch precedes all per-token dims in every
    layout used by repro.models)."""
    def put(dst, src):
        if dst.shape == src.shape:          # slots == 1: replace wholesale
            return src.astype(dst.dtype)
        for ax in range(dst.ndim):
            if src.shape[ax] == 1 and dst.shape[ax] != 1:
                idx = [slice(None)] * dst.ndim
                idx[ax] = slice(slot, slot + 1)
                return dst.at[tuple(idx)].set(src.astype(dst.dtype))
        return dst
    return jax.tree.map(put, state, one_state)
