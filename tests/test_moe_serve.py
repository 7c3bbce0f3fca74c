"""Expert-parallel MoE serving: the engine's ``expert_parallel`` /
``expert_cache_size`` knobs against the dense decode_step reference.

The serving-side MoE contract: sharding routed experts over an
``("expert",)`` mesh axis (alone or composed with ``seq_shards``) and
running the placement cache's telemetry must never change greedy
outputs — EP=1 runs the full EP machinery on a 1-shard mesh so the
dispatch itself is covered on one device, the 4-shard and 2x2 legs run
in a subprocess (and in-process on the multidevice CI lane).  The
dropless regression pins GShard capacity semantics under adversarial
routing skew on both the GSPMD scatter and the EP-local dispatch:
``capacity_factor >= E/k`` keeps every assignment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models import moe
from repro.serve import ServeEngine

multidevice = pytest.mark.multidevice

_SETUPS = {}


def _setup(arch="olmoe-1b-7b"):
    """Reduced arch; MoE configs get a dropless capacity factor
    (``cf >= E/k`` caps at T, keeping every assignment).  GShard capacity
    is dispatch-size-dependent, so chunked prefill (T = bucket) and a
    monolithic reference (T = prompt) only agree exactly when neither
    drops — the parity tests pin the dropless contract."""
    if arch not in _SETUPS:
        cfg = reduced(get_config(arch))
        if cfg.n_experts:
            cfg = cfg.replace(
                capacity_factor=float(cfg.n_experts) / cfg.top_k)
        params = M.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        _SETUPS[arch] = (cfg, params)
    return _SETUPS[arch]


_PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7], list(range(1, 20))]


def _reference(cfg, params, prompt, max_new, max_seq=64):
    """The dense decode_step path: exact-length prefill + greedy decode."""
    state = M.init_decode_state(cfg, 1, max_seq, dtype=jnp.float32)
    lg, state = M.prefill(cfg, params, state,
                          tokens=jnp.asarray([prompt], jnp.int32),
                          lengths=jnp.array([len(prompt)], jnp.int32))
    toks = [int(jnp.argmax(lg[0] if lg.ndim == 2 else lg[0, 0]))]
    ln = len(prompt)
    for _ in range(max_new - 1):
        lg, state = M.decode_step(cfg, params, state,
                                  jnp.array([toks[-1]], jnp.int32),
                                  jnp.array([ln], jnp.int32))
        ln += 1
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def _drain(cfg, params, prompts, max_new=5, **kw):
    """3 requests on 2 slots with an 12-token tick budget: the long prompt
    chunk-prefills WHILE the short ones decode (the interleaving that a
    broken EP dispatch or telemetry plumbing would corrupt)."""
    eng = ServeEngine(cfg, params, max_seq=64, slots=2, block_size=8,
                      prefill_buckets=(8, 16, 64), max_tokens_per_tick=12,
                      **kw)
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    got = {r.rid: list(r.out_tokens) for r in eng.run_until_drained()}
    return got, eng


def _check_load_invariant(eng):
    """Every dispatch routes each of its rows top_k ways through every
    MoE layer (drops lose outputs, not routing counts), so the telemetry
    must satisfy sum(expert_load) == n_layers * top_k * routed_tokens
    EXACTLY — replicated across shards, not scaled by them."""
    s = eng.stats
    load = np.asarray(s["expert_load"], np.float64)
    assert load.shape == (eng.runner.padded_experts(),)
    assert int(load.sum()) == (eng.cfg.n_layers * eng.cfg.top_k
                               * int(s["expert_routed_tokens"]))


# ---------------------------------------------------------------------------
# 1-device legs: EP=1 (full EP machinery, 1-shard mesh) and cache-only
# ---------------------------------------------------------------------------

def test_ep1_engine_matches_reference():
    cfg, params = _setup()
    got, eng = _drain(cfg, params, _PROMPTS, expert_parallel=1)
    for rid, p in enumerate(_PROMPTS):
        assert got[rid] == _reference(cfg, params, p, 5), rid
    assert eng.mesh is not None                 # EP=1 still shard_maps
    _check_load_invariant(eng)
    s = eng.stats
    assert s["expert_skew"] >= 1.0              # max/mean is >= 1 always
    assert 0.0 <= s["expert_gini"] < 1.0
    assert s["expert_dropped_tokens"] == 0.0    # dropless capacity factor


def test_expert_cache_engine_matches_reference():
    """Placement accounting without EP: no mesh, plain jit, but the
    telemetry output feeds the LRU cache and the expert_* stats."""
    cfg, params = _setup()
    cache_size = max(1, cfg.n_experts // 2)
    got, eng = _drain(cfg, params, _PROMPTS, expert_cache_size=cache_size)
    for rid, p in enumerate(_PROMPTS):
        assert got[rid] == _reference(cfg, params, p, 5), rid
    assert eng.mesh is None
    _check_load_invariant(eng)
    s, cache = eng.stats, eng.expert_cache
    assert cache.capacity == cache_size
    assert s["expert_hits"] + s["expert_misses"] > 0
    assert (s["expert_hits"] + s["expert_misses"]
            == cache.counters["lookups"])
    assert s["expert_sram_hit_rate"] == pytest.approx(cache.sram_hit_rate)
    assert (s["expert_migration_bytes"]
            == s["expert_migrations"] * cache.expert_bytes)
    # reset_stats zeroes the telemetry but keeps placement state
    residents = cache.residents(0)
    eng.reset_stats()
    assert float(np.sum(eng.stats["expert_load"])) == 0.0
    assert eng.stats["expert_sram_hit_rate"] == 0
    assert cache.counters["lookups"] == 0
    assert cache.residents(0) == residents


def test_ep_chunked_prefill_matches_monolithic():
    """Chunked prefill (8-token chunks) under EP == one monolithic
    prefill under EP == the dense reference."""
    cfg, params = _setup()
    prompt = list(range(1, 27))                 # 26 tokens -> 8+8+8+2 chunks
    chunked, eng = _drain(cfg, params, [prompt], max_new=6,
                          expert_parallel=1)
    assert eng.stats["prefill_tokens"] == len(prompt)
    assert eng.stats["ticks"] >= 4              # it really chunked
    mono = ServeEngine(cfg, params, max_seq=64, slots=1, block_size=8,
                       prefill_buckets=(64,), expert_parallel=1)
    mono.submit(prompt, max_new_tokens=6)
    mono_toks = list(mono.run_until_drained()[0].out_tokens)
    ref = _reference(cfg, params, prompt, 6)
    assert chunked[0] == mono_toks == ref


def test_expert_engine_validation():
    cfg, params = _setup()
    dense_cfg, dense_params = _setup("granite-3-2b")
    kw = dict(max_seq=32, slots=1)
    with pytest.raises(ValueError, match="MoE family"):
        ServeEngine(dense_cfg, dense_params, expert_parallel=1, **kw)
    with pytest.raises(ValueError, match="MoE family"):
        ServeEngine(dense_cfg, dense_params, expert_cache_size=2, **kw)
    with pytest.raises(ValueError, match=">= 1"):
        ServeEngine(cfg, params, expert_parallel=0, **kw)
    with pytest.raises(ValueError, match="divide"):
        ServeEngine(cfg, params, expert_parallel=3, **kw)
    with pytest.raises(ValueError, match="devices"):
        # the product must fit the visible device count on every lane
        ServeEngine(cfg, params, expert_parallel=2,
                    seq_shards=8 * jax.device_count(), **kw)
    with pytest.raises(ValueError, match="dense-slab"):
        ServeEngine(cfg, params, paged=False, expert_parallel=1, **kw)
    with pytest.raises(ValueError, match="expert_placement"):
        ServeEngine(cfg, params, expert_cache_size=2,
                    expert_placement="hot", **kw)


# ---------------------------------------------------------------------------
# dropless regression: capacity_factor >= E/k keeps every assignment,
# GSPMD scatter and EP-local dispatch alike, under adversarial skew
# ---------------------------------------------------------------------------

def _adversarial_moe():
    """Router forced so EVERY token routes to the two hottest (highest
    index) experts: columns E-1/E-2 get large positive weights, x is
    strictly positive so the forced logits always win top-2."""
    cfg = reduced(get_config("olmoe-1b-7b"))
    p = dict(moe.moe_init(jax.random.key(0), cfg, dtype=jnp.float32))
    router = np.zeros(np.shape(p["router"]), np.float32)
    # column weights sized so the forced logits (~0.1-0.2 x sum(x), a few
    # nats) dominate without underflowing the softmax — a 10.0 weight
    # pushes the runner-up to exp(-hundreds) == 0.0 in fp32 and top_k
    # then tie-breaks the zero probabilities by index instead
    router[:, cfg.n_experts - 1] = 0.2
    router[:, cfg.n_experts - 2] = 0.1
    p["router"] = jnp.asarray(router)
    x = 0.1 + jnp.abs(jax.random.normal(jax.random.key(1),
                                        (2, 8, cfg.d_model), jnp.float32))
    return cfg, p, x


def _ep_local_apply(p, x, cfg, cf, n_shards):
    """Run the EP-local dispatch the way the engine does: inside a
    shard_map over an ``("expert",)`` mesh with the expert banks sharded
    and the router replicated."""
    mesh = make_mesh((n_shards,), ("expert",))
    pspec = {k: (P("expert") if k in ("w_gate", "w_up", "w_down") else P())
             for k in p}

    def body(p_loc, x_rep):
        return moe.moe_apply(p_loc, x_rep, cfg, capacity_factor=cf,
                             expert_axis="expert", return_stats=True)

    f = jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                      out_specs=(P(), P()), check_vma=False)
    return f(p, x)


def test_dropless_capacity_adversarial_gspmd():
    cfg, p, x = _adversarial_moe()
    t = x.shape[0] * x.shape[1]
    cf = float(cfg.n_experts) / cfg.top_k
    y, aux = jax.jit(lambda p, x: moe.moe_apply(
        p, x, cfg, capacity_factor=cf, return_stats=True))(p, x)
    assert float(aux["frac_dropped"]) == 0.0
    load = np.asarray(aux["expert_load"])
    # all T*k assignments land on the two forced experts, T each
    assert load[cfg.n_experts - 1] == t and load[cfg.n_experts - 2] == t
    assert load.sum() == t * cfg.top_k
    # sanity contrast: cf=1 must overflow the two hot experts
    _, aux_tight = jax.jit(lambda p, x: moe.moe_apply(
        p, x, cfg, capacity_factor=1.0, return_stats=True))(p, x)
    assert float(aux_tight["frac_dropped"]) > 0.0


def _dropless_ep_local(n_shards):
    cfg, p, x = _adversarial_moe()
    t = x.shape[0] * x.shape[1]
    cf = float(cfg.n_experts) / cfg.top_k
    y_ref, _ = jax.jit(lambda p, x: moe.moe_apply(
        p, x, cfg, capacity_factor=cf))(p, x)
    y, aux = _ep_local_apply(p, x, cfg, cf, n_shards)
    assert float(aux["frac_dropped"]) == 0.0
    load = np.asarray(aux["expert_load"])
    assert load[cfg.n_experts - 1] == t and load[cfg.n_experts - 2] == t
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    _, aux_tight = _ep_local_apply(p, x, cfg, 1.0, n_shards)
    assert float(aux_tight["frac_dropped"]) > 0.0


def test_dropless_capacity_adversarial_ep_local():
    """EP-local on a 1-shard mesh (the degenerate dispatch)."""
    _dropless_ep_local(1)


@multidevice
@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (multidevice CI lane)")
def test_dropless_capacity_adversarial_ep_local_4shard():
    _dropless_ep_local(4)


# ---------------------------------------------------------------------------
# sharded EP engine parity: 4-shard EP and 2x2 EP x seq composition
# ---------------------------------------------------------------------------

_EP_ENGINE_SNIPPET = """
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import model as M
from repro.serve import ServeEngine

cfg = reduced(get_config("olmoe-1b-7b"))
params = M.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7], list(range(1, 20))]

def drain(**extra):
    eng = ServeEngine(cfg, params, max_seq=64, slots=2, block_size=8,
                      prefill_buckets=(8, 16, 64), max_tokens_per_tick=12,
                      **extra)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = eng.run_until_drained()
    return {r.rid: tuple(r.out_tokens) for r in done}, eng

base, _ = drain()
for kw in (dict(expert_parallel=4),
           dict(expert_parallel=4, expert_cache_size=4),
           dict(expert_parallel=2, seq_shards=2),
           dict(expert_parallel=2, seq_shards=2, expert_cache_size=4)):
    toks, eng = drain(**kw)
    assert toks == base, (kw, toks)
    s = eng.stats
    load = np.asarray(s["expert_load"], np.float64)
    assert int(load.sum()) == (cfg.n_layers * cfg.top_k
                               * int(s["expert_routed_tokens"])), kw
    if "expert_cache_size" in kw:
        assert s["expert_hits"] + s["expert_misses"] > 0, kw
        assert (s["expert_migration_bytes"] == s["expert_migrations"]
                * eng.expert_cache.expert_bytes), kw
print("OK")
"""


def test_ep_engine_parity_subprocess(subproc):
    """4-shard EP, EP + cache, and the 2x2 EP x seq_shards composition
    are all token-identical to the unsharded engine, with the replicated
    telemetry invariant intact."""
    assert "OK" in subproc(_EP_ENGINE_SNIPPET)


@multidevice
@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (multidevice CI lane)")
def test_ep_engine_parity_multidevice():
    exec(compile(_EP_ENGINE_SNIPPET, "<ep-engine-parity>", "exec"), {})
