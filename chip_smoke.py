#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU.

Serves stablelm-1.6b at its published widths (24 layers, d_model 2048, 32
MHA heads of 64, d_ff 5632, vocabulary 100352) with random bf16 weights made
from ``--seed``, through ``ServeEngine``'s paged KV cache with prefix caching
on, and checks the engine's logits against a float32 run of the same weights
on the pure-jnp reference path.

    python3 chip_smoke.py                # one chip: bf16 and int8 page pools
    python3 chip_smoke.py --four-chips   # seq_shards=4 over four chips
                                         # against a one-shard engine

Exits non-zero, printing no result line, when JAX finds no TPU, when a phase
fails, or when ``repro`` cannot be imported.  The last line of a passing run
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402

# Logit agreement is judged as rms(engine - reference) / rms(reference) over
# the whole vocabulary row.  The engine keeps weights, activations and KV in
# bf16 (8-bit mantissa: ~0.4% per rounding) through 24 residual layers; the
# reference runs the same bf16-valued weights in float32 end to end.  A few
# percent of accumulated rounding is expected, 5% is the bound.  int8 pages
# add one 8-bit quantization of every K/V row (per-page-per-head scale),
# about as coarse again, so that pool gets twice the bound.  Four shards vs
# one differ only in how the f32 softmax partials are merged before the
# bf16 cast, so they must agree far tighter.
TOL_BF16 = 0.05
TOL_INT8 = 0.10
TOL_SHARDS = 0.02

SLOTS = 8
MAX_SEQ = 1024
BLOCK_SIZE = 16
MAX_NEW = 16


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _require(ok: bool, what) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def setup_compile_cache() -> str:
    """Follow ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself);
    otherwise keep the cache at a fixed path inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class _CompileClock:
    """Sums XLA backend compile seconds (JAX's own monitoring events)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration


class RecordingEngine(ServeEngine):
    """A ServeEngine that keeps every logits row a token was sampled from:
    per request, row 0 is the prompt's last prefill position and row i the
    i-th decode step."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.sampled_logits = {}

    def _sample(self, logits, req):
        self.sampled_logits.setdefault(req.rid, []).append(
            np.asarray(logits, np.float32).reshape(-1))
        return super()._sample(logits, req)


def make_prompts(vocab: int, seed: int):
    """Two rounds of requests; round 2's first prompt repeats the first 256
    tokens of round 1's first prompt, so it attaches cached prefix pages."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, vocab, 320).tolist()
    round1 = [a, rng.integers(0, vocab, 256).tolist(),
              rng.integers(0, vocab, 384).tolist()]
    round2 = [a[:256] + rng.integers(0, vocab, 64).tolist(),
              rng.integers(0, vocab, 200).tolist()]
    return round1, round2


def serve(cfg, params, rounds, **engine_kw):
    """Drain each round through one engine, as ``repro.launch.serve`` sets it
    up (paged KV, prefix caching on).  Returns (engine, requests by submit
    order, wall seconds per round)."""
    eng = RecordingEngine(cfg, params, slots=SLOTS, max_seq=MAX_SEQ,
                          block_size=BLOCK_SIZE, preempt_policy="auto",
                          **engine_kw)
    reqs, walls = [], []
    for prompts in rounds:
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        done = {r.rid: r for r in eng.run_until_drained()}
        walls.append(time.perf_counter() - t0)
        for f in futs:
            r = done[f.rid]
            _require(len(r.out_tokens) == MAX_NEW,
                     (f.rid, len(r.out_tokens)))
            reqs.append(r)
    return eng, reqs, walls


def kernel_counts(eng, bucket: int) -> dict:
    """``tpu_custom_call`` ops in the lowered decode step and in one prefill
    chunk: nonzero means the Pallas kernels are in the program."""
    i32 = jnp.int32
    tables = eng.alloc.table
    bt = np.zeros((eng._page_bucket(bucket // eng.block_size),), np.int32)
    if eng.seq_shards > 1:          # the engine passes shard-local tables
        tables, bt = eng.alloc.shard_local(tables), eng.alloc.shard_local(bt)
    dec = eng._decode.lower(
        eng.params, eng.state, jnp.zeros((eng.slots,), i32),
        jnp.zeros((eng.slots,), i32), jnp.asarray(tables),
        jnp.ones((eng.slots,), bool)).as_text()
    pre = eng._prefill_fn(bucket).lower(
        eng.params, eng.state, jnp.zeros((1, bucket), i32), i32(bucket),
        i32(0), jnp.asarray(bt), i32(0)).as_text()
    return {"decode": dec.count("tpu_custom_call"),
            "prefill": pre.count("tpu_custom_call")}


def rel_rms(got, want) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def reference_logits(cfg, params32, tokens) -> np.ndarray:
    """float32 full-sequence forward on the pure-jnp reference ops."""
    with ops.use_mode("ref"):
        fwd = jax.jit(lambda p, t: M.forward(cfg, p, tokens=t)[0][0])
        return np.asarray(fwd(params32, jnp.asarray([tokens], jnp.int32)),
                          np.float32)


def check_against_reference(cfg, params32, logits, reqs, tol, label):
    """The engine's logits (``logits[rid]``, as ``RecordingEngine`` keeps
    them) at the prompt's last position and at the first decode step,
    against the float32 reference on prompt + first token."""
    worst = 0.0
    for r in reqs:
        rows = logits[r.rid]
        ref = reference_logits(cfg, params32, list(r.prompt) + [r.out_tokens[0]])
        n = len(r.prompt)
        for name, got, want in (("prefill", rows[0], ref[n - 1]),
                                ("decode", rows[1], ref[n])):
            err = rel_rms(got, want)
            worst = max(worst, err)
            _log(f"{label} rid={r.rid} {name}: rel_rms={err:.3e} "
                 f"max_abs={np.abs(got - want).max():.3e} "
                 f"ref_rms={np.sqrt(np.mean(want ** 2)):.3e} "
                 f"argmax_match={int(got.argmax()) == int(want.argmax())}")
            _require(np.isfinite(got).all(), f"{label} rid={r.rid} {name}: "
                     "non-finite logits")
            _require(err <= tol, f"{label} rid={r.rid} {name}: {err} > {tol}")
    return worst


def tokens_agree(ref_logits, ref_reqs, logits, reqs, tol) -> int:
    """Greedy outputs of ``reqs`` against ``ref_reqs``, the same requests
    served by another engine, with the logits each engine recorded.
    Logits must agree within ``tol`` for as long as the token histories are
    equal; a token may differ only where the reference's two candidates are
    closer than the logits difference measured at that step (a tie within
    rounding), after which the histories part and the comparison stops.
    Returns the number of tokens compared."""
    compared = 0
    for a, b in zip(ref_reqs, reqs):
        _require(np.array_equal(a.prompt, b.prompt), "request order differs")
        la, lb = ref_logits[a.rid], logits[b.rid]
        for i, (ta, tb) in enumerate(zip(a.out_tokens, b.out_tokens)):
            err = rel_rms(lb[i], la[i])
            _require(err <= tol,
                     f"rid={b.rid} step {i}: rel_rms {err} > {tol}")
            compared += 1
            if ta != tb:
                gap = abs(la[i][ta] - la[i][tb])
                bound = 2 * np.abs(la[i] - lb[i]).max()
                _require(gap <= bound,
                         f"rid={b.rid} step {i}: tokens {ta} vs {tb} with "
                         f"logit gap {gap} > {bound}")
                _log(f"rid={b.rid} step {i}: near-tie {ta} vs {tb} "
                     f"(gap {gap:.3e} <= {bound:.3e}); histories part here")
                break
    return compared


def init_bf16_params(cfg, seed: int):
    return jax.jit(lambda k: M.init_params(cfg, k))(jax.random.key(seed))


def one_chip(cfg, seed: int, clock: _CompileClock) -> None:
    params = init_bf16_params(cfg, seed)
    rounds = make_prompts(cfg.vocab_size, seed)
    stats = {}
    recorded = {}
    for kv in ("fp16", "int8"):
        c0 = clock.seconds
        eng, reqs, walls = serve(cfg, params, rounds, kv_dtype=kv)
        hit = eng.stats["prefix_hit_tokens"]
        _require(hit >= 256, f"kv={kv}: prefix_hit_tokens={hit}")
        counts = kernel_counts(eng, 512)
        _require(counts["decode"] > 0 and counts["prefill"] > 0,
                 f"no Pallas kernel in the lowered steps: {counts}")
        fb = ops.fallback_stats()
        _require(not fb, f"reference fallbacks on the serving path: {fb}")
        toks = sum(len(r.out_tokens) for r in reqs)
        _log(f"kv={kv}: {len(reqs)} requests, {toks} tokens, "
             f"prefix_hit_tokens={hit:.0f}, "
             f"prefill_dispatches={eng.stats['prefill_dispatches']:.0f}, "
             f"tpu_custom_call decode={counts['decode']} "
             f"prefill={counts['prefill']}, compile_s="
             f"{clock.seconds - c0:.1f}, round_wall_s="
             f"{', '.join(f'{w:.1f}' for w in walls)}")
        # compare the plain first request and the prefix-cache hit
        recorded[kv] = (eng.sampled_logits, [reqs[0], reqs[3]])
        stats[kv] = toks
        del eng
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    _log(f"peak_bytes_in_use after serving: {peak}")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    del params
    for kv, tol in (("fp16", TOL_BF16), ("int8", TOL_INT8)):
        logits, reqs = recorded[kv]
        worst = check_against_reference(cfg, params32, logits, reqs, tol,
                                        f"kv={kv}")
        _log(f"kv={kv}: worst rel_rms vs float32 reference {worst:.3e} "
             f"(bound {tol})")
    _log(f"tokens produced: {sum(stats.values())}")


def four_chips(cfg, seed: int, clock: _CompileClock) -> None:
    n = len(jax.devices())
    _require(n >= 4, f"--four-chips needs 4 devices, found {n}")
    params = init_bf16_params(cfg, seed)
    rounds = make_prompts(cfg.vocab_size, seed)
    ref_eng, ref_reqs, _ = serve(cfg, params, rounds)
    ref_logits = ref_eng.sampled_logits
    del ref_eng
    c0 = clock.seconds
    eng, reqs, walls = serve(cfg, params, rounds, seq_shards=4)
    counts = kernel_counts(eng, 512)
    _require(counts["decode"] > 0 and counts["prefill"] > 0,
             f"no Pallas kernel in the lowered steps: {counts}")
    _require(not ops.fallback_stats(),
             f"reference fallbacks: {ops.fallback_stats()}")
    pages = eng.state["attn"]["k_pages"]
    written = {}
    for shard in pages.addressable_shards:
        live = jnp.any(shard.data != 0, axis=(0, 1, 3, 4))
        written[shard.device.id] = int(live.sum())
    _log(f"seq_shards=4: k_pages {pages.shape} over devices "
         f"{sorted(written)}; written pages per device {written}")
    _require(len(written) == 4 and all(v > 0 for v in written.values()),
             f"pages not spread over four devices: {written}")
    compared = tokens_agree(ref_logits, ref_reqs, eng.sampled_logits, reqs,
                            TOL_SHARDS)
    _log(f"seq_shards=4 vs 1: {compared} token steps agree "
         f"(logits within {TOL_SHARDS}); "
         f"tpu_custom_call decode={counts['decode']} "
         f"prefill={counts['prefill']}; compile_s={clock.seconds - c0:.1f}; "
         f"round_wall_s={', '.join(f'{w:.1f}' for w in walls)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sequence-sharded phase: seq_shards=4 "
                         "over four chips against a one-shard engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache = setup_compile_cache()
    _log(f"device_kind={dev.device_kind} count={len(jax.devices())} "
         f"compile_cache={cache}")
    clock = _CompileClock()
    ops.reset_fallback_stats()
    cfg = get_config("stablelm-1.6b")
    if args.four_chips:
        four_chips(cfg, args.seed, clock)
    else:
        one_chip(cfg, args.seed, clock)
    _log(f"total backend compile seconds {clock.seconds:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
