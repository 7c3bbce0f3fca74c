"""One run of one cell: set-up, lead-in, measured window, drain, check.

The harness drives ``ServeEngine.submit`` / ``step`` itself and takes every
time on its own clock, outside the engine: a token is seen when the
``step()`` that produced it returns (the engine samples on the host, so
``step()`` returns after the device work of its tick).

Everything that belongs to one cell is read from files by name (see
``spec.py``); nothing here names a configuration, a mix or a metric.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from serve_bench import check, cost, spec as specmod, traffic as tr
from serve_bench import trace as trace_mod

ENGINE_KEYS = ("slots", "max_seq", "num_blocks", "prefill_buckets",
               "max_tokens_per_tick")


def log(msg: str) -> None:
    print(f"[serve_bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA executables built or loaded from the persistent cache
    (JAX's ``backend_compile`` monitoring event), with their seconds."""

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration


@dataclass
class Rec:
    """The harness's view of one request."""
    req: tr.Req
    rid: int
    due: float                          # absolute due time (harness clock)
    submitted: float
    times: List[float] = field(default_factory=list)   # token arrivals
    pos: int = 0                        # prefill position last seen
    done: bool = False
    engine_req: object = None           # the engine's request (out_tokens)
    out: Optional[List[int]] = None     # served tokens, read after the run


@dataclass
class Tick:
    t0: float
    t1: float
    queued: int = 0                     # requests waiting after the tick
    decode_rows: int = 0
    decode_ctx: int = 0
    chunks: List[tuple] = field(default_factory=list)   # (n, offset)


def model_config(c: dict):
    """The program's ``ModelConfig`` with the configuration file's sizes."""
    from repro.configs import get_config

    return get_config(c["registry"]).replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]))


def percentile(xs, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Run:
    def __init__(self, cell: specmod.Cell, seed: int, seconds: float,
                 traced: bool, peaks: dict, t_start: float):
        import jax

        self.jax = jax
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.traced, self.peaks, self.t_start = traced, peaks, t_start
        self.cfg = cell.config
        self.compiles = CompileCounter(jax)
        self.recs: Dict[int, Rec] = {}
        self.ticks: List[Tick] = []
        self.lateness: List[float] = []
        # window edges: engine counters, compile counts, traced interval
        self.stats0 = self.stats1 = None
        self.compiles0 = self.compiles1 = 0
        self.trace_t0 = self.trace_t1 = None
        # the rate's window, from tick boundary to tick boundary
        self.rate_t0 = self.rate_t1 = None

    # -- set-up ---------------------------------------------------------
    def build(self):
        import jax.numpy as jnp

        from repro.serve import ServeEngine
        from serve_bench import weights

        c = self.cfg
        self.model = model_config(c)
        dtype = jnp.dtype(c["dtype"])
        self.params = weights.make(c, self.seed, dtype)
        self.jax.block_until_ready(self.params)
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in self.cell.engine.items() if k in ENGINE_KEYS}
        self.eng = ServeEngine(self.model, self.params,
                               block_size=c["block_size"],
                               kv_dtype=c["kv_dtype"], prefix_caching=True,
                               preempt_policy="auto", seed=0, **kw)
        self.max_seq = self.eng.max_seq
        self.traffic = tr.Traffic(self.cell.mix, self.seed, c["vocab_size"],
                                  self.max_seq)

    def warm_up(self) -> None:
        """Compile every shape the mix can reach, through submit/step: one
        prompt at each end of the length range and on both sides of each
        prefill bucket and power-of-two page count, with and without an
        attached shared prefix; then cache the mix's shared prefixes."""
        eng, mix, rng = self.eng, self.cell.mix, np.random.default_rng(0)
        marks = list(eng.prefill_buckets) + tr.page_marks(
            self.cfg["block_size"], self.max_seq)
        prompts = []
        pre = mix.get("prefix")
        if pre:
            full = dict(mix["prompt"], min=mix["prompt"]["min"] + pre["tokens"],
                        max=mix["prompt"]["max"] + pre["tokens"])
            sfx = tr.boundary_lengths(
                mix["prompt"], marks + [m - pre["tokens"] for m in marks])
            prompts += [rng.integers(0, self.cfg["vocab_size"], n)
                        for n in tr.boundary_lengths(full, marks)]
        else:
            prompts += [rng.integers(0, self.cfg["vocab_size"], n)
                        for n in tr.boundary_lengths(mix["prompt"], marks)]
        while len(prompts) < eng.slots:       # every slot decodes once
            prompts.append(rng.integers(0, self.cfg["vocab_size"],
                                        mix["prompt"]["min"]))
        self._drain_batch(prompts)
        if pre:
            # the shared prefixes enter the cache here; the attached
            # shapes are then compiled against the first of them
            self._drain_batch([np.concatenate(
                [p, rng.integers(0, self.cfg["vocab_size"], 16)])
                for p in self.traffic.prefixes])
            self._drain_batch([np.concatenate(
                [self.traffic.prefixes[0],
                 rng.integers(0, self.cfg["vocab_size"], n)]) for n in sfx])

    def _drain_batch(self, prompts) -> None:
        eng = self.eng
        for i in range(0, len(prompts), eng.slots):
            for p in prompts[i:i + eng.slots]:
                eng.submit(np.asarray(p, np.int32), max_new_tokens=2)
            eng.run_until_drained()

    # -- the timed path -------------------------------------------------
    def _submit(self, r: tr.Req, due: float) -> None:
        import jax

        with jax.profiler.TraceAnnotation("submit"):
            now = time.perf_counter()
            fut = self.eng.submit(r.prompt, max_new_tokens=r.max_new)
        self.recs[int(fut)] = Rec(r, int(fut), due, now)
        self.lateness.append(now - due)

    def _step(self, record: bool) -> None:
        """One engine tick, and what the harness sees of it."""
        import jax

        eng = self.eng
        with jax.profiler.TraceAnnotation("engine.step"):
            t0 = time.perf_counter()
            finished = eng.step()
            t1 = time.perf_counter()
        tick = Tick(t0, t1, eng.queued)
        if t0 < self.t_open <= t1:
            self.rate_t0 = t1       # the tick running as the window opens
        if t0 < self.t_close:
            self.rate_t1 = t1       # the last tick begun before the close
        for r in [a for a in eng.active if a is not None] + finished:
            rec = self.recs.get(r.rid)
            if rec is None:
                continue
            n_prev, n_now = len(rec.times), len(r.out_tokens)
            plen = len(r.prompt)
            if r.prefill_pos > rec.pos:
                off = rec.pos if rec.pos else max(rec.pos, r.cached_len)
                if r.prefill_pos > off:
                    tick.chunks.append((r.prefill_pos - off, off))
                rec.pos = r.prefill_pos
            dec = n_now - n_prev - (1 if n_prev == 0 and n_now else 0)
            if dec > 0:
                tick.decode_rows += dec
                tick.decode_ctx += plen + n_now - 1
            rec.times.extend([t1] * (n_now - n_prev))
            rec.engine_req = r
            if r.done and not rec.done:
                rec.done = True
                self.src.finished(rec.req, r.out_tokens, t1 - self.t_open)
        if record:
            self.ticks.append(tick)

    def _idle(self) -> bool:
        eng = self.eng
        return eng.queued == 0 and all(a is None for a in eng.active)

    def drive(self) -> None:
        """Lead-in, window and drain.  Open loop: requests are submitted
        at their due time whatever the engine is doing (between ticks: the
        engine is synchronous), and the drain lasts until every request
        due in the window has its first token, ``drain_s`` at most.
        Closed loop: each client sends its next request when its last one
        finishes, and the run stops at the window's close.  The mix's
        arrival process (``traffic/arrivals/<name>.py``) says which."""
        import jax

        w = self.cell.workload
        lead, drain = float(w["lead_in_s"]), float(w["drain_s"])
        T = self.seconds
        self.src = src = self.traffic.source(w, lead, T)
        self.t_setup_end = time.perf_counter()
        t_open = self.t_setup_end + lead
        t_close = t_open + T
        self.t_open, self.t_close = t_open, t_close
        prof_dir = None
        in_window = False
        while True:
            now = time.perf_counter()
            if not in_window and now >= t_open:
                in_window = True
                self.stats0 = dict(self.eng.stats)
                self.compiles0 = self.compiles.count
                if self.traced:
                    prof_dir = tempfile.mkdtemp(prefix="serve_bench_trace_")
                    jax.profiler.start_trace(prof_dir)
                    self.trace_t0 = time.perf_counter()
            if in_window and now >= t_close and prof_dir is not None \
                    and self.trace_t1 is None:
                jax.profiler.stop_trace()
                self.trace_t1 = time.perf_counter()
            if in_window and now >= t_close and self.stats1 is None:
                self.stats1 = dict(self.eng.stats)
                self.compiles1 = self.compiles.count
            for r, due in src.take(now - t_open):
                self._submit(r, t_open + due)
            if now >= t_close and (src.closed or now >= t_close + drain or all(
                    r.times for r in self.recs.values() if r.due < t_close)):
                break     # every request due in the window has its first token
            if self._idle():
                # nothing to serve: wait for the next arrival, briefly, so
                # the window's edges are still seen on time
                nxt = src.next_due()
                nxt = now if nxt is None else t_open + nxt
                with jax.profiler.TraceAnnotation("generator.wait"):
                    time.sleep(min(0.01, max(0.0005, nxt - now)))
                continue
            ann = "drain" if now >= t_close else None
            if ann:
                with jax.profiler.TraceAnnotation(ann):
                    self._step(record=False)
            else:
                self._step(record=in_window)
        if prof_dir is not None and self.trace_t1 is None:
            jax.profiler.stop_trace()
            self.trace_t1 = time.perf_counter()
        if self.stats1 is None:
            self.stats1 = dict(self.eng.stats)
            self.compiles1 = self.compiles.count
        self.prof_dir = prof_dir
        self.t_end = time.perf_counter()

    # -- results --------------------------------------------------------
    def end_to_end(self) -> dict:
        t_open, t_close = self.t_open, self.t_close
        due = [r for r in self.recs.values() if t_open <= r.due < t_close]
        ttft, failed = [], 0
        for r in due:
            if r.times:
                ttft.append(r.times[0] - r.due)
            else:
                failed += 1
                ttft.append(self.t_end - r.due)
        # The rate counts whole ticks: from the end of the tick running as
        # the window opens to the end of the last tick begun before it
        # closes, every token and all the time between (a bare window
        # would count whole ticks over a fixed time, and read in steps of
        # one tick's tokens).  Where the engine idles at an edge, the edge
        # is the window's own.
        r0 = t_open if self.rate_t0 is None else self.rate_t0
        r1 = t_close if self.rate_t1 is None else max(t_close, self.rate_t1)
        gaps, toks, rate_toks = [], 0, 0
        for r in self.recs.values():
            for a, b in zip(r.times, r.times[1:]):
                if t_open <= b < t_close:
                    gaps.append(b - a)
            toks += sum(1 for t in r.times if t_open <= t < t_close)
            rate_toks += sum(1 for t in r.times if r0 < t <= r1)
        if self.src.closed:
            attempted = sum(1 for r in self.recs.values()
                            if r.submitted < t_close)
        else:
            attempted = len(due)
        self.summary = dict(
            attempted=attempted, failed=failed, ttft=ttft, gaps=gaps,
            tokens=toks, rate_tokens=rate_toks, rate_edges=(r0, r1),
            rate_s=r1 - r0,
            requests_done=sum(r.done for r in self.recs.values()))
        return {
            "setup_s": self.t_setup_end - self.t_start,
            "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
            "itl_p50_ms": percentile(gaps, 50) * 1e3 if gaps else None,
            "itl_p99_ms": percentile(gaps, 99) * 1e3 if gaps else None,
            "output_tok_s": rate_toks / (r1 - r0),
        }

    def layer_record(self, reduced) -> dict:
        """What a per-layer metric reader gets: the cell, the peaks, the
        window's ticks as the harness saw them (only those inside the
        traced window when there is a trace), the window differences of
        the engine's counters, and the reduced trace."""
        ticks = self.ticks
        if self.trace_t0 is not None:
            ticks = [t for t in ticks
                     if t.t0 >= self.trace_t0 and t.t1 <= self.trace_t1]
        d = {k: self.stats1[k] - self.stats0[k] for k in self.stats0
             if isinstance(self.stats0[k], (int, float))}
        cfg, peaks = self.cfg, self.peaks
        dec = [cost.decode_step(cfg, t.decode_rows, t.decode_ctx)
               for t in ticks if t.decode_rows]
        pre = [cost.prefill_chunk(cfg, n, o) for t in ticks
               for n, o in t.chunks]
        L = cfg["num_hidden_layers"]
        kdec = [cost.paged_decode_call(cfg, t.decode_rows,
                                       t.decode_ctx).scale(L)
                for t in ticks if t.decode_rows]
        kpre = [cost.paged_prefill_call(cfg, n, o).scale(L)
                for t in ticks for n, o in t.chunks]
        sum_cost = lambda cs: sum(cs, cost.Cost())              # noqa: E731
        dev = (reduced or {}).get("modules_s", {})
        notes = {}
        for name, cs, key in (("decode", dec, "decode"),
                              ("prefill", pre, "prefill")):
            if dev.get(key):
                notes[f"{name}_flop_share_pct"] = 100.0 * sum_cost(
                    cs).flops / peaks["bf16_flops"] / dev[key]
        for name, cs in (("paged_decode", kdec), ("paged_prefill", kpre)):
            if cs:
                notes[f"{name}_bound"] = sum_cost(cs).bound(peaks)
        return {
            "notes": notes,
            "cell": self.cell.name, "config": cfg, "peaks": peaks,
            "window_s": (self.trace_t1 - self.trace_t0
                         if self.trace_t0 is not None else self.seconds),
            "ticks": ticks, "stats": d, "trace": reduced,
            "decode": sum_cost(dec), "decode_least_s":
                sum(c.least_s(peaks) for c in dec),
            "prefill": sum_cost(pre), "prefill_least_s":
                sum(c.least_s(peaks) for c in pre),
            "paged_decode": sum_cost(kdec), "paged_decode_least_s":
                sum(c.least_s(peaks) for c in kdec),
            "paged_prefill": sum_cost(kpre), "paged_prefill_least_s":
                sum(c.least_s(peaks) for c in kpre),
        }

    def served(self) -> List[Rec]:
        """Every request that served a token, finished or not, with the
        tokens it served: a greedy token is final once served."""
        out = []
        for rec in sorted(self.recs.values(), key=lambda r: r.rid):
            if rec.engine_req is not None and rec.engine_req.out_tokens:
                rec.out = list(rec.engine_req.out_tokens)
                out.append(rec)
        return out

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.local_devices()[:self.cell.chips]]
        return int(max(peaks))

    def release_engine(self) -> None:
        self.eng = None
        gc.collect()


def run(cell: specmod.Cell, seed: int, seconds: float, traced: bool,
        peaks: dict, t_start: float, device: dict) -> dict:
    """A whole run; returns the result object the command prints last."""
    r = Run(cell, seed, seconds, traced, peaks, t_start)
    r.build()
    log(f"engine: slots={r.eng.slots} max_seq={r.max_seq} "
        f"prefill_buckets={r.eng.prefill_buckets} "
        f"max_tokens_per_tick={r.eng.max_tokens_per_tick} "
        f"pool_pages={r.eng.alloc.num_blocks}")
    r.warm_up()
    log(f"warm-up done at {time.perf_counter() - t_start:.2f} s; "
        f"{r.compiles.count} executables built or loaded "
        f"({r.compiles.seconds:.2f} s)")
    r.drive()
    e2e = r.end_to_end()
    s = r.summary
    in_window = r.compiles1 - r.compiles0
    late = np.asarray(r.lateness) * 1e3
    log(f"compilations inside the window: {in_window}")
    log(f"generator lateness ms: p50 {np.median(late):.3f} "
        f"p99 {np.percentile(late, 99):.3f} max {late.max():.3f} "
        f"over {len(late)} submits")
    log(f"window: {s['attempted']} requests due, {s['failed']} without a "
        f"first token at the end of the drain, {len(s['ttft'])} TTFT and "
        f"{len(s['gaps'])} gap samples, {s['tokens']} tokens, "
        f"{len(r.ticks)} ticks, "
        f"{sum(1 for t in r.ticks if t.chunks)} with a prefill chunk")
    log(f"output rate: {s['rate_tokens']} tokens over {s['rate_s']!r} s, "
        f"tick boundary to tick boundary ({s['tokens']} tokens over the "
        f"bare {r.seconds!r} s window)")
    if s["ttft"]:
        log(f"ttft ms: p50 {percentile(s['ttft'], 50) * 1e3:.3f} "
            f"p90 {percentile(s['ttft'], 90) * 1e3:.3f}")
    d = {k: r.stats1[k] - r.stats0[k] for k in ("ticks", "occupancy_sum")}
    if d["ticks"] and "rate_per_s" in cell.workload:
        occ = d["occupancy_sum"] / d["ticks"]
        log(f"slot occupancy {occ:.4f} at {cell.workload['rate_per_s']} "
            f"req/s: the slots saturate at about "
            f"{cell.workload['rate_per_s'] / occ:.4f} req/s")
    if s["gaps"]:
        log(f"itl ms: p50 {percentile(s['gaps'], 50) * 1e3:.3f} "
            f"p95 {percentile(s['gaps'], 95) * 1e3:.3f} "
            f"p99 {percentile(s['gaps'], 99) * 1e3:.3f}")
    mem = r.memory_peak()
    device = dict(device, memory_peak_bytes=mem)

    metrics = {}
    breakdown = None
    if traced:
        reduced = trace_mod.reduce(r.prof_dir, cell.chips)
        shutil.rmtree(r.prof_dir, ignore_errors=True)
        rec = r.layer_record(reduced)
        for m in cell.per_layer:
            v = specmod.load_metric(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log("per-layer: " + json.dumps(rec.get("notes", {})))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = reduced["breakdown"]
    else:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    served = r.served()
    r.release_engine()
    t_chk = time.perf_counter()
    ok, numbers, vals, n_tok = check.compare(cell, seed, served, r.params)
    log(f"reference check took {time.perf_counter() - t_chk:.2f} s; "
        f"readings over {n_tok} served tokens: "
        + ", ".join(f"{k} {v!r}" for k, v in vals.items()))
    for name, (value, limit) in numbers.items():
        log(f"check {name}: {value!r} limit {limit!r}")
    out = {"correct": bool(ok),
           "attempted": s["attempted"], "failed": s["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in numbers.items()}
    return out
