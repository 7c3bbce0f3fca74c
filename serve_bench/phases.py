#!/usr/bin/env python3
"""Device idle time put down to the phases of ``ServeEngine.step``.

The engine opens profiler spans inside ``step`` (``SPANS`` in
``repro/serve/engine.py``: ``serve.step``, ``serve.admit``,
``serve.prefill``, ``serve.decode``, ``serve.sample``, ``serve.retire``).
They land on the host plane, on the device planes' clock.  This reduction
reads them beside the harness's own spans (``trace.SPANS``) and puts every
stretch of device idle time inside the traced window down to the
innermost span covering it.  The window and the device's busy intervals
are those of ``trace.py``, so the idle time split here is the idle time
``device_idle_share`` reads.

Idle time under ``serve.sample`` is the host's sampling (its first read
waits for the decode program, so the device is busy until then); under
``serve.decode`` and ``serve.prefill`` it is input building, upload and
dispatch; under ``serve.admit``, ``serve.retire`` and ``serve.step``
itself it is scheduling.  Idle time inside ``engine.step`` under no
``serve.*`` span is what the spans do not cover.

``run.py`` does not read this: the harness removes its trace before the
per-layer readers run, and ``trace.load`` keeps only the harness's spans.
Use it on a one-chip trace kept by hand, or run a cell's traced window
here and keep the trace:

    python3 -m serve_bench.phases --trace-dir <dir>
    python3 -m serve_bench.phases --workload <cell> --seed <n> \\
        --seconds <s> [--keep <dir>]

Temporary: this is a second reduction beside ``trace.py``.  Once the
benchmark reads the engine's spans, ``segments`` and ``reduce_loaded``
belong in ``trace.py``; ``idle_intervals``, ``run_cell`` and the
``--workload`` path then go, leaving at most the ``--trace-dir`` reader.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from collections import defaultdict

from serve_bench import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STEP = "engine.step"            # the harness's span around each step()
# per-tick idle of each group of innermost program spans, in ms
GROUPS = {
    "sample_idle_ms": ("serve.sample",),
    "dispatch_idle_ms": ("serve.decode", "serve.prefill"),
    "schedule_idle_ms": ("serve.admit", "serve.retire", "serve.step"),
}


def program_spans(prof_dir: str):
    """The engine's spans on the host plane: (start_ns, end_ns, name,
    metadata dict), in start order."""
    from jax.profiler import ProfileData

    from repro.serve.engine import SPANS

    pd = ProfileData.from_file(trace._xplane(prof_dir))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in SPANS:
                        out.append((e.start_ns, e.end_ns, e.name,
                                    dict(e.stats)))
    return sorted(out, key=lambda v: (v[0], -v[1]))


def load(prof_dir: str, chips: int) -> dict:
    """``trace.load``'s lists, and the engine's spans under ``program``."""
    data = trace.load(prof_dir, chips)
    data["program"] = program_spans(prof_dir)
    return data


def segments(spans):
    """Cut time at every span edge: (start, end, innermost name, inside
    ``engine.step``) for each stretch some span covers.  Spans of one
    thread nest, so the innermost is the one opened last."""
    edges = []
    for k, (s, e, _) in enumerate(spans):
        if e > s:
            edges.append((s, 1, -e, k))   # at one instant: ends first,
            edges.append((e, 0, 0, k))    # then the outer span opens
    edges.sort()
    out, stack, prev = [], [], None
    for t, opens, _, k in edges:
        if stack and t > prev:
            names = [spans[j][2] for j in stack]
            out.append((prev, t, names[-1], STEP in names))
        if opens:
            stack.append(k)
        else:
            stack.remove(k)
        prev = t
    return out


def idle_intervals(data: dict, t0, t1):
    """Per chip, the stretches of [t0, t1) in which no op runs."""
    out = []
    for _, ops, _ in data["devices"]:
        busy = trace.union([(max(s, t0), min(e, t1)) for s, e, _ in ops
                            if e > t0 and s < t1])
        idle, prev = [], t0
        for s, e in busy + [(t1, t1)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        out.append(idle)
    return out


def reduce_loaded(data: dict) -> dict:
    """Idle seconds by innermost span, averaged over chips.

    ``idle_by_span``: idle inside each innermost program span;
    ``step_idle_s``: all idle inside ``engine.step``;
    ``uncovered_s``: the part of it under no program span;
    ``program_ticks``: ``serve.step`` spans inside the window;
    ``idle_gaps``: the longest gaps, named by the innermost span (the
    program's included) at their midpoint."""
    host = data["host"]
    if not host:
        raise ValueError("no harness spans in the trace")
    t0 = min(s for s, _, _ in host)
    t1 = max(e for _, e, _ in host)
    prog = data.get("program", [])
    spans = [(s, e, n) for s, e, n in host] + \
        [(s, e, n) for s, e, n, _ in prog]
    segs = segments(spans)
    by_span = defaultdict(float)
    step_idle = uncovered = 0.0
    gaps = []
    for idle in idle_intervals(data, t0, t1):
        gaps += idle
        j = 0
        for a, b in idle:
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < b:
                s, e, name, in_step = segs[k]
                d = min(b, e) - max(a, s)
                if d > 0 and in_step:
                    step_idle += d
                    if name == STEP:
                        uncovered += d
                    else:
                        by_span[name] += d
                k += 1
    n = max(1, len(data["devices"]))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:trace.TOP]:
        mid = (a + b) // 2
        what = [name for s, e, name, _ in segs if s <= mid < e]
        named.append([what[0] if what else "host", (b - a) / 1e9])
    return {
        "idle_by_span": {k: v / n / 1e9 for k, v in sorted(by_span.items())},
        "step_idle_s": step_idle / n / 1e9,
        "uncovered_s": uncovered / n / 1e9,
        "program_ticks": sum(1 for s, e, name, _ in prog
                             if name == "serve.step" and s >= t0 and e <= t1),
        "idle_gaps": named,
    }


def per_tick_ms(r: dict) -> dict:
    """Each group's idle per ``serve.step``, in ms; empty where the trace
    holds no ``serve.step`` (a program without the spans)."""
    n = r["program_ticks"]
    if not n:
        return {}
    return {k: 1e3 * sum(r["idle_by_span"].get(s, 0.0) for s in names) / n
            for k, names in GROUPS.items()}


def summary(r: dict) -> str:
    """One line: the idle inside ``engine.step`` by innermost span."""
    n = max(1, r["program_ticks"])
    total = r["step_idle_s"]
    parts = [f"{k} {1e3 * v:.3f} ms" for k, v in sorted(
        r["idle_by_span"].items(), key=lambda kv: -kv[1])]
    share = 100.0 * r["uncovered_s"] / total if total else 0.0
    return (f"idle inside engine.step: {1e3 * total:.3f} ms over "
            f"{r['program_ticks']} ticks ({1e3 * total / n:.3f} ms a tick): "
            + ", ".join(parts)
            + f"; under no serve.* span {1e3 * r['uncovered_s']:.3f} ms "
            f"({share:.2f} %)")


def run_cell(cell, seed: int, seconds: float, peaks: dict,
             keep: str = None) -> dict:
    """The cell's set-up, lead-in and traced window as ``run.py`` makes
    them (no reference check), reduced; the trace is copied to ``keep``."""
    from serve_bench import harness, spec

    r = harness.Run(cell, seed, seconds, True, peaks, time.perf_counter())
    r.build()
    r.warm_up()
    r.drive()
    reduced = trace.reduce(r.prof_dir, cell.chips)
    rec = r.layer_record(reduced)
    out = {"cell": cell.name, "seed": seed,
           "tick_ms": spec.load_metric("tick_ms")(rec),
           "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
           "kernels_s": reduced["kernels_s"],
           "modules_s": reduced["modules_s"],
           "harness_idle_gaps": reduced["breakdown"]["idle_gaps"]}
    out.update(reduce_loaded(load(r.prof_dir, cell.chips)))
    if keep:
        shutil.copytree(r.prof_dir, keep, dirs_exist_ok=True)
    shutil.rmtree(r.prof_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", help="a profiler output dir to reduce")
    ap.add_argument("--workload", help="run this cell's traced window")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--keep", help="copy the cell's trace here")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if args.trace_dir:
        r = reduce_loaded(load(args.trace_dir, 1))
    elif args.workload:
        import jax

        from serve_bench import spec
        from serve_bench.run import setup_compile_cache

        setup_compile_cache(jax)
        r = run_cell(spec.load_cell(args.workload), args.seed, args.seconds,
                     spec.load_peaks(jax.devices()[0].device_kind),
                     args.keep)
    else:
        ap.error("give --trace-dir or --workload")
    r.update(per_tick_ms(r))
    print(summary(r), file=sys.stderr, flush=True)
    print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
