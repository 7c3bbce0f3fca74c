"""Reduces a profiler trace of the measured window to per-layer inputs.

Read with ``jax.profiler.ProfileData`` (nothing but JAX).  Device planes
are the ``/device:TPU:<n>`` planes; on each, the ``XLA Ops`` line holds one
event per operation (a ``while`` holds the ops of its body) and the ``XLA
Modules`` line one per program execution.

The trace does not name Pallas kernels: each is a ``tpu_custom_call``
whose event is named by its HLO text, ``%closed_call.N = <result>
custom-call(<operands>)``.  The attention kernels are the ones that return
the flash-attention triple ``(out, m f32, l f32)``; the first operand is
the block table, two-dimensional ``s32[B, MB]`` for paged decode
(``kernels/decode_attention.py``) and one-dimensional ``s32[MB]`` for
paged prefill (``kernels/prefill_attention.py``).  A program is classified
by the attention kernel it runs: a prefill chunk or a decode step.

The host plane carries the harness's own spans (``engine.step``,
``submit``, ``generator.wait``, ``drain``), which bound the traced window
on the trace's clock and name what the host was doing in each idle gap of
the device.

    python3 -m serve_bench.trace --dump <trace dir>   # look at one by hand
"""
from __future__ import annotations

import argparse
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

# result (out, m f32, l f32), then the first operand: the block table
_ATTN = re.compile(r"^\S+ = \(\w+\[[\d,]*\][^ ]* f32\[[\d,]*\][^ ]* "
                   r"f32\[[\d,]*\][^ ]*\) custom-call\(s32\[([\d,]*)\]")
SPANS = ("engine.step", "submit", "generator.wait", "drain")
TOP = 10


def _xplane(prof_dir: str) -> str:
    found = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {prof_dir}")
    return max(found, key=os.path.getmtime)


def kernel_of(text: str):
    """``paged_decode``, ``paged_prefill`` or None for one op's text."""
    if "tpu_custom_call" not in text:
        return None
    m = _ATTN.match(text)
    if m is None:
        return None
    return "paged_decode" if "," in m.group(1) else "paged_prefill"


def self_times(events):
    """(start, end, text, self ns) with each op's time less the ops nested
    in it (a ``while`` less its body), so no time is counted twice."""
    out, stack = [], []
    for s, e, x in sorted(events, key=lambda v: (v[0], -v[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, x, e - s]
        if stack:
            stack[-1][3] -= e - s
        out.append(rec)
        stack.append(rec)
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def load(prof_dir: str, chips: int) -> dict:
    """Plain lists out of the trace: device ops and modules per chip
    (start_ns, end_ns, text) and host spans (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane(prof_dir))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name[len("/device:TPU:"):]:
            lines = {ln.name: ln for ln in plane.lines}
            ops = [(e.start_ns, e.end_ns, e.name)
                   for e in (lines["XLA Ops"].events
                             if "XLA Ops" in lines else [])]
            mods = [(e.start_ns, e.end_ns, e.name)
                    for e in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else [])]
            devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in SPANS:
                        host.append((e.start_ns, e.end_ns, e.name))
    devices.sort(key=lambda d: d[0])
    return {"devices": devices[:chips], "host": sorted(host)}


def reduce_loaded(data: dict) -> dict:
    """Busy and idle time, program and kernel time, and the breakdown."""
    host = data["host"]
    if not host:
        raise ValueError("no harness spans in the trace")
    t0 = min(s for s, _, _ in host)
    t1 = max(e for _, e, _ in host)
    window_ns = t1 - t0
    busy, modules, kernels = [], defaultdict(float), defaultdict(float)
    ops_time: Dict[str, float] = defaultdict(float)
    gaps = []
    for _, ops, mods in data["devices"]:
        clipped = [(max(s, t0), min(e, t1), x) for s, e, x in ops
                   if e > t0 and s < t1]
        u = union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in u))
        # classify each program execution by the kernel inside it
        ops_sorted = sorted(clipped)
        j = 0
        spans = []
        for s, e, name in sorted(mods):
            if e <= t0 or s >= t1:
                continue
            while j < len(ops_sorted) and ops_sorted[j][0] < s:
                j += 1
            kinds = set()
            k = j
            while k < len(ops_sorted) and ops_sorted[k][0] < e:
                kk = kernel_of(ops_sorted[k][2])
                if kk:
                    kinds.add(kk)
                k += 1
            kind = ("prefill" if "paged_prefill" in kinds else
                    "decode" if "paged_decode" in kinds else "other")
            modules[kind] += (min(e, t1) - max(s, t0)) / 1e9
            spans.append((max(s, t0), min(e, t1), kind))
        # self time of each op, keyed by the program it ran in
        spans.sort()
        j = 0
        for s, e, x, own in self_times(clipped):
            k = kernel_of(x)
            if k:
                kernels[k] += (e - s) / 1e9
            while j + 1 < len(spans) and spans[j][1] <= s:
                j += 1
            where = spans[j][2] if spans and spans[j][0] <= s < spans[j][1] \
                else "other"
            ops_time[f"{where}: {k or x[:100]}"] += own / 1e9
        prev = t0
        for s, e in u + [(t1, t1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    n = max(1, len(data["devices"]))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:TOP]:
        mid = (s + e) // 2
        what = [name for hs, he, name in host if hs <= mid < he]
        idle.append([what[-1] if what else "host", (e - s) / 1e9])
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "modules_s": {k: v / n for k, v in modules.items()},
        "kernels_s": {k: v / n for k, v in kernels.items()},
        "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops],
                      "idle_gaps": idle},
    }


def reduce(prof_dir: str, chips: int) -> dict:
    return reduce_loaded(load(prof_dir, chips))


def dump(prof_dir: str, limit: int = 40) -> None:
    """Print planes, lines, event counts and the commonest event names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane(prof_dir))
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for ln in plane.lines:
            evs = list(ln.events)
            names = defaultdict(lambda: [0, 0])
            for e in evs:
                names[e.name][0] += 1
                names[e.name][1] += e.duration_ns
            print(f"  LINE {ln.name!r}: {len(evs)} events")
            for nm, (c, d) in sorted(names.items(),
                                     key=lambda kv: -kv[1][1])[:limit]:
                print(f"    {c:7d} {d / 1e6:12.3f} ms  {nm[:150]}")
            for e in evs[:3]:
                print(f"    e.g. {e.name[:80]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={list(e.stats)[:12]}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump", required=True, help="a profiler output dir")
    dump(ap.parse_args().dump)
