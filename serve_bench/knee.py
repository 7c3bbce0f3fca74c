#!/usr/bin/env python3
"""An open-loop cell's knee by Little's law, from its measured tick times.

    python3 serve_bench/knee.py --workload <cell> --tick-s 0.386 \\
        --chunk-s 0.28 [--rates 0.18,0.2,0.22] [--seconds 4000]

Simulates the engine's slots under the cell's own schedule (the same
lengths, arrivals, lead-in and preload as a run): FIFO admission into
``slots``, one tick for a request's prefill (it gives the first token),
then one tick per further token; a tick takes ``--tick-s``, and
``--chunk-s`` more when it carries a prefill chunk.  Both come from a chip
run (``itl_p50_ms``; the gap on ticks with a chunk).  Per rate it prints
the slots' mean occupancy over the cell's own window and over a window of
``--seconds``, where the start has worn off: the knee is the rate at which
the long-run occupancy reaches 1, and a run's window reads about its
long-run occupancy only if the cell's preload and lead-in start it near
steady state.  No chip, no JAX.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from serve_bench import spec, traffic as tr  # noqa: E402


def occupancy(cell, rate: float, window_s: float, tick_s: float,
              chunk_s: float) -> dict:
    """Mean slot occupancy and queue over the window, time-weighted."""
    w = dict(cell.workload, rate_per_s=rate)
    lead = float(w["lead_in_s"])
    src = tr.Traffic(cell.mix, 0, cell.config["vocab_size"],
                     1 << 30).source(w, lead, window_s)
    slots = int(cell.engine["slots"])
    now, queue, active = -lead, [], []
    busy = waiting = span = 0.0
    while now < window_s:
        queue += [[r.max_new, True] for r, _ in src.take(now)]
        while queue and len(active) < slots:
            active.append(queue.pop(0))
        dt = tick_s + (chunk_s if any(a[1] for a in active) else 0.0)
        if now >= 0:
            busy += dt * len(active) / slots
            waiting += dt * len(queue)
            span += dt
        for a in active:
            a[0], a[1] = a[0] - 1, False
        active = [a for a in active if a[0] > 0]
        now += dt
    return {"occupancy": busy / span, "queue": waiting / span}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tick-s", type=float, required=True)
    ap.add_argument("--chunk-s", type=float, required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--seconds", type=float, default=4000.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    rates = ([float(x) for x in args.rates.split(",")] if args.rates
             else [cell.workload["rate_per_s"]])
    for rate in rates:
        print(json.dumps({
            "rate": rate,
            "window": occupancy(cell, rate, 50.0, args.tick_s, args.chunk_s),
            "long_run": occupancy(cell, rate, args.seconds, args.tick_s,
                                  args.chunk_s)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
