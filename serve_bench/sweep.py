#!/usr/bin/env python3
"""Finds an open-loop cell's knee on the chip: the highest rate its slots
sustain.

    python3 serve_bench/sweep.py --workload <cell> --rates 0.1,0.2,0.3 \\
        --seconds 200 [--seed 7]

One engine is built and warmed once; the rates then run in the order
given, each from an empty engine with the cell's own lead-in and preload,
then a window of arrivals from the cell's mix.  Give the window several
request lifetimes: a backlog takes about one lifetime to show.
Per rate, one JSON line: the slots' mean occupancy in the window and the
rate at which they would be full (rate / occupancy, the knee by Little's
law while requests hold their slot as long as at this rate), the queue's
mean in the window's first and last thirds, TTFT p50/p90, the inter-token
gap p50/p95, and the share of ticks that carry a prefill chunk.  The
benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from serve_bench import harness, run, spec

    cell = spec.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    run.setup_compile_cache(jax)
    peaks = spec.load_peaks(devs[0].device_kind)
    r = harness.Run(cell, args.seed, args.seconds, False, peaks, T_START)
    r.build()
    r.warm_up()
    for rate in (float(x) for x in args.rates.split(",")):
        cell.workload["rate_per_s"] = rate
        r.eng.run_until_drained()
        r.recs, r.ticks, r.lateness = {}, [], []
        r.stats0 = r.stats1 = None
        r.drive()
        e2e = r.end_to_end()
        occ = ((r.stats1["occupancy_sum"] - r.stats0["occupancy_sum"])
               / max(1, r.stats1["ticks"] - r.stats0["ticks"]))
        s = r.summary
        ticks = r.ticks
        third = max(1, len(ticks) // 3)
        q0 = float(np.mean([t.queued for t in ticks[:third]]))
        q1 = float(np.mean([t.queued for t in ticks[-third:]]))
        done = sum(1 for x in r.recs.values()
                   if x.done and x.times[-1] < r.t_close)
        print(json.dumps({
            "rate": rate, "due": s["attempted"], "failed": s["failed"],
            "slot_occupancy": occ, "slots_full_at_per_s": rate / occ,
            "queue_first_third": q0, "queue_last_third": q1,
            "growing": q1 > q0 + 1.0,
            "completed_per_s": done / args.seconds,
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p90_ms": harness.percentile(s["ttft"], 90) * 1e3,
            "itl_p50_ms": harness.percentile(s["gaps"], 50) * 1e3,
            "itl_p95_ms": harness.percentile(s["gaps"], 95) * 1e3,
            "tick_ms": 1e3 * float(np.mean([t.t1 - t.t0 for t in ticks])),
            "prefill_tick_share": sum(1 for t in ticks if t.chunks)
            / max(1, len(ticks)),
            "lateness_p99_ms": float(np.percentile(r.lateness, 99)) * 1e3,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
