#!/usr/bin/env python3
"""The control, on the chip: the int8 reference in the program's place.

    python3 serve_bench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 20

For each seed, in one process: runs the cell as ``run.py`` does (same
engine, warm-up, traffic and window, shorter if asked), then puts the
same sample of served requests through ``check.compare`` twice, with the
cell's own limits:

* ``program``: the served tokens, as ``run.py`` judges them;
* ``control``: at the same positions, the token that the reference
  computed in int8 (W8A8) puts first, in place of the served token.

One JSON line per seed on standard output, each with the readings and
``correct`` of both.  Exits 1 if the control came out correct on any
seed, or the program did not.  The benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from serve_bench import check, harness, run, spec

    cell = spec.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 3
    peaks = spec.load_peaks(devs[0].device_kind)
    run.setup_compile_cache(jax)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = harness.Run(cell, seed, args.seconds, False, peaks, t0)
        r.build()
        r.warm_up()
        r.drive()
        served = r.served()
        r.release_engine()
        line = {"seed": seed}
        for side in ("program", "control"):
            ok, compared, vals, n = check.compare(
                cell, seed, served, r.params, control=side == "control")
            line[side] = {"correct": ok, "tokens": n, "readings": vals,
                          "compared": compared}
            bad += ok != (side == "program")
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del r, served
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
