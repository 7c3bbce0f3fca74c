#!/usr/bin/env python3
"""Serving benchmark: one cell of BENCHMARK.json, one run, on the chip.

    python3 serve_bench/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Builds the cell's engine with random bf16 weights made from ``--seed``,
warms every shape the cell's traffic can reach, runs a lead-in, measures
for ``--seconds``, drains, and checks a sample of the served tokens
against the float32 reference.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``; ``check`` last).  With
``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a profiler trace of the window.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program cannot be imported.  The
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def setup_compile_cache(jax) -> str:
    """Follow ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself);
    otherwise keep the cache at a fixed path inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from serve_bench import harness, spec

    cell = spec.load_cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"serve_bench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    peaks = spec.load_peaks(devs[0].device_kind)
    cache = setup_compile_cache(jax)
    harness.log(f"device_kind={devs[0].device_kind} count={len(devs)} "
                f"compile_cache={cache} seed={args.seed}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace), peaks,
                      T_START, device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
