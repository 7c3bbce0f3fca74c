#!/usr/bin/env python3
"""Compiles each cell's serving steps for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 serve_bench/compile_check.py [cell ...]

For every cell (default: all of BENCHMARK.json) this compiles, at the
cell's engine sizes, the batched decode step and the prefill chunk of each
bucket at the largest page count, as the engine jits them, and prints
``memory_analysis()`` and the count of Pallas kernels (``tpu_custom_call``)
of each.  Nothing runs: the compiler refuses what would not fit or tile.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    from repro.models.runner import ModelRunner
    from serve_bench import cost, harness, spec, weights

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    names = argv or [w["name"] for w in spec.load_benchmark()["workloads"]]
    for name in names:
        cell = spec.load_cell(name)
        c, e = cell.config, cell.engine
        mc = harness.model_config(c)
        slots, max_seq, bs = e["slots"], e["max_seq"], c["block_size"]
        mb = -(-max_seq // bs)
        nb = e.get("num_blocks") or 1 + slots * mb
        runner = ModelRunner(mc, slots, max_seq, kv_dtype=c["kv_dtype"])
        on_chip = lambda t: jax.tree.map(                        # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            t)
        params = on_chip(jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16),
            weights.shapes(c), is_leaf=lambda x: isinstance(x, tuple)))
        state = on_chip(jax.eval_shape(
            lambda: runner.init_state(nb, bs, jnp.bfloat16)))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,      # noqa: E731
                                              sharding=chip)
        print(f"== {name}: slots {slots}, max_seq {max_seq}, pool {nb} "
              f"pages = {nb * bs * cost.kv_bytes_per_token(c) / 1e9:.3f} GB, "
              f"weights {cost.weight_bytes(c) / 1e9:.3f} GB", flush=True)
        with ops.use_mode("pallas"):
            dec = jax.jit(lambda p, s, t, l, tb, m: runner.decode(
                p, s, t, l, tb, m)).lower(
                params, state, i32(slots), i32(slots), i32(slots, mb),
                jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=chip)
            ).compile()
            _report("decode", dec)
            buckets = sorted({min(b, max_seq) for b in e.get(
                "prefill_buckets", (32, 128, 512, 2048))} | {max_seq})
            for b in buckets:
                pre = jax.jit(lambda p, s, t, n, o, bt, sl:
                              runner.prefill_chunk(p, s, t, n, o, bt, sl)
                              ).lower(params, state, i32(1, b), i32(), i32(),
                                      i32(mb), i32()).compile()
                _report(f"prefill {b} x {mb} pages", pre)
    return 0


def _report(what, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"  {what}: arguments {m.argument_size_in_bytes:,} outputs "
          f"{m.output_size_in_bytes:,} temporaries {m.temp_size_in_bytes:,} "
          f"aliased {m.alias_size_in_bytes:,} -> {total:,} bytes; "
          f"tpu_custom_call {compiled.as_text().count('tpu_custom_call')}",
          flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
