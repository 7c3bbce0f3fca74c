"""Loop-aware HLO cost walker: trip-count multiplication, collective byte
accounting, dot FLOPs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import hlo_analysis as H


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_scan_flops_multiplied():
    def f(x, ws):
        def step(c, w):
            return jax.nn.relu(jnp.dot(c, w)), None
        return jax.lax.scan(step, x, ws)[0]

    txt = _compiled_text(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                         jax.ShapeDtypeStruct((12, 64, 64), jnp.float32))
    s = H.analyze(txt)
    want = 12 * 2 * 64 ** 3
    assert 0.95 * want <= s.flops <= 1.3 * want, s.flops
    assert 12 in s.while_trips


def test_unrolled_matches_xla_costanalysis():
    def f(x, w):
        for _ in range(4):
            x = jnp.dot(x, w)
        return x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x, x).compile()
    ours = H.analyze(compiled.as_text()).flops
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):   # older jax: one dict per program
        ca = ca[0]
    xla = ca["flops"]
    assert abs(ours - xla) / xla < 0.05


def test_nested_scan_trips_compound():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return jnp.dot(ci, w), None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, ws)[0]

    txt = _compiled_text(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
                         jax.ShapeDtypeStruct((5, 32, 32), jnp.float32))
    s = H.analyze(txt)
    want = 5 * 3 * 2 * 32 ** 3
    assert 0.9 * want <= s.flops <= 1.3 * want, s.flops


def test_collective_bytes_counted(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
x = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
fn = jax.jit(lambda a: a.sum(0), in_shardings=NamedSharding(mesh, P('x', None)),
             out_shardings=NamedSharding(mesh, P()))
txt = fn.lower(x).compile().as_text()
s = H.analyze(txt)
assert s.total_collective_bytes > 0, s.collective_bytes
assert 'all-reduce' in s.collective_bytes or 'all-gather' in s.collective_bytes
print('OK', dict(s.collective_bytes))
""")
    assert "OK" in out


def test_shape_parsing_tuple_with_comment():
    comps, entry = H.parse_hlo("""
HloModule m
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %t = (s32[], f32[4]{0}, /*index=2*/f32[2,2]{1,0}) tuple(%p)
  ROOT %w = f32[4]{0} while(%p), condition=%c, body=%b
}
""")
    ins = comps["main"].by_name["w"]
    assert ins.opcode == "while"
    assert comps["main"].by_name["t"].opcode == "tuple"
