"""Planner lane selection + mapping cost-model properties."""
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
import jax

from repro.configs import DECODE_32K, PREFILL_32K, TRAIN_4K, get_config, reduced
from repro.configs.base import ShapeSpec
from repro.core import mapping, planner
from repro.core.planner import Lane, OpProfile, TPU_V5E


def test_lane_crossover_with_batch():
    """The paper's Fig. 4B crossover: FC moves from bandwidth lane to
    matrix lane as batch (m) grows."""
    lo = planner.classify(OpProfile("fc", 1, 4096, 4096))
    hi = planner.classify(OpProfile("fc", 4096, 4096, 4096))
    assert lo == Lane.VPU and hi == Lane.MXU


def test_decode_attention_always_bandwidth_lane():
    for s in (4096, 32768, 524288):
        op = OpProfile("attn_sv", 1, s, 128, weight_static=False)
        assert planner.classify(op) == Lane.VPU


@hypothesis.settings(max_examples=40, deadline=None)
@hypothesis.given(m=st.integers(1, 1 << 20), k=st.sampled_from([512, 4096]),
                  n=st.sampled_from([512, 8192]))
def test_lane_monotone_in_m(m, k, n):
    """If m is on the MXU lane, any larger m' >= m stays MXU (monotone
    intensity)."""
    if planner.classify(OpProfile("fc", m, k, n)) == Lane.MXU:
        assert planner.classify(OpProfile("fc", m * 2, k, n)) == Lane.MXU


@hypothesis.settings(max_examples=30, deadline=None)
@hypothesis.given(k=st.integers(128, 16384), n=st.integers(128, 65536))
def test_blocks_fit_vmem(k, n):
    op = OpProfile("fc", 1 << 16, k, n)
    bm, bn = planner.plan_blocks(op)
    assert bm % 128 == 0 and bn % 128 == 0
    assert k * bn * TPU_V5E.dtype_bytes <= TPU_V5E.vmem_bytes


def test_profiles_cover_all_archs():
    from repro.configs import ARCHS, SHAPES, shape_applicable
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES:
            if not shape_applicable(cfg, shape)[0]:
                continue
            plans = planner.plan_model(cfg, shape)
            assert plans, (arch, shape.name)
            assert all(p.op.flops > 0 for p in plans)


def test_fc_split_cost_prefers_input_split_for_wide_k():
    """Paper §3.3: with cheap reduction, imbalanced FCs (long input, short
    output) should be input-split."""
    c = mapping.choose_fc_split(m=1024, k=16384, n=512, tp=16,
                                input_sharded=True)
    assert c.split == "input"
    c2 = mapping.choose_fc_split(m=1024, k=512, n=16384, tp=16,
                                 input_sharded=True)
    assert c2.split == "output"


def test_megatron_mixed_beats_pure_output():
    r = mapping.megatron_block_bytes(4096, 5120, 13824, tp=16)
    assert r["speedup"] > 1.0


@pytest.mark.parametrize("arch", ["qwen2-72b", "qwen2-moe-a2.7b", "rwkv6-3b",
                                  "zamba2-7b"])
def test_sharding_plan_divisibility(subproc, arch):
    """Every emitted PartitionSpec divides its dim on the production mesh
    (validated by actually constructing NamedShardings on 8 fake devices
    with a (2,2,2) mesh)."""
    code = f"""
import jax
from repro.launch.mesh import make_mesh
from repro.configs import get_config, TRAIN_4K, DECODE_32K
from repro.core import mapping
from repro.models import model
from repro.train import step as ts
cfg = get_config({arch!r})
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,2,2), ('pod','data','model'))
state = ts.init_state_shaped(cfg)
sshape = jax.eval_shape(lambda: model.init_decode_state(cfg, DECODE_32K.global_batch, 1024))
for shape, st_ in ((TRAIN_4K, None), (DECODE_32K, sshape)):
    plan = mapping.sharding_plan(cfg, mesh, shape, params_shape=state.params,
                                 state_shape=st_)
    def check(spec, leaf):
        ns = jax.sharding.NamedSharding(mesh, spec)
        assert ns.is_fully_addressable is not None
        # shard_shape raises if not divisible
        ns.shard_shape(leaf.shape)
    jax.tree.map(check, plan.params, state.params,
                 is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    if st_ is not None and plan.state_specs is not None:
        jax.tree.map(check, plan.state_specs, st_,
                     is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
print('OK')
"""
    out = subproc(code)
    assert "OK" in out
