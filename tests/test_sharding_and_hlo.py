"""Unit tests: logical-axis rules, size-aware specs, HLO collective
parser, roofline arithmetic."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import parse_collectives
from repro.sharding.rules import (DEFAULT_TRAIN_RULES, fsdp_rules,
                                  logical_to_spec, logical_to_spec_sized)


class FakeMesh:
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
RULES = fsdp_rules(dict(DEFAULT_TRAIN_RULES, batch=("data",)))


def test_spec_basic_mapping():
    spec = logical_to_spec(("vocab", "embed"), RULES)
    assert spec == P("model", "data")


def test_spec_no_axis_reuse():
    # both logical axes map to 'model'; second claim must drop
    spec = logical_to_spec(("q_heads", "mlp"), DEFAULT_TRAIN_RULES)
    assert spec == P("model", None)


def test_sized_spec_drops_non_divisible():
    # 60 experts don't divide model=16 -> experts drops, mlp picks it up
    spec = logical_to_spec_sized(("experts", "embed", "mlp"),
                                 (60, 2048, 1408), DEFAULT_TRAIN_RULES,
                                 MESH)
    assert spec == P(None, None, "model")
    # 64 experts do divide -> experts takes model, mlp drops
    spec = logical_to_spec_sized(("experts", "embed", "mlp"),
                                 (64, 2048, 1024), DEFAULT_TRAIN_RULES,
                                 MESH)
    assert spec == P("model", None, None)


def test_sized_spec_fsdp_embed():
    spec = logical_to_spec_sized(("embed", "mlp"), (4096, 14336),
                                 RULES, MESH)
    assert spec == P("data", "model")
    # odd embed dim -> FSDP drops rather than padding
    spec = logical_to_spec_sized(("embed", "mlp"), (4097, 14336),
                                 RULES, MESH)
    assert spec == P(None, "model")


from hypothesis import given, settings
from hypothesis import strategies as st

LOGICAL = [None, "embed", "vocab", "q_heads", "kv_heads", "mlp",
           "experts", "batch", "seq", "layers"]


@given(st.lists(st.sampled_from(LOGICAL), min_size=1, max_size=5),
       st.lists(st.integers(1, 4096), min_size=1, max_size=5))
@settings(max_examples=100, deadline=None)
def test_sized_spec_properties(names, dims):
    """Invariants: every mesh axis used at most once; every sharded dim
    is divisible by its axis size; spec length == rank."""
    n = min(len(names), len(dims))
    names, dims = names[:n], dims[:n]
    spec = logical_to_spec_sized(names, dims, RULES, MESH)
    assert len(spec) == n
    used = []
    for entry, dim in zip(spec, dims):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        for a in axes:
            assert a in MESH.axis_names
            used.append(a)
            assert dim % MESH.shape[a] == 0
    assert len(used) == len(set(used)), f"axis reused: {spec}"


HLO = """
HloModule test
fused_computation {
  ...
}
ENTRY main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ag = bf16[256,512]{1,0} all-gather(%p0), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %rs = bf16[8,128]{1,0} reduce-scatter(%y), replica_groups=[32,8]<=[256], dimensions={0}
  %cp = f32[64,64]{1,0} collective-permute(%z), source_target_pairs={{0,1},{1,2}}
  %a2a = bf16[128,32]{1,0} all-to-all(%w), replica_groups=[16,16]<=[256]
}
"""


def test_parse_collectives_formulas():
    st = parse_collectives(HLO, 256)
    assert st.op_counts == {"all-gather": 1, "all-reduce": 1,
                            "reduce-scatter": 1, "collective-permute": 1,
                            "all-to-all": 1}
    ag = 256 * 512 * 2 * 15 / 16          # out_bytes * (g-1)/g
    ar = 2 * 1024 * 4 * 3 / 4             # 2 * bytes * (g-1)/g, g=4
    rs = 8 * 128 * 2 * 7                  # out_bytes * (g-1), g=8
    cp = 64 * 64 * 4
    a2a = 128 * 32 * 2 * 15 / 16
    assert st.op_bytes["all-gather"] == pytest.approx(ag)
    assert st.op_bytes["all-reduce"] == pytest.approx(ar)
    assert st.op_bytes["reduce-scatter"] == pytest.approx(rs)
    assert st.op_bytes["collective-permute"] == pytest.approx(cp)
    assert st.op_bytes["all-to-all"] == pytest.approx(a2a)
    assert st.per_device_link_bytes == pytest.approx(
        ag + ar + rs + cp + a2a)


def test_parse_collectives_ignores_done_and_singleton_groups():
    txt = """
  %ag1 = bf16[16,4]{1,0} all-gather-start(%p), replica_groups=[256,1]<=[256]
  %agd = bf16[16,4]{1,0} all-gather-done(%ag1)
"""
    st = parse_collectives(txt, 256)
    # group size 1 => no traffic
    assert st.per_device_link_bytes == 0


def test_real_compiled_module_parse():
    """End-to-end: compile a tiny sharded matmul and find its psum."""
    import jax.numpy as jnp
    if jax.device_count() != 1:
        pytest.skip("needs the default single-device pytest process")
    # single device: no collectives expected; parser returns 0 cleanly
    co = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    st = parse_collectives(co.as_text(), 1)
    assert st.per_device_link_bytes == 0
