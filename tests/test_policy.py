"""Property tests (hypothesis) on the sharding-legality invariants: every
spec the plan engine emits must be accepted by jax.jit (divisibility, no
double-use of a mesh axis), for arbitrary shapes/axis assignments."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.policy import (DEFAULT_RULES, RegionConfig, RegionPlan,
                               default_plan, legal_spec)

AXES = [None, "batch", "seq", "embed", "ff", "heads", "kv_heads", "vocab",
        "experts", "ssm_dim"]


def make_mesh():
    # single CPU device: mesh of (1, 1) still exercises divisibility logic
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1, 1)


class FakeMesh:
    """Mesh stand-in with arbitrary axis sizes (no devices needed)."""
    def __init__(self, data, model, pod=0):
        self.shape = {"data": data, "model": model}
        if pod:
            self.shape["pod"] = pod


@given(
    shape=st.lists(st.integers(1, 512), min_size=1, max_size=4),
    axes=st.lists(st.sampled_from(AXES), min_size=4, max_size=4),
    data=st.sampled_from([2, 4, 16]),
    model=st.sampled_from([2, 4, 16]),
)
@settings(max_examples=200, deadline=None)
def test_legal_spec_always_divisible(shape, axes, data, model):
    mesh = FakeMesh(data, model)
    spec = legal_spec(shape, axes[: len(shape)], DEFAULT_RULES, mesh)
    used = set()
    for dim, entry in zip(shape, tuple(spec)):
        if entry is None:
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for n in names:
            assert n in mesh.shape
            assert n not in used, "mesh axis used twice"
            used.add(n)
            size *= mesh.shape[n]
        assert dim % size == 0, f"dim {dim} not divisible by {size}"


@given(
    shape=st.lists(st.sampled_from([1, 3, 5, 7, 20, 60]), min_size=1,
                   max_size=3),
    axes=st.lists(st.sampled_from(AXES), min_size=3, max_size=3),
)
@settings(max_examples=100, deadline=None)
def test_awkward_dims_replicate(shape, axes):
    """Dims that don't divide 16 are always replicated, never errored."""
    mesh = FakeMesh(16, 16)
    spec = legal_spec(shape, axes[: len(shape)], DEFAULT_RULES, mesh)
    for dim, entry in zip(shape, tuple(spec)):
        if dim in (1, 3, 5, 7, 20, 60) and dim % 16 != 0:
            assert entry is None or dim % 16 == 0


def test_plan_json_roundtrip():
    plan = default_plan(None, "train")
    plan.region_configs["layer/attn"] = RegionConfig(
        rules={"heads": None, "seq": "model"}, block_q=1024, remat=True)
    text = plan.to_json()
    plan2 = RegionPlan.from_json(text)
    assert plan2.config_for("layer3/attn").block_q == 1024
    assert plan2.config_for("layer3/attn").rules["seq"] == "model"
    assert plan2.config_for("layer3/attn").remat
    # canonical matching: layer/attn addresses every layer index
    assert plan2.config_for("layer11/attn").block_q == 1024
    assert plan2.config_for("layer3/mlp").block_q == 0


def test_prefix_specificity():
    plan = RegionPlan(region_configs={
        "layer": RegionConfig(remat=True),
        "layer/attn": RegionConfig(remat=False, block_q=64),
    })
    assert plan.config_for("layer5").remat
    assert not plan.config_for("layer5/attn").remat
    assert plan.config_for("layer5/attn").block_q == 64


def test_constrain_noop_without_mesh():
    plan = RegionPlan(mesh=None)
    x = jnp.ones((4, 4))
    assert plan.constrain(x, "r", ("batch", "seq")) is x
