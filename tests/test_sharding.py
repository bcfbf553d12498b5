"""Sharding-rule resolution logic (pure; no multi-device requirement)."""
import jax
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.distributed import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    # single-device 1x1 mesh: resolve_spec only reads axis NAMES and SIZES,
    # so divisibility is exercised with a fake-shape wrapper below.
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


class FakeMesh:
    """Duck-typed mesh exposing .shape only (resolve_spec needs nothing else)."""

    def __init__(self, **shape):
        self.shape = shape


def test_resolve_basic():
    m = FakeMesh(data=16, model=16)
    rules = shd.Rules({"embed": "data", "heads": "model"})
    spec = shd.resolve_spec(("embed", "heads"), (4096, 8192), rules, m)
    assert spec == P("data", "model")


def test_resolve_divisibility_drops():
    m = FakeMesh(data=16, model=16)
    rules = shd.Rules({"kv": "model"})
    # 2 KV heads cannot shard 16 ways -> replicated
    assert shd.resolve_spec(("kv",), (2,), rules, m) == P()
    assert shd.resolve_spec(("kv",), (32,), rules, m) == P("model")


def test_resolve_tuple_axes_shorten():
    m = FakeMesh(pod=2, data=16, model=16)
    rules = shd.Rules({"batch": ("pod", "data")})
    # 32 divides pod*data -> both; 16 only divides pod... (2) -> shortened
    assert shd.resolve_spec(("batch",), (32,), rules, m) == P(("pod", "data"))
    assert shd.resolve_spec(("batch",), (16,), rules, m) == P("pod")
    assert shd.resolve_spec(("batch",), (3,), rules, m) == P()


def test_resolve_tuple_prefix_rechecked_against_used():
    """Regression: a (pod, data) batch rule colliding with an embed rule.

    The batch tuple is shortened from the right; whatever prefix survives
    must be re-checked against the axes other dims already claimed — in
    either dim order the resolved spec may never duplicate a mesh axis."""
    m = FakeMesh(pod=2, data=4, model=4)
    rules = shd.Rules({"batch": ("pod", "data"), "embed": "data"})
    # batch first: 2 % (pod*data)=8 fails -> prefix ("pod",); embed takes data
    assert shd.resolve_spec(("batch", "embed"), (2, 8), rules, m) == \
        P("pod", "data")
    # embed first claims data; the batch tuple must drop it and keep pod only
    assert shd.resolve_spec(("embed", "batch"), (8, 2), rules, m) == \
        P("data", "pod")
    # embed first, batch dim divisible by pod*data — data is claimed, so the
    # re-check must strip it from the surviving candidate, NOT emit it twice
    assert shd.resolve_spec(("embed", "batch"), (8, 8), rules, m) == \
        P("data", "pod")


def test_resolve_duplicate_axis_inside_rule_tuple():
    """A rule tuple that names one mesh axis twice dedups instead of emitting
    an illegal duplicate-axis PartitionSpec."""
    m = FakeMesh(data=4, model=4)
    rules = shd.Rules({"batch": ("data", "data")})
    # 16 % (4*4) == 0, so without within-tuple dedup the unshortened
    # candidate ("data", "data") survives verbatim -> illegal spec
    assert shd.resolve_spec(("batch",), (16,), rules, m) == P("data")


def test_resolve_no_duplicate_mesh_axes():
    m = FakeMesh(data=4, model=4)
    rules = shd.Rules({"a": "model", "b": "model"})
    spec = shd.resolve_spec(("a", "b"), (8, 8), rules, m)
    assert spec == P("model")       # second claim dropped, trailing None trimmed


def test_resolve_skips_missing_axes():
    m = FakeMesh(data=4)            # no "model" on this mesh
    rules = shd.Rules({"heads": "model", "embed": "data"})
    assert shd.resolve_spec(("heads", "embed"), (8, 8), rules, m) == \
        P(None, "data")


def test_train_rules_profile(mesh):
    rules = shd.train_rules(mesh)
    assert rules.lookup("vocab") == ("model",)
    assert rules.lookup("embed") == ("data",)
    assert rules.lookup(None) == ()


def test_param_shardings_cover_every_tensor(mesh):
    from repro.configs import registry
    cfg = registry.reduced(registry.get("glm4-9b"))
    rules = shd.train_rules(mesh)
    shards = shd.param_shardings(cfg, mesh, rules)
    from repro.models import api
    assert set(shards) == set(api.build(cfg).schema(cfg))


def test_cell_builders_construct_for_host_mesh(mesh):
    """build_cell on the 1x1 host mesh: structure + shardings line up (the
    production-mesh versions are exercised by the dry-run)."""
    from repro.configs import registry
    from repro.configs.base import SHAPES
    from repro.launch import specs
    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    shape = SHAPES["train_4k"]
    try:
        cell = specs.build_train_cell(cfg, shape, mesh, microbatches=1)
        assert set(cell.args[0]) == set(cell.in_shardings[0])
    finally:
        specs.clear_contexts()


def test_qt_leaf_shardings_consistent():
    """QT triples resolve q along the output-channel axis and scale/zero
    FOLLOW it (same mesh axes where sizes line up, replicated on size-1
    broadcast dims)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.models.layers import QT, QT4
    m = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)

    class FM:        # fake 4x4 shape, real mesh for NamedSharding construction
        shape = {"data": 4, "model": 4}
    rules = shd.Rules({"vocab": "model", "embed": "data"})
    qt = QT(jnp.zeros((64, 32), jnp.uint8), jnp.zeros((64, 1), jnp.float32),
            jnp.zeros((64, 1), jnp.float32))
    spec = shd.resolve_spec(("vocab", "embed"), (64, 32), rules, FM)
    assert spec == P("model", "data")
    sspec = shd.follower_spec(spec, (64, 32), (64, 1), FM)
    assert sspec == P("model")          # channel rows follow q, bcast dim trimmed
    sh = shd.leaf_shardings(("vocab", "embed"), qt, rules, m)
    assert isinstance(sh, QT)
    assert all(isinstance(s, NamedSharding) for s in sh)
    assert sh.q.spec == shd.resolve_spec(("vocab", "embed"), (64, 32), rules, m)
    # packed QT4: last-dim divisibility is checked at the PACKED size
    qt4 = QT4(jnp.zeros((64, 16), jnp.uint8), jnp.zeros((64, 1), jnp.float32),
              jnp.zeros((64, 1), jnp.float32))
    sh4 = shd.leaf_shardings(("vocab", "embed"), qt4, rules, m)
    assert isinstance(sh4, QT4)


def test_qt_follower_per_group_divisibility():
    """Per-group scale (C, G, 1): group dim keeps q's axes only when every
    shard owns whole groups; otherwise that dim replicates."""

    class FM:
        shape = {"data": 4, "model": 4}
    qspec = P("model", "data")
    # q (64, 32) sharded 4-ways on dim1; 8 groups % 4 == 0 -> follow
    assert shd.follower_spec(qspec, (64, 32), (64, 8), FM) == P("model", "data")
    # 6 groups % 4 != 0 -> group dim replicates, channel dim still follows
    assert shd.follower_spec(qspec, (64, 32), (64, 6), FM) == P("model")


def test_quantized_param_structs_match_schema():
    from repro.configs import registry
    from repro.launch import specs
    m = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
    cfg = registry.get("qwen3-1.7b")
    rules = shd.serve_rules(m)
    for fmt in ("bf16", "int8", "int4"):
        structs, shards = specs.param_structs(cfg, m, rules, fmt)
        assert set(structs) == set(shards)
        if fmt == "int4":
            from repro.models.layers import QT4
            big = [v for v in structs.values() if isinstance(v, QT4)]
            assert big, "int4 format must quantize the big matrices"
            for qt in big:
                assert qt.q.dtype.name == "uint8"
