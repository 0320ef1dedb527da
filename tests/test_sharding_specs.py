"""Sharding-spec rules: shape-divisibility invariants for every assigned arch."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED, get_config, get_reduced
from repro.models import init_params
from repro.sharding.specs import (
    batch_axes,
    leaf_param_spec,
    paged_state_specs,
    param_specs,
    pool_kv_spec,
)


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("arch", ASSIGNED)
def test_specs_divide_shapes(arch):
    """Every sharded dim must be divisible by its mesh axes — the invariant
    that makes the 40-way dry-run lower."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, shapes, MESH)

    def check(path, leaf, spec):
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= MESH.shape[a]
            assert leaf.shape[i] % size == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        check, shapes, specs,
    )


@pytest.mark.parametrize("arch", ["gemma3-1b", "recurrentgemma-2b", "arctic-480b"])
def test_head_guard_replicates_odd_head_counts(arch):
    """4, 10 and 56 heads don't divide 16: wq/wo must stay replicated."""
    cfg = get_config(arch)
    spec = leaf_param_spec(("stack", "g0", "p0", "mixer", "wq"),
                           (cfg.n_layers, cfg.d_model, cfg.n_heads * cfg.head_dim),
                           cfg, 16)
    assert spec == P(None, None, None)


def test_moe_experts_shard_over_model():
    cfg = get_config("qwen3-moe-30b-a3b")
    spec = leaf_param_spec(("stack", "g0", "p0", "ffn", "w_up"),
                           (48, 128, 2048, 768), cfg, 16)
    assert spec[1] == "model"


def test_embed_sharded_head_sharded():
    cfg = get_config("granite-8b")
    assert leaf_param_spec(("embed", "table"), (49152, 4096), cfg, 16) == P("model", None)
    assert leaf_param_spec(("head", "w"), (4096, 49152), cfg, 16) == P(None, "model")


def test_pool_kv_spec_shards_head_axis_or_replicates():
    """Paged KV pools shard their kv-head axis (dim -3 of the head-major
    (..., N, Kv, page, hd) pool) over `model` when the head count divides,
    and fall back to replication otherwise (MQA)."""
    gqa = get_config("moonshot-v1-16b-a3b")      # 16 kv heads
    assert pool_kv_spec(gqa, 5, 2) == P(None, None, "model", None, None)
    full = get_config("granite-8b")              # 8 kv heads at full size...
    red = get_reduced("granite-8b")              # ...but 1 when reduced (MQA)
    assert red.n_kv_heads == 1
    assert pool_kv_spec(red, 5, 2) == P(None, None, None, None, None)
    assert pool_kv_spec(full, 5, 3) == P(None, None, None, None, None)


def test_paged_state_specs_tables_replicated():
    """Block tables / lengths stay replicated — page ids are global, only
    the head slices of their contents are sharded."""
    import numpy as np

    cfg = get_reduced("moonshot-v1-16b-a3b")
    state_shape = {
        "caches": [{"p0": {
            "kp": jnp.zeros((2, 9, cfg.n_kv_heads, 8, cfg.head_dim)),
            "vp": jnp.zeros((2, 9, cfg.n_kv_heads, 8, cfg.head_dim)),
        }}],
        "tables": jnp.zeros((4, 8), jnp.int32),
        "lengths": jnp.zeros((4,), jnp.int32),
    }
    specs = paged_state_specs(cfg, state_shape, FakeMesh({"data": 1, "model": 2}))
    assert specs["caches"][0]["p0"]["kp"] == P(None, None, "model", None, None)
    assert specs["tables"] == P(None, None)
    assert specs["lengths"] == P(None)


def test_batch_axes_divisibility():
    assert batch_axes(MESH, 256) == ("data",)
    assert batch_axes(MESH3, 256) == ("pod", "data")
    assert batch_axes(MESH3, 1) == ()          # long_500k: batch unshardable
    assert batch_axes(MESH3, 2) == ("pod",)
