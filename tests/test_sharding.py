"""Sharding-rule tests using AbstractMesh (no devices needed)."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.models import zoo
from repro.models.params import (DEFAULT_RULES, Spec, partition_spec,
                                 tree_pspecs)


MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_divisible_dims_shard():
    s = Spec((4096, 14336), ("embed", "mlp"))
    assert partition_spec(s, MESH) == P(None, "model")


def test_indivisible_dims_replicate():
    # whisper: 12 heads on a 16-way model axis -> replicated
    s = Spec((768, 12, 64), ("embed", "heads", None))
    assert partition_spec(s, MESH) == P()


def test_each_mesh_axis_used_once():
    s = Spec((8, 4096, 32768), ("experts", "embed", "mlp"))
    rules = dict(DEFAULT_RULES)
    rules["experts"] = ("model",)
    # experts=8 not divisible by 16 -> falls through to mlp
    assert partition_spec(s, MESH, rules) == P(None, None, "model")
    rules2 = dict(DEFAULT_RULES)
    rules2["experts"] = ("model",)
    s2 = Spec((160, 5120, 1536), ("experts", "embed", "mlp"))
    # 160 % 16 == 0: experts take 'model'; mlp cannot reuse it
    assert partition_spec(s2, MESH, rules2) == P("model")


def test_batch_composite_axis():
    s = Spec((256, 32768, 8, 128), ("batch", None, "kv_heads", None))
    assert partition_spec(s, MESH3) == P(("pod", "data"))
    s1 = Spec((1, 524288, 8, 128), ("batch", None, "kv_heads", None))
    # batch=1: no data sharding possible
    assert partition_spec(s1, MESH3) == P()


def test_vocab_padding_is_shardable():
    for arch in ASSIGNED:
        cfg = get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size


def test_all_param_specs_have_matching_axes():
    """Every Spec's axes tuple must match its rank (catches drift)."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        specs = zoo.get_model(cfg).specs(cfg)
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, Spec))
        for s in leaves:
            assert len(s.shape) == len(s.axes), (arch, s)


def test_full_model_pspecs_build():
    """tree_pspecs over every full-size arch must not raise."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        specs = zoo.get_model(cfg).specs(cfg)
        ps = tree_pspecs(specs, MESH3)
        assert jax.tree_util.tree_leaves(ps, is_leaf=lambda x: isinstance(
            x, P)) or True
