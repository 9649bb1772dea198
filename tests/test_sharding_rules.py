"""Sharding rule engine: divisibility fallbacks across the 10 archs.

These tests exercise spec_for_param / cache_specs directly (no devices
needed).
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import pytest

from repro.configs import ARCH_IDS, PEFTConfig, get_config
from repro.core import peft as peft_lib
from repro.launch.input_specs import eval_param_shapes
from repro.sharding import specs as S

TP = 16


def _specs_for(arch):
    cfg = get_config(arch)
    shapes = eval_param_shapes(cfg)
    return shapes, S.param_specs(shapes, TP)


def _find(tree, *needles):
    found = []

    def visit(path, leaf):
        parts = S._path_parts(path)
        if all(any(n == p for p in parts) for n in needles):
            found.append((parts, leaf))
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return found


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_all_specs_divisible(arch):
    """Every emitted spec must satisfy GSPMD's divisibility requirement."""
    shapes, specs = _specs_for(arch)

    def check(leaf, spec):
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            assert leaf.shape[dim] % TP == 0, (leaf.shape, spec)

    jax.tree.map(check, shapes, specs, is_leaf=lambda x: isinstance(x, P))


def _drop_layer_lead(parts, spec):
    """Strip the leading stacked-layer None so per-layer rule assertions are
    layout-independent (stacked-native leaves carry a leading (L, ...) axis
    that always replicates)."""
    if S._stacked_layer_lead(parts):
        assert len(spec) == 0 or spec[0] is None
        return P(*tuple(spec)[1:])
    return spec


def test_yi_attention_megatron_pattern():
    shapes, specs = _specs_for("yi-6b")
    wq_parts, wq = _find(specs, "wq", "w")[0]
    wo_parts, wo = _find(specs, "wo", "w")[0]
    assert _drop_layer_lead(wq_parts, wq) == P(None, "model")   # column parallel
    assert _drop_layer_lead(wo_parts, wo) == P("model", None)   # row parallel


def test_llama4_expert_parallel():
    shapes, specs = _specs_for("llama4-scout-17b-a16e")
    parts, eg = _find(specs, "experts", "gate")[0]
    # 16 experts over 16-way axis
    assert _drop_layer_lead(parts, eg) == P("model", None, None)


def test_granite_expert_fallback():
    """40 experts don't divide 16 -> shard within-expert d_ff instead."""
    shapes, specs = _specs_for("granite-moe-3b-a800m")
    parts, eg = _find(specs, "experts", "gate")[0]
    assert _drop_layer_lead(parts, eg) == P(None, None, "model")
    # granite vocab 49155 is not divisible by 16 -> embed shards d_model
    emb = _find(specs, "embed")[0][1]
    assert emb == P(None, "model")


def test_whisper_small_head_fallback():
    """6-head attention cannot TP 16-way on heads, but h*hd=384 divides."""
    shapes, specs = _specs_for("whisper-tiny")
    wq = [(p, x) for p, x in _find(specs, "wq", "w")]
    assert all(_drop_layer_lead(p, s) == P(None, "model") for p, s in wq)


def test_peft_replicated():
    cfg = get_config("yi-6b")
    tree = jax.eval_shape(
        lambda k: peft_lib.init_peft(k, cfg, PEFTConfig(method="lora")),
        jax.random.PRNGKey(0),
    )
    specs = S.peft_specs(tree)
    assert all(s == P() for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))


def test_cache_specs_decode_vs_longcontext():
    cfg = get_config("yi-6b")
    S.set_mesh_axis_sizes(type("M", (), {"shape": {"data": 16, "model": 16}})())
    from repro.models.transformer import init_caches

    caches = jax.eval_shape(lambda: init_caches(cfg, 128, 1024))
    sp = S.cache_specs(caches, ("data",), TP)
    k_spec = sp[0]["k"]
    assert k_spec[0] == "data"          # batch sharded
    assert k_spec[3] == "model"         # kv=4 < 16 -> head_dim sharded

    caches1 = jax.eval_shape(lambda: init_caches(cfg, 1, 4096))
    sp1 = S.cache_specs(caches1, ("data",), TP, shard_seq_on_data=True)
    assert sp1[0]["k"][1] == "data"     # sequence sharded for B=1


def test_rwkv_state_sharding():
    cfg = get_config("rwkv6-3b")
    S.set_mesh_axis_sizes(type("M", (), {"shape": {"data": 16, "model": 16}})())
    from repro.models.transformer import init_caches

    caches = jax.eval_shape(lambda: init_caches(cfg, 128, 16))
    sp = S.cache_specs(caches, ("data",), TP)
    assert sp[0]["shift_tm"][0] == "data"
