"""Equivalence tests for the beyond-paper performance variants
(EXPERIMENTS.md §Perf): every optimized path must match its baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PEFTConfig, TrainConfig, get_config
from repro.models import init_params
from repro.nn.moe import init_moe, moe_apply


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "llama4-scout-17b-a16e"])
def test_moe_gather_dispatch_equals_einsum(arch, key):
    cfg = get_config(arch, smoke=True).replace(dtype="float32")
    p = init_moe(key, cfg)
    x = jax.random.normal(key, (2, 24, cfg.d_model), dtype=jnp.float32)
    oe, ae = moe_apply(p, cfg, x, dispatch_mode="einsum")
    og, ag = moe_apply(p, cfg, x, dispatch_mode="gather")
    np.testing.assert_allclose(oe, og, atol=1e-5)
    np.testing.assert_allclose(ae, ag, atol=1e-6)


def test_moe_weight_gather_equals_full_capacity(key):
    cfg = get_config("llama4-scout-17b-a16e", smoke=True).replace(
        dtype="float32", capacity_factor=8.0
    )
    p = init_moe(key, cfg)
    x = jax.random.normal(key, (2, 2, cfg.d_model))  # t=4 -> weight-gather path
    og, _ = moe_apply(p, cfg, x)
    oe, _ = moe_apply(p, cfg, x, dispatch_mode="einsum_forced")
    np.testing.assert_allclose(og, oe, atol=1e-5)


def test_moe_weight_gather_grads_flow(key):
    cfg = get_config("granite-moe-3b-a800m", smoke=True).replace(dtype="float32")
    p = init_moe(key, cfg)
    x = jax.random.normal(key, (1, 4, cfg.d_model))

    def loss(p):
        out, _ = moe_apply(p, cfg, x)
        return jnp.mean(out**2)

    g = jax.grad(loss)(p)
    assert any(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(g))


def test_train_step_gather_mode(key):
    from repro.core import peft as peft_lib
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init

    cfg = get_config("yi-6b", smoke=True).replace(num_layers=4, dtype="float32")
    pcfg = PEFTConfig(method="lora", lora_rank=2)
    params = init_params(key, cfg)
    peft = peft_lib.init_peft(key, cfg, pcfg)
    step = make_train_step(cfg, pcfg, TrainConfig(), stld_mode="gather", mean_rate=0.5)
    batch = {"tokens": jax.random.randint(key, (2, 9), 0, cfg.vocab_size)}
    new_peft, _, metrics = jax.jit(step)(params, peft, adamw_init(peft), batch, key)
    assert bool(jnp.isfinite(metrics["loss"]))


def test_fsdp_specs_divisible():
    from repro.launch.input_specs import eval_param_shapes
    from repro.sharding import specs as S
    from jax.sharding import PartitionSpec as P

    S.set_mesh_axis_sizes(type("M", (), {"shape": {"data": 16, "model": 16}})())
    cfg = get_config("internvl2-76b")
    shapes = eval_param_shapes(cfg)
    specs = S.param_specs(shapes, 16, fsdp_axes=("data",))

    fsdp_bytes = 0
    big_bytes = 0

    def check(leaf, spec):
        nonlocal fsdp_bytes, big_bytes
        has_fsdp = False
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            names = axes if isinstance(axes, tuple) else (axes,)
            size = 1
            for nm in names:
                size *= 16
            assert leaf.shape[dim] % size == 0
            if "data" in names:
                has_fsdp = True
        size = int(np.prod(leaf.shape))
        if size >= 1 << 20:  # the param_specs big-leaf threshold (elements)
            nbytes = size * leaf.dtype.itemsize
            big_bytes += nbytes
            if has_fsdp:
                fsdp_bytes += nbytes

    jax.tree.map(check, shapes, specs, is_leaf=lambda x: isinstance(x, P))
    # most big-weight bytes got an fsdp dim (layout-independent: the stacked
    # layer layout has 16x fewer but 16x larger leaves than the list layout)
    assert big_bytes > 0 and fsdp_bytes / big_bytes > 0.8, (fsdp_bytes, big_bytes)

    # the stacked layer axis must never be sharded: lax.scan iterates it, so
    # a data-axis sharding there would reshard the operand every layer
    def check_layer_axis(path, spec):
        parts = S._path_parts(path)
        if S._stacked_layer_lead(parts) and len(spec):
            assert spec[0] is None, (parts, spec)
        return spec

    jax.tree_util.tree_map_with_path(
        check_layer_axis, specs, is_leaf=lambda x: isinstance(x, P)
    )
