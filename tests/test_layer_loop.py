"""The layer loop (``transformer.stack_apply``: one ``lax.scan`` over groups
of ``cfg.layer_period`` layers) against a plain per-layer Python loop, in
every smoke family: forward under STLD gates, gather-STLD, and a decode step
with caches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, PEFTConfig, get_config
from repro.core import peft as peft_lib
from repro.models import encdec, init_params, model_apply, stacking
from repro.models.layers import layer_apply
from repro.models.transformer import _norm_apply, init_caches, stack_apply


def per_layer_loop(layers, cfg, h, *, positions, keep, caches=None, enc_kvs=None,
                   peft=None, lora_scale=1.0):
    """The stack as a plain Python loop: ``layer_apply`` on each kept
    layer's view, in layer order; a layer not kept passes ``h`` and its
    cache through and adds no aux.  Returns (h, aux_sum, new_caches) in the
    layout the caches came in."""
    view = lambda tree, l: None if tree is None else stacking.layer_view(tree, l)
    aux_sum = jnp.zeros((), jnp.float32)
    new_caches = []
    for l in range(stacking.stack_size(layers)):
        cache_l = view(caches, l)
        if l in keep:
            h, aux, cache_l = layer_apply(
                view(layers, l), cfg, h, positions=positions, cache=cache_l,
                enc_kv=view(enc_kvs, l), peft=view(peft, l), lora_scale=lora_scale,
            )
            aux_sum = aux_sum + aux
        new_caches.append(cache_l)
    if caches is None:
        return h, aux_sum, None
    if stacking.is_stacked(caches):
        new_caches = stacking.stack_params(new_caches)
    return h, aux_sum, new_caches


def _close(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)
    assert jax.tree.structure(got[2]) == jax.tree.structure(want[2])
    for a, b in zip(jax.tree.leaves(got[2]), jax.tree.leaves(want[2])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_stack_matches_the_per_layer_loop(arch, key):
    """A forward with every other layer dropped, then one decode step with
    caches (stacked for a homogeneous stack, a list for a hybrid one), give
    the per-layer loop's hidden states, summed aux and new caches."""
    cfg = get_config(arch, smoke=True).replace(dtype="float32")
    k_p, k_h, k_d, k_e = jax.random.split(key, 4)
    params = init_params(k_p, cfg)
    layers = params["decoder"]["layers"] if cfg.is_encoder_decoder else params["layers"]
    peft = peft_lib.init_peft(k_p, cfg, PEFTConfig(method="lora", lora_rank=2))
    peft = jax.tree.map(lambda x: x + 0.01, peft)  # every factor off zero
    b, s, n = 2, 8, cfg.num_layers
    kw = {"peft": peft, "lora_scale": 2.0}
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(k_e, (b, cfg.frontend_seq, cfg.d_model))
        kw["enc_kvs"] = encdec.encoder_cross_kvs(params, cfg, frames)
    drops = jnp.arange(n) % 2 == 0
    keep = [l for l in range(n) if not drops[l]]

    h = jax.random.normal(k_h, (b, s, cfg.d_model))
    pos = jnp.arange(s)
    _close(stack_apply(layers, cfg, h, positions=pos, drops=drops, **kw),
           per_layer_loop(layers, cfg, h, positions=pos, keep=keep, **kw))

    layout = "stacked" if stacking.is_stacked(layers) else "list"
    caches = init_caches(cfg, b, 16, dtype=jnp.float32, layout=layout)
    _, _, caches = per_layer_loop(
        layers, cfg, h, positions=pos, keep=range(n), caches=caches, **kw
    )
    h1 = jax.random.normal(k_d, (b, 1, cfg.d_model))
    pos1 = jnp.array([s])
    _close(stack_apply(layers, cfg, h1, positions=pos1, drops=drops, caches=caches, **kw),
           per_layer_loop(layers, cfg, h1, positions=pos1, keep=keep, caches=caches, **kw))


def test_gather_matches_the_per_layer_loop(key):
    """Gather-STLD over indices (0, 3) of four layers runs exactly those
    layers: the per-layer loop over the same indices."""
    cfg = get_config("qwen3-1.7b", smoke=True).replace(num_layers=4, dtype="float32")
    params = init_params(key, cfg)
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    idx = jnp.array([0, 3])
    logits, _, _ = model_apply(params, cfg, {"tokens": tokens}, active_idx=idx)
    h = params["embed"][tokens]
    h, _, _ = per_layer_loop(params["layers"], cfg, h, positions=jnp.arange(8), keep=(0, 3))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    want = _norm_apply(cfg, params["final_norm"], h) @ head
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-5)


def test_period_must_divide_the_stack(key):
    """A stack that is not whole groups of ``layer_period`` is refused, and
    gather-STLD is refused above period 1."""
    cfg = get_config("jamba-v0.1-52b", smoke=True).replace(dtype="float32")
    layers = init_params(key, cfg)["layers"]
    h = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="layer_period"):
        stack_apply(layers[:1], cfg, h, positions=jnp.arange(4))
    with pytest.raises(ValueError, match="layer_period 1"):
        stack_apply(layers, cfg, h, positions=jnp.arange(4), active_idx=jnp.array([1]))
