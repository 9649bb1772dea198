"""STLD core: sampling statistics, gating semantics, schedules, gather mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.core import stld
from repro.core.schedules import drop_rates, unit_shape
from repro.models import init_params, model_apply


def test_expected_active_layers():
    rates = jnp.array([0.0, 0.5, 1.0, 0.25])
    assert float(stld.expected_active_layers(rates)) == pytest.approx(2.25)


def test_sample_drops_statistics(key):
    rates = jnp.array([0.1, 0.5, 0.9] * 4)
    keys = jax.random.split(key, 2000)
    drops = jax.vmap(lambda k: stld.sample_drops(k, rates, 1))(keys)
    freq = np.asarray(jnp.mean(drops.astype(jnp.float32), axis=0))
    np.testing.assert_allclose(freq, np.asarray(rates), atol=0.05)


def test_sample_drops_min_active(key):
    rates = jnp.full((6,), 0.95)
    keys = jax.random.split(key, 500)
    drops = jax.vmap(lambda k: stld.sample_drops(k, rates, 2))(keys)
    active = np.asarray(jnp.sum(~drops, axis=1))
    assert active.min() >= 2


def test_sample_active_indices_sorted_unique(key):
    rates = unit_shape("incremental", 12) * 0.5
    idx = stld.sample_active_indices(key, jnp.clip(rates, 0, 0.95), 5)
    idx = np.asarray(idx)
    assert len(np.unique(idx)) == 5
    assert (np.sort(idx) == idx).all()


@given(mean=st.floats(0.05, 0.9), L=st.integers(2, 64))
@settings(max_examples=30, deadline=None)
def test_drop_rates_mean_property(mean, L):
    for dist in ("uniform", "incremental", "decay"):
        r = np.asarray(drop_rates(dist, mean, L))
        assert (r >= 0).all() and (r <= 0.95).all()
        # mean preserved when no clipping occurred
        if r.max() < 0.95 - 1e-6:
            assert abs(r.mean() - mean) < 1e-4


def test_incremental_monotone_decay_antitone():
    inc = np.asarray(drop_rates("incremental", 0.4, 10))
    dec = np.asarray(drop_rates("decay", 0.4, 10))
    assert (np.diff(inc) >= -1e-7).all()
    assert (np.diff(dec) <= 1e-7).all()


def test_static_active_count():
    assert stld.static_active_count(0.5, 24, bucket=4) == 12
    assert stld.static_active_count(0.9, 24, bucket=4) == 4
    assert stld.static_active_count(0.99, 24, bucket=1, min_active=2) == 2
    assert stld.static_active_count(0.0, 24) == 24


def test_gate_skip_is_identity(key):
    h = jax.random.normal(key, (2, 3, 8))
    cache = {"x": jnp.ones((2, 2))}
    block = lambda hh, cc: (hh * 2.0, jnp.ones(()), jax.tree.map(lambda t: t + 1, cc))
    h1, aux1, c1 = stld.gate(block, jnp.array(True), h, cache)
    np.testing.assert_allclose(h1, h)
    assert float(aux1) == 0.0
    np.testing.assert_allclose(c1["x"], cache["x"])
    h2, aux2, c2 = stld.gate(block, jnp.array(False), h, cache)
    np.testing.assert_allclose(h2, h * 2.0)
    assert float(aux2) == 1.0


def test_all_dropped_reduces_to_head_only(key):
    cfg = get_config("yi-6b", smoke=True).replace(num_layers=3, dtype="float32")
    params = init_params(key, cfg)
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size)}
    drops = jnp.ones((3,), dtype=bool)
    logits, _, _ = model_apply(params, cfg, batch, drops=drops)
    # equals embed -> final_norm -> head with no layers
    cfg0 = cfg.replace(num_layers=0)
    params0 = dict(params, layers=[])
    logits0, _, _ = model_apply(params0, cfg0, batch)
    np.testing.assert_allclose(logits, logits0, atol=1e-5)


def test_gather_equals_cond_for_same_active_set(key):
    cfg = get_config("glm4-9b", smoke=True).replace(num_layers=4, dtype="float32")
    params = init_params(key, cfg)
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size)}
    active = jnp.array([0, 2])
    drops = jnp.array([False, True, False, True])
    lg, _, _ = model_apply(params, cfg, batch, active_idx=active)
    lc, _, _ = model_apply(params, cfg, batch, drops=drops)
    np.testing.assert_allclose(lg, lc, atol=1e-5)


def test_gather_grads_zero_for_dropped_layers(key):
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib

    cfg = get_config("yi-6b", smoke=True).replace(num_layers=4, dtype="float32")
    params = init_params(key, cfg)
    peft = peft_lib.init_peft(key, cfg, PEFTConfig(method="lora", lora_rank=2))
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size)}
    active = jnp.array([1, 3])

    def loss(pf):
        lo, _, _ = model_apply(params, cfg, batch, peft=pf, active_idx=active)
        return jnp.mean(lo**2)

    from repro.models import stacking

    g = jax.grad(loss)(peft)
    for l in (0, 2):  # dropped layers get exactly zero grads
        g_l = jax.tree.leaves(stacking.layer_view(g, l))
        assert all(float(jnp.abs(x).max()) == 0.0 for x in g_l)
    for l in (1, 3):
        g_l = jax.tree.leaves(stacking.layer_view(g, l))
        assert any(float(jnp.abs(x).max()) > 0.0 for x in g_l)


def test_gated_remat_scan_saves_no_copy_of_the_weights(key):
    """The gradient through a gated, rematerialized layer scan saves each
    layer's input, not its weights: the checkpoint wraps the ``cond`` with
    the block.  A ``cond`` around a checkpointed block would save the
    checkpoint's inputs, each layer's float32 weights among them, and the
    scan would stack them into a copy of the whole stack."""
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib
    from repro.models.transformer import stack_apply

    cfg = get_config("qwen3-1.7b", smoke=True).replace(
        num_layers=8, d_model=256, d_ff=1024, num_heads=4, num_kv_heads=2, head_dim=64,
        dtype="bfloat16",
    )
    layers = init_params(key, cfg)["layers"]
    peft = peft_lib.init_peft(key, cfg, PEFTConfig(method="lora", lora_rank=4))
    h = jax.random.normal(key, (2, 16, cfg.d_model), jnp.bfloat16)
    drops = jnp.arange(cfg.num_layers) % 2 == 1

    def loss(peft):
        out, _, _ = stack_apply(
            layers, cfg, h, positions=jnp.arange(16), drops=drops, peft=peft,
            lora_scale=2.0, remat=True,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    temp = jax.jit(jax.grad(loss)).lower(peft).compile().memory_analysis().temp_size_in_bytes
    weights = sum(x.nbytes for x in jax.tree.leaves(layers))
    assert temp < weights, (temp, weights)


@pytest.mark.parametrize(
    "arch", ["qwen3-1.7b", "jamba-v0.1-52b"], ids=["period1", "period2-jamba"]
)
def test_remat_gate_matches_the_plain_gate(key, arch):
    """Rematerialized as one unit, the gated stack gives the plain gated
    stack's loss and adapter gradients; dropped layers get none.  Jamba's
    period of 2 runs each gate inside a group of the layer loop."""
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib
    from repro.models import stacking

    cfg = get_config(arch, smoke=True).replace(num_layers=4, dtype="float32")
    params = init_params(key, cfg)
    peft = peft_lib.init_peft(key, cfg, PEFTConfig(method="lora", lora_rank=2))
    peft = jax.tree.map(lambda x: x + 0.01, peft)  # every factor off zero
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size)}
    drops = jnp.array([False, True, False, True])

    def loss(pf, remat):
        lo, _, _ = model_apply(params, cfg, batch, peft=pf, drops=drops, remat=remat)
        return jnp.mean(lo**2)

    (l0, g0), (l1, g1) = (jax.value_and_grad(loss)(peft, r) for r in (False, True))
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    for l in (1, 3):
        g_l = jax.tree.leaves(stacking.layer_view(g1, l))
        assert g_l and all(float(jnp.abs(x).max()) == 0.0 for x in g_l)
    with pytest.raises(ValueError, match="frozen"):
        jax.grad(lambda p: model_apply(
            p, cfg, batch, drops=drops, remat=True)[0].sum())(params)


def _weight_products(jaxpr) -> int:
    """``dot_general``s without batch dimensions (the weight products) in
    ``jaxpr`` and every jaxpr inside it."""
    from repro.analysis.jaxpr_contracts import walk_eqns

    return sum(
        1
        for eqn in walk_eqns(jaxpr)
        if eqn.primitive.name == "dot_general"
        and not any(eqn.params["dimension_numbers"][1])
    )


def test_gated_remat_backward_runs_no_forward_weight_product(key):
    """The gated, rematerialized stack's gradient has as many weight
    products as the plain gated stack's, forward plus backward: the
    backward replays the kept block's VJP from its saved products and
    recomputes none of them.  (The attention core's batched einsums may be
    recomputed and are not counted.)"""
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib

    cfg = get_config("qwen3-1.7b", smoke=True).replace(num_layers=4, dtype="float32")
    params = init_params(key, cfg)
    peft = peft_lib.init_peft(key, cfg, PEFTConfig(method="lora", lora_rank=2))
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size)}
    drops = jnp.array([False, True, False, True])

    def products(remat):
        loss = lambda pf: jnp.mean(
            model_apply(params, cfg, batch, peft=pf, drops=drops, remat=remat)[0] ** 2
        )
        return _weight_products(jax.make_jaxpr(jax.grad(loss))(peft))

    # per kept layer: q, k, v, o, gate, up, down and two LoRA factors on
    # each of q and v forward (11); the input products of the seven and
    # four per LoRA projection backward (15); the head forward and back
    assert products(True) == products(False) == 28
