"""Stacked-native parameter layout: converters, traced-program guarantees,
checkpoint back-compat, and donation safety.

The acceptance contract of the stacked-layout refactor:

* every registered config family round-trips ``stack_params`` /
  ``unstack_params`` exactly (or is honestly heterogeneous and stays a
  list),
* no ``jnp.stack``/concatenate of base-layer params appears inside any
  traced training program on the smoke config (the list layout provably
  does contain one — the test would catch a regression in either
  direction),
* the client call signature shrinks from O(L·k) to O(k) leaves,
* a pre-refactor list-layout ``save_state`` checkpoint loads into the
  stacked runner and resumes bit-identically,
* donated round buffers are never reused by the engine.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.analysis import jaxpr_contracts
from repro.configs import (
    ARCH_IDS,
    FederatedConfig,
    PEFTConfig,
    STLDConfig,
    TrainConfig,
    get_config,
)
from repro.core import peft as peft_lib
from repro.data import make_task
from repro.federated.client import make_client_fns
from repro.models import stacking
from repro.models.registry import init_params
from repro.optim import adamw_init

_CFG = get_config("qwen3-1.7b", smoke=True).replace(
    num_layers=4, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
    vocab_size=128, dtype="float32",
)
_FED = FederatedConfig(num_devices=5, devices_per_round=3, local_steps=2, batch_size=8)
_TRAIN = TrainConfig(learning_rate=5e-3, total_steps=100, warmup_steps=2)
_TASK = make_task(num_examples=256, vocab_size=128, seed=0)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- round-trip
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_layout_round_trip_all_families(arch, key):
    """For every registered config family: the stacked and list layouts hold
    identical values, and stack/unstack round-trips exactly."""
    cfg = get_config(arch, smoke=True)
    auto = init_params(key, cfg)
    listed = init_params(key, cfg, layout="list")

    def layer_trees(params):
        if cfg.is_encoder_decoder:
            return {
                "enc": params["encoder"]["layers"],
                "dec": params["decoder"]["layers"],
            }
        return {"lm": params["layers"]}

    for name, (a, l) in (
        (k, (layer_trees(auto)[k], layer_trees(listed)[k]))
        for k in layer_trees(auto)
    ):
        if stacking.is_stacked(a):
            _tree_equal(stacking.unstack_params(a), l)
            _tree_equal(stacking.stack_params(l), a)
            _tree_equal(
                stacking.stack_params(stacking.unstack_params(a)), a
            )
        else:
            # honestly heterogeneous: auto must equal the list layout and
            # refuse to stack
            _tree_equal(a, l)
            assert not stacking.is_stackable(l)
            with pytest.raises(ValueError):
                stacking.stack_params(l)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "h2o-danube-1.8b"])
def test_homogeneous_stack_is_drawn_stacked(arch, key, monkeypatch):
    """A homogeneous stack is drawn stacked, never built layer by layer and
    then stacked (which holds both copies at once), and its values are the
    per-layer draw's to the bit."""
    cfg = get_config(arch, smoke=True)
    listed = init_params(key, cfg, layout="list")["layers"]

    def refuse(layers):
        raise AssertionError("the init stacked a per-layer list")

    monkeypatch.setattr(stacking, "stack_params", refuse)
    drawn = init_params(key, cfg)["layers"]
    assert jax.tree.structure(drawn) == jax.tree.structure(listed[0])
    _tree_equal(drawn, jax.tree.map(lambda *xs: np.stack(xs), *listed))


@pytest.mark.parametrize("method", ["lora", "adapter", "bitfit"])
def test_peft_layout_round_trip(method, key):
    pcfg = PEFTConfig(method=method, lora_rank=2, adapter_dim=4)
    stacked = peft_lib.init_peft(key, _CFG, pcfg)
    listed = peft_lib.init_peft(key, _CFG, pcfg, layout="list")
    assert stacking.is_stacked(stacked)
    _tree_equal(stacking.unstack_params(stacked), listed)
    _tree_equal(stacking.stack_params(listed), stacked)


# --------------------------------------------------- traced-program contract
def _client_setup(layout, stld_mode="cond"):
    pcfg = PEFTConfig(method="lora", lora_rank=2)
    scfg = STLDConfig(mode=stld_mode, mean_rate=0.5, gather_bucket=1)
    fns = make_client_fns(_CFG, pcfg, scfg, _TRAIN, donate=False)
    key = jax.random.PRNGKey(0)
    base = init_params(key, _CFG, layout=layout)
    peft = peft_lib.init_peft(key, _CFG, pcfg, layout=layout)
    batches = {
        "tokens": jnp.zeros((2, 4, 8), dtype=jnp.int32),
        "targets": jnp.zeros((2, 4, 8), dtype=jnp.int32),
        "mask": jnp.ones((2, 4, 8), dtype=jnp.float32),
    }
    args = (
        base, peft, adamw_init(peft), batches,
        jnp.asarray(0.5, jnp.float32), key, jnp.asarray(0, jnp.int32),
    )
    return fns, base, args


def _stacking_concats(fns, base, args, num_active=None):
    """Concatenate eqns in the traced local_round whose output shape matches
    a stacked base-layer leaf (i.e. trace-time layer stacking).  The walker
    lives in ``repro.analysis`` and is shared with the contract checker."""
    target_shapes = jaxpr_contracts.stacked_leaf_shapes(base["layers"])
    jaxpr = jax.make_jaxpr(
        lambda *a: fns.local_round(*a, num_active=num_active)
    )(*args)
    return jaxpr_contracts.stacking_concats(jaxpr, target_shapes)


@pytest.mark.parametrize("stld_mode,num_active", [("cond", None), ("gather", 2)])
def test_no_traced_base_stack_in_stacked_layout(stld_mode, num_active):
    """Acceptance: no jnp.stack of base-layer params inside the traced
    training program when the stacked layout is used — and the list layout
    DOES contain one, proving the test can detect a regression."""
    fns, base, args = _client_setup("stacked", stld_mode)
    assert _stacking_concats(fns, base, args, num_active) == []
    fns, base, args = _client_setup("list", stld_mode)
    assert len(_stacking_concats(fns, base, args, num_active)) > 0


def test_signature_leaf_count_reduction():
    """O(L·k) -> O(k): the stacked client signature must not scale with L."""
    _, base_s, args_s = _client_setup("stacked")
    _, base_l, args_l = _client_setup("list")
    leaves_s = len(jax.tree.leaves(args_s))
    leaves_l = len(jax.tree.leaves(args_l))
    assert leaves_l > leaves_s * 2
    # base layers alone: k leaves vs L·k
    n_stacked = len(jax.tree.leaves(base_s["layers"]))
    n_list = len(jax.tree.leaves(base_l["layers"]))
    assert n_list == n_stacked * _CFG.num_layers


# ----------------------------------------------------- checkpoint back-compat
def _experiment_kwargs(tmp, **kw):
    return dict(
        cfg=_CFG, peft_cfg=PEFTConfig(method="lora", lora_rank=2),
        stld_cfg=STLDConfig(mode="cond", mean_rate=0.5),
        fed_cfg=_FED, train_cfg=_TRAIN, seed=3, task=_TASK, **kw,
    )


def test_list_layout_checkpoint_resumes_bit_identical(tmp_path):
    """A pre-refactor (list-layout) ``save_state`` checkpoint loads into the
    stacked-native runner and resumes exactly like an uninterrupted run."""
    from repro.checkpoint import ckpt as ckpt_lib

    full_dir = str(tmp_path / "full")
    runner = api.build(
        "droppeft", **_experiment_kwargs(
            tmp_path, checkpoint_dir=full_dir, checkpoint_every=2,
        )
    )
    res_full = runner.run(rounds=4)

    # replay the first 2 rounds, then rewrite the checkpoint's PEFT trees
    # into the legacy list layout (exactly what a pre-refactor run saved)
    half_dir = str(tmp_path / "half")
    r1 = api.build(
        "droppeft", **_experiment_kwargs(
            tmp_path, checkpoint_dir=half_dir, checkpoint_every=2,
        )
    )
    r1.run(rounds=2)
    latest = ckpt_lib.latest_state_dir(half_dir)
    arrays, meta = ckpt_lib.load_state(latest)
    num_layers = _CFG.num_layers

    def to_list(tree):
        return [
            jax.tree.map(lambda x: np.asarray(x)[l], tree) for l in range(num_layers)
        ]

    arrays["global_peft"] = to_list(arrays["global_peft"])
    arrays["device_peft"] = {
        d: to_list(t) for d, t in arrays["device_peft"].items()
    }
    ckpt_lib.save_state(half_dir, meta["round_index"], arrays, meta)

    r2 = api.build(
        "droppeft", **_experiment_kwargs(
            tmp_path, checkpoint_dir=half_dir, checkpoint_every=2, resume=True,
        )
    )
    assert r2.state.round_index == 2
    assert stacking.is_stacked(r2.state.global_peft)  # converted on load
    res_resumed = r2.run(rounds=4)
    for f in ("cum_time_s", "accuracy", "loss", "rates", "traffic_mb"):
        np.testing.assert_array_equal(
            getattr(res_full, f), getattr(res_resumed, f), err_msg=f
        )
    assert res_full.final_accuracy == res_resumed.final_accuracy


# -------------------------------------------------------------- donation
def test_donation_safe_round_trip():
    """With donation force-enabled, repeated engine-style rounds never reuse
    a donated buffer (fresh stacks each round) and reproduce the
    non-donating programs' results.

    NOTE: XLA CPU ignores donation, so on the CPU-only CI runner this test
    exercises the donate_argnums plumbing and call discipline but cannot
    observe actual buffer invalidation — the ``is_deleted`` assertions below
    only engage on GPU/TPU, where donation is real."""
    pcfg = PEFTConfig(method="lora", lora_rank=2)
    scfg = STLDConfig(mode="cond", mean_rate=0.5)
    fns_d = make_client_fns(_CFG, pcfg, scfg, _TRAIN, donate=True)
    fns_n = make_client_fns(_CFG, pcfg, scfg, _TRAIN, donate=False)
    key = jax.random.PRNGKey(0)
    base = init_params(key, _CFG)
    peft = peft_lib.init_peft(key, _CFG, pcfg)
    n = 3
    batch_stack = {
        "tokens": jnp.zeros((n, 2, 4, 8), dtype=jnp.int32),
        "targets": jnp.zeros((n, 2, 4, 8), dtype=jnp.int32),
        "mask": jnp.ones((n, 2, 4, 8), dtype=jnp.float32),
    }
    rates = jnp.full((n,), 0.3, dtype=jnp.float32)
    rngs = jnp.stack(jax.random.split(key, n))
    gsteps = jnp.arange(n, dtype=jnp.int32)
    val = (
        jnp.zeros((n, 4, 8), dtype=jnp.int32),
        jnp.zeros((n, 4), dtype=jnp.int32),
        jnp.ones((n, 4), dtype=jnp.float32),
        jnp.arange(4),
    )

    def stack_fresh():
        return jax.tree.map(lambda *xs: jnp.stack(xs), *([peft] * n))

    ref = None
    for _ in range(2):  # a fresh stack per round: donate never sees a reuse
        donated = stack_fresh()
        out_d = fns_d.cohort_round_eval(
            base, donated, batch_stack, rates, rngs, gsteps, *val
        )
        out_n = fns_n.cohort_round_eval(
            base, stack_fresh(), batch_stack, rates, rngs, gsteps, *val
        )
        for a, b in zip(jax.tree.leaves(out_d), jax.tree.leaves(out_n)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if jax.default_backend() != "cpu":
            # where XLA implements donation the input buffer must be gone
            assert all(x.is_deleted() for x in jax.tree.leaves(donated))
        ref = out_d
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(ref))

    # local_round donates its AdamW state: fresh state per call is safe
    batches = {k: v[0] for k, v in batch_stack.items()}
    out1 = fns_d.local_round(
        base, peft, adamw_init(peft), batches, rates[0], rngs[0], gsteps[0]
    )
    out2 = fns_n.local_round(
        base, peft, adamw_init(peft), batches, rates[0], rngs[0], gsteps[0]
    )
    for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- stacked select ops
def test_select_layers_matches_list_selection(key):
    from repro.federated import server as server_lib

    pcfg = PEFTConfig(method="lora", lora_rank=2)
    g = peft_lib.init_peft(key, _CFG, pcfg)
    o = jax.tree.map(lambda x: x + 1.0, g)
    mask = np.array([True, False, True, False])
    sel = server_lib.select_layers(mask, g, o)
    gl, ol = stacking.unstack_params(g), stacking.unstack_params(o)
    expect = [gl[l] if mask[l] else ol[l] for l in range(_CFG.num_layers)]
    _tree_equal(stacking.unstack_params(sel), expect)
