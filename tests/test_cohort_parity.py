"""Batched cohort engine == sequential per-device loop, and the
configurator's vector-rate interface.

The batched engine (``cohort_round``: ``local_round`` over a leading device
axis, the devices in turn) must be a
pure execution-strategy change: for identical seeds both modes consume the
same PRNG streams and must produce numerically matching per-device PEFT
trees, round metrics, PTLS importances, and accuracies.  Exercised through
the new ``repro.api`` / ``ExperimentRunner`` surface.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.configs import FederatedConfig, PEFTConfig, STLDConfig, TrainConfig, get_config
from repro.core.configurator import OnlineConfigurator

_CFG = get_config("qwen3-1.7b", smoke=True).replace(
    num_layers=4, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
    vocab_size=128, dtype="float32",
)
_FED = FederatedConfig(num_devices=6, devices_per_round=4, local_steps=2, batch_size=8)
_TRAIN = TrainConfig(learning_rate=5e-3, total_steps=100, warmup_steps=2)


def _runner(mode, *, method="droppeft", stld_mode="cond", seed=3, stld_enabled=True):
    return api.build(
        method,
        cfg=_CFG,
        peft_cfg=PEFTConfig(method="lora", lora_rank=2),
        stld_cfg=STLDConfig(
            mode=stld_mode, mean_rate=0.5, gather_bucket=1, enabled=stld_enabled
        ),
        fed_cfg=_FED,
        train_cfg=_TRAIN,
        seed=seed,
        cohort_mode=mode,
    )


def _tree_allclose(a, b, atol=1e-5):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x, dtype=np.float64), np.asarray(y, dtype=np.float64), atol=atol
        )


def _run_cohort(runner, cohort, rates):
    state = runner.state
    start = [state.global_peft for _ in cohort]
    _, _, outs = runner.ctx.engine.run_cohort(
        state.key, 0, cohort, rates, start, runner.ctx.num_classes,
        runner.ctx.cfg.num_layers,
    )
    return outs


@pytest.mark.parametrize("stld_mode", ["cond", "gather"])
def test_cohort_round_parity(stld_mode):
    """Per-device PEFT trees, metrics, importances, and accuracies match
    between batched and sequential execution for the same PRNG keys.  The
    gather case exercises the static-count cohort grouping (two groups)."""
    run_s = _runner("sequential", stld_mode=stld_mode)
    run_b = _runner("batched", stld_mode=stld_mode)
    cohort = [0, 1, 2, 3]
    rates = [0.25, 0.5, 0.25, 0.5]

    outs_s = _run_cohort(run_s, cohort, rates)
    outs_b = _run_cohort(run_b, cohort, rates)
    assert len(outs_s) == len(outs_b) == 4
    for (p_s, m_s, imp_s, acc_s), (p_b, m_b, imp_b, acc_b) in zip(outs_s, outs_b):
        _tree_allclose(p_s, p_b)
        np.testing.assert_allclose(
            np.asarray(imp_s), np.asarray(imp_b), atol=1e-4, rtol=1e-4
        )
        for k in ("loss", "accuracy", "active_layers"):
            assert float(m_s[k]) == pytest.approx(float(m_b[k]), abs=1e-4)
        assert acc_s == pytest.approx(acc_b, abs=1e-5)


def test_full_run_parity_smoke():
    """End-to-end: both modes trace identical accuracy/loss/cost curves."""
    res_s = _runner("sequential").run(rounds=3)
    res_b = _runner("batched").run(rounds=3)
    np.testing.assert_allclose(res_s.accuracy, res_b.accuracy, atol=1e-5)
    np.testing.assert_allclose(res_s.loss, res_b.loss, atol=1e-4)
    np.testing.assert_allclose(res_s.cum_time_s, res_b.cum_time_s, rtol=1e-6)
    np.testing.assert_allclose(res_s.active_fraction, res_b.active_fraction, atol=1e-5)
    np.testing.assert_allclose(res_s.traffic_mb, res_b.traffic_mb, rtol=1e-6)
    assert res_s.final_accuracy == pytest.approx(res_b.final_accuracy, abs=1e-5)


def test_hetlora_forces_sequential_fallback():
    runner = _runner("auto", method="fedhetlora")
    assert runner.cohort_mode == "sequential"
    with pytest.raises(ValueError):
        _runner("batched", method="fedhetlora")


def test_configurator_vector_rate_interface():
    """Regression: per-device rate vectors (float32 arrays, as produced by
    the batched engine) round-trip through next_round/report without minting
    duplicate float32-drifted arms."""
    cfgor = OnlineConfigurator(
        rate_grid=(0.1, 0.3, 0.5),
        startup=(0.1, 0.5),
        num_candidates=2,
        explore_rate=0.5,
        explore_interval=2,
        seed=0,
    )
    for _ in range(8):
        rates = cfgor.next_round(4, as_array=True)
        assert isinstance(rates, np.ndarray) and rates.dtype == np.float32
        gains = np.full(4, 0.1, dtype=np.float32)
        times = np.ones(4, dtype=np.float32)
        cfgor.report(rates, gains, times)
    grid = (0.1, 0.3, 0.5)
    for arm_rate in cfgor.arms:
        assert any(arm_rate == g for g in grid), f"drifted arm key {arm_rate!r}"
    assert cfgor.best_rate() in grid


def _cond_depths(jaxpr, lengths=()):
    """The lengths of the scans around every ``cond`` in ``jaxpr``."""
    from jax.extend import core as jex_core

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            out.append(lengths)
        inner = lengths + (eqn.params["length"],) if eqn.primitive.name == "scan" else lengths
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    out += _cond_depths(sub, inner)
    return out


def test_cond_mode_cohort_skips_dropped_layers():
    """Cond-mode STLD: the cohort programs train their devices in turn, so
    every gate stays a real ``cond`` (``stablehlo.case``) inside the layer
    loop, as many as the un-vmapped local round has, and a dropped layer
    skips its compute (a vmapped cond would be a select that runs it).
    With STLD disabled the layers are ungated and nothing lowers to a
    case."""
    import jax.numpy as jnp

    from repro.optim import adamw_init

    n, s = 2, _FED.local_steps
    shape = (s, _FED.batch_size, 8)
    batch = {
        "tokens": jnp.zeros(shape, jnp.int32),
        "targets": jnp.zeros(shape, jnp.int32),
        "mask": jnp.ones(shape, jnp.float32),
    }
    stack = lambda t: jax.tree.map(lambda x: jnp.stack([x] * n), t)
    val = (jnp.zeros((n, 4, 8), jnp.int32), jnp.zeros((n, 4), jnp.int32), jnp.ones((n, 4)))
    for enabled in (True, False):
        runner = _runner("batched", stld_enabled=enabled)
        client = runner.ctx.engine.client
        base = runner.ctx.engine.base_params
        peft = runner.state.global_peft
        local = client.local_round.lower(
            base, peft, adamw_init(peft), batch, jnp.float32(0.5),
            jax.random.PRNGKey(0), jnp.int32(0),
        ).as_text()
        args = (
            base, stack(peft), stack(batch), jnp.full((n,), 0.5),
            jax.random.split(jax.random.PRNGKey(0), n), jnp.zeros((n,), jnp.int32),
        )
        programs = {
            "cohort_round_eval": (
                client.cohort_round_eval, args + val + (runner.ctx.num_classes,)
            ),
            "cohort_round": (client.cohort_round, args),
        }
        for name, (fn, fn_args) in programs.items():
            cases = fn.lower(*fn_args).as_text().count("stablehlo.case")
            depths = _cond_depths(jax.make_jaxpr(fn)(*fn_args).jaxpr)
            if not enabled:
                assert cases == 0 and depths == [], name
                continue
            assert cases == local.count("stablehlo.case") > 0, name
            # each cond sits in the layer scan, inside the scan over the
            # cohort's devices
            assert depths, name
            assert all(d[0] == n and _CFG.num_layers in d[1:] for d in depths), (name, depths)
