"""The comparison that decides ``correct``, driven end to end on the CPU at a
tiny size: a sound run passes its cell's limits, the float8 control reads
above the program, and a run whose timed path is broken underneath comes
out not correct, once for each fault a cell can have (one chip: no
exchange between chips to leave out)."""
import copy

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, readings, run

TINY = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512)
DANUBE_TINY = dict(d_model=160, head_dim=40, sliding_window=64)
PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


# cells whose files are ready but which BENCHMARK.json does not list yet
PREPARED = {
    "h2o-danube-1.8b.round.stld50": {"config": "h2o-danube-1.8b", "traffic": "fed.stld50.b16"},
    "qwen3-1.7b.serve.chat": {"config": "qwen3-1.7b", "traffic": "chat"},
    "qwen3-1.7b.round.stld0": {"config": "qwen3-1.7b", "traffic": "fed.stld0.b8"},
}


def _load(name):
    if name in PREPARED:
        return harness.cell_files(dict(PREPARED[name], name=name, chips=1), harness.load_benchmark())
    return harness.load_cell(name)


def _cell(name):
    """The cell at the tiny shape its limits' CPU readings were taken at."""
    cell = copy.deepcopy(_load(name))
    cell["config_file"]["model"].update(TINY)
    if "danube" in name:
        cell["config_file"]["model"].update(DANUBE_TINY)
    if cell["traffic_file"]["driver"] == "round":
        cell["traffic_file"].update(batch_size=cell["traffic_file"]["batch_size"] // 2, seq_len=16,
                                    num_examples=256, rounds_per_call=1)
    else:
        cell["traffic_file"].update(
            batch=4, max_len=64, n_slots=4, rate_per_s=8.0, warm_s=1.5, check_requests=4,
            prompt={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
            output={"median": 6, "sigma": 0.5, "min": 2, "max": 10})
    return cell


def _execute(cell, seed=2**31 + 17):
    return run.execute(cell, seed, 1.0, False, jax.devices(), PEAK)


ROUND, SERVE = "qwen3-1.7b.round.stld50", "qwen3-1.7b.serve.chat"


@pytest.mark.parametrize("name", [ROUND, "h2o-danube-1.8b.round.stld50", SERVE, "qwen3-1.7b.round.stld0"])
def test_sound_run_is_correct(name):
    result = _execute(_cell(name))
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


def _unchanged_state(monkeypatch):
    from repro.federated import client

    monkeypatch.setattr(client, "adamw_update", lambda grads, opt, params, **kw: (params, opt))


def _half_batch(monkeypatch):
    from repro.federated import client

    xent = client.softmax_xent

    def half(logits, labels, mask=None):
        rows = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return xent(logits, labels, mask * rows[:, None])

    monkeypatch.setattr(client, "softmax_xent", half)


def _altered_label(monkeypatch):
    from repro.federated import client

    xent = client.softmax_xent
    monkeypatch.setattr(client, "softmax_xent",
                        lambda logits, labels, mask=None: xent(logits, labels.at[0].add(1), mask))


def _altered_token(monkeypatch):
    from repro.launch import steps

    make = steps.make_serve_step

    def broken(cfg, **kw):
        step = make(cfg, **kw)

        def altered(*a, **k):
            logits, nxt, caches = step(*a, **k)
            return logits, nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), caches

        return altered

    monkeypatch.setattr(steps, "make_serve_step", broken)


@pytest.mark.parametrize(
    "name,fault",
    [(ROUND, _unchanged_state), (ROUND, _half_batch), (ROUND, _altered_label), (SERVE, _altered_token)],
    ids=["round-state-unchanged", "round-half-batch", "round-label-altered", "serve-token-altered"],
)
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    result = _execute(_cell(name))
    assert not result["correct"], result["checks"]


def _round_control(name):
    from chipbench.run import Context

    ctx = Context(_cell(name), 5, 1.0, False)
    got = readings.round_readings(ctx, ["program", "fp8"])
    assert got["program"]["correct"], got
    assert not got["fp8"]["correct"], got  # judged by the cell's own limits
    return got


def test_control_reads_above_the_program_round():
    got = _round_control(ROUND)
    assert got["fp8"]["loss_gap"] > 3 * got["program"]["loss_gap"]
    assert got["fp8"]["grad_gap"] > got["program"]["grad_gap"]


def test_control_is_not_correct_danube_round():
    got = _round_control("h2o-danube-1.8b.round.stld50")
    assert got["fp8"]["grad_gap"] > got["program"]["grad_gap"]


def test_control_reads_above_the_program_serve():
    from chipbench.run import Context

    ctx = Context(_cell(SERVE), 5, 2.0, False)
    got = readings.serve_readings(ctx, ["program", "fp8"])
    assert got["fp8"]["logit_gap"] > 3 * got["program"]["logit_gap"]


@pytest.mark.parametrize("name", ["qwen3-1.7b", "h2o-danube-1.8b"])
def test_reference_regenerates_the_programs_weights(name):
    """The reference's own initialiser, from the same key, gives the
    weights the program's does, at a tiny size of each configuration."""
    import numpy as np

    from chipbench.reference import model as ref
    from repro.configs import PEFTConfig
    from repro.configs.base import ModelConfig
    from repro.core.peft import init_peft
    from repro.models.registry import init_params

    model = dict(_load(f"{name}.round.stld50")["config_file"]["model"], **TINY)
    if name == "h2o-danube-1.8b":
        model.update(DANUBE_TINY)
    cfg, s = ModelConfig(**model), ref.sizes(model)
    key = jax.random.PRNGKey(2**31 + 3)
    prog, mine = init_params(key, cfg), ref.init_base(key, s)
    layers = prog["layers"]
    pairs = [(prog["embed"], mine["embed"]), (layers["attn"]["wq"]["w"], mine["layers"]["wq"]),
             (layers["attn"]["wv"]["w"], mine["layers"]["wv"]), (layers["mlp"]["down"]["w"], mine["layers"]["down"])]
    if not s["tied"]:
        pairs.append((prog["lm_head"], mine["lm_head"]))
    for a, b in pairs:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
    peft = init_peft(key, cfg, PEFTConfig())
    lora = ref.init_lora(key, s, 8)
    np.testing.assert_allclose(np.asarray(peft["attn"]["q"]["a"]), np.asarray(lora["q"]["a"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(peft["attn"]["v"]["b"]), np.asarray(lora["v"]["b"]))
