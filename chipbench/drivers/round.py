"""Federated rounds: DropPEFT through ``api.build`` and ``ExperimentRunner.run``.

Set-up builds one runner from the seed and drives its first round, plus the
all-device accuracy that ends every ``run`` call, through the same call the
window makes.  That round's inputs and outputs are kept on the host for the
comparison with the reference.  The window then repeats ``run`` calls of
``rounds_per_call`` rounds until ``--seconds`` have passed; it counts the
client training tokens of the rounds it completed, and the time of the
evaluations that end the calls counts in the window.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import flops


def setup(ctx):
    """The runner built from the seed, driven through its first round; the
    round's inputs and outputs are left in ``ctx.check_inputs``."""
    from repro import api
    from repro.configs import FederatedConfig, PEFTConfig, STLDConfig, TrainConfig
    from repro.data import make_task

    cfg, mix, seed = ctx.model_config, ctx.traffic, ctx.seed
    peft = ctx.config["peft"]
    fed = FederatedConfig(
        num_devices=mix["num_devices"],
        devices_per_round=mix["devices_per_round"],
        local_steps=mix["local_steps"],
        batch_size=mix["batch_size"],
        ptls_share_fraction=mix["ptls_share_fraction"],
        seed=seed,
    )
    task = make_task(seq_len=mix["seq_len"], vocab_size=cfg.vocab_size,
                     num_examples=mix["num_examples"], seed=seed)
    runner = api.build(
        "droppeft",
        cfg=cfg,
        peft_cfg=PEFTConfig(method=peft["method"], lora_rank=peft["lora_rank"],
                            lora_alpha=peft["lora_alpha"], lora_targets=tuple(peft["lora_targets"])),
        stld_cfg=STLDConfig(mode=mix["stld_mode"], mean_rate=mix["fixed_rate"],
                            distribution=mix["distribution"], min_active_layers=mix["min_active_layers"]),
        fixed_rate=mix["fixed_rate"],
        fed_cfg=fed,
        train_cfg=TrainConfig(**mix["train"]),
        schedule=mix["schedule"],
        task=task,
        seed=seed,
        cohort_mode="batched",
    )
    engine = runner.ctx.engine
    if runner.cohort_mode != "batched":
        raise RuntimeError(f"cohort mode {runner.cohort_mode}, expected batched")

    # keep the first round's inputs and outputs, as the program saw them
    first = {}
    cohort_fn, aggregate_fn = engine.client.cohort_round_eval, engine.ptls_aggregate

    def cohort_round_eval(base, peft_stack, batch_stack, rates, keys, gsteps, *rest, **kw):
        if "inputs" not in first:
            first["inputs"] = jax.device_get((peft_stack, batch_stack, rates, keys, gsteps))
        out = cohort_fn(base, peft_stack, batch_stack, rates, keys, gsteps, *rest, **kw)
        if "outputs" not in first:
            first["outputs"] = jax.device_get(out[:3])
        return out

    def ptls_aggregate(trees, masks, prev, **kw):
        out = aggregate_fn(trees, masks, prev, **kw)
        if "masks" not in first:
            first["masks"] = np.asarray(masks)
            first["global"] = jax.device_get(out)
        return out

    engine.client = engine.client._replace(cohort_round_eval=cohort_round_eval)
    engine.ptls_aggregate = ptls_aggregate

    with ctx.span("round_call"):
        runner.run(rounds=1)
    engine.client = engine.client._replace(cohort_round_eval=cohort_fn)
    engine.ptls_aggregate = aggregate_fn
    # a device sampled again starts from its kept layers: warm that program
    from repro.federated import server

    state = runner.state
    dev = next(iter(state.device_peft))
    jax.block_until_ready(server.select_layers(state.last_mask[dev], state.global_peft, state.device_peft[dev]))
    ctx.check_inputs = first
    return runner


def run(ctx):
    """Set-up, then the window; returns (record, attempted, failed)."""
    mix, peft = ctx.traffic, ctx.config["peft"]
    runner = setup(ctx)
    per_call = mix["rounds_per_call"]
    start_round = runner.state.round_index
    ctx.open_window()
    t0 = time.perf_counter()
    while True:
        with ctx.span("round_call"):
            res = runner.run(rounds=runner.state.round_index + per_call)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    ctx.close_window()
    rounds = runner.state.round_index - start_round
    active = [float(a) for a in res.active_fraction[start_round:]]
    losses = [float(x) for x in res.loss[start_round:]]

    shape = flops.Shape.of(ctx.config["model"])
    step_tokens = mix["batch_size"] * mix["seq_len"]
    steps = rounds * mix["devices_per_round"] * mix["local_steps"]
    required = sum(
        mix["devices_per_round"] * mix["local_steps"] * flops.round_step_flops(
            shape, batch=mix["batch_size"], seq=mix["seq_len"], active_layers=a * shape.layers,
            rank=peft["lora_rank"], targets=tuple(peft["lora_targets"]))
        for a in active
    )
    record = {
        "window_s": window_s,
        "round": {
            "rounds": rounds,
            "tokens": steps * step_tokens,
            "active_fraction": active,
            "loss": losses,
            "required_flops": required,
            "calls": -(-rounds // per_call),
        },
    }
    return record, rounds, 0


def check(ctx) -> list:
    """The first round against the reference: (name, value) pairs."""
    from chipbench.reference import compare

    return compare.round_numbers(ctx, ctx.check_inputs)
