"""Drive the main path once on one TPU chip at qwen3-1.7b's full width.

    python3 chip_smoke.py

One process, one chip, random weights from seed 0 and the synthetic task:

1. Train: two DropPEFT rounds through ``api.build`` and ``runner.run`` —
   cond-mode STLD, 16 devices, cohort 4, 4 local steps, batch 16 — so the
   batched cohort engine, PTLS aggregation and the all-device final accuracy
   all run.
2. Serve: the rounds' per-device adapters through ``api.serve`` (continuous
   batcher, segmented multi-adapter LoRA kernel), sharing the trainer's base
   weights.
3. Check: finite losses and accuracies, adapters moved off their initial
   values, every request finished with in-vocabulary tokens, and one
   full-width ``segmented_lora`` call agreeing with its XLA reference.

Exits non-zero, printing no result, when JAX finds no TPU: there is no CPU
fallback.  The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROUNDS = 2
SERVE_REQUESTS = 8
SERVE_NEW_TOKENS = 16


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def leaves_differ(a, b) -> bool:
    import jax
    import numpy as np

    return any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def print_memory(dev, after: str) -> None:
    stats = dev.memory_stats() or {}
    print(
        f"memory after {after}: bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
        flush=True,
    )


def train(api, configs):
    import jax
    import numpy as np

    fed = configs.FederatedConfig(
        num_devices=16, devices_per_round=4, local_steps=4, batch_size=16, seed=0
    )
    runner = api.build(
        "droppeft",
        smoke=False,
        stld_mode="cond",
        fed_cfg=fed,
        train_cfg=configs.TrainConfig(
            learning_rate=5e-3, total_steps=ROUNDS * fed.local_steps
        ),
        seed=0,
    )
    check(runner.cohort_mode == "batched", f"cohort mode {runner.cohort_mode}")
    print_memory(jax.devices()[0], "build")
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        res = runner.run(rounds=r + 1)  # this round, then the all-device accuracy
        jax.block_until_ready((runner.state.global_peft, runner.state.device_peft))
        wall = time.perf_counter() - t0
        print(
            f"train run(rounds={r + 1}): wall_s={wall!r} loss={float(res.loss[r])!r} "
            f"acc={float(res.accuracy[r])!r} rate={float(res.rates[r])!r} "
            f"active={float(res.active_fraction[r])!r} "
            f"final_accuracy={float(res.final_accuracy)!r}",
            flush=True,
        )
    check(res.rounds == ROUNDS, f"{res.rounds} rounds recorded")
    for name in ("loss", "accuracy"):
        check(bool(np.all(np.isfinite(getattr(res, name)))), f"finite {name}")
    check(bool(np.isfinite(res.final_accuracy)), "finite final accuracy")
    state = runner.state
    init = runner.ctx.init_global_peft
    check(leaves_differ(state.global_peft, init), "global adapter moved")
    check(len(state.device_peft) >= 3, f"{len(state.device_peft)} trained devices")
    for dev, tree in state.device_peft.items():
        check(leaves_differ(tree, init), f"device {dev} adapter moved")
    return runner


def serve(api, runner):
    import numpy as np

    from repro.serving.batcher import Request

    adapters = {f"client{d}": t for d, t in sorted(runner.state.device_peft.items())}
    batcher = api.serve(
        cfg=runner.ctx.cfg,
        params=runner.ctx.engine.base_params,
        adapters=adapters,
        batch=4,
        max_len=256,
    )
    names = list(adapters)[:4]
    val = runner.ctx.devices[0].val_batch()["tokens"]
    for j in range(SERVE_REQUESTS):
        batcher.submit(
            Request(
                prompt=val[j % len(val)].tolist(),
                adapter=names[j % len(names)],
                max_new_tokens=SERVE_NEW_TOKENS,
                uid=j,
            )
        )
    t0 = time.perf_counter()
    done = batcher.run()  # every step pulls its tokens to the host
    wall = time.perf_counter() - t0
    tokens = sum(len(c.tokens) for c in done)
    print(
        f"serve: requests={len(done)} adapters={len({c.adapter for c in done})} "
        f"tokens={tokens} wall_s={wall!r} swaps={batcher.pool.swaps}",
        flush=True,
    )
    vocab = runner.ctx.cfg.vocab_size
    check(len(done) == SERVE_REQUESTS, f"{len(done)}/{SERVE_REQUESTS} requests finished")
    check(len({c.adapter for c in done}) >= 3, "at least 3 adapters served")
    for c in done:
        check(len(c.tokens) == SERVE_NEW_TOKENS, f"request {c.uid}: {len(c.tokens)} tokens")
        check(all(0 <= t < vocab for t in c.tokens), f"request {c.uid}: token outside vocab")


def kernel_parity(cfg):
    """One full-width q projection through the Pallas kernel and its XLA form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import segmented_lora

    m, k, n, n_adapters, r_max = 4, cfg.d_model, cfg.num_heads * cfg.resolved_head_dim, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (k, n)) * k**-0.5).astype(jnp.bfloat16)
    a = (jax.random.normal(keys[2], (n_adapters, k, r_max)) * k**-0.5).astype(jnp.bfloat16)
    b = jax.random.normal(keys[3], (n_adapters, r_max, n), jnp.bfloat16)
    idx = jnp.asarray([2, 0, 3, 0], jnp.int32)
    ranks = jnp.asarray([8, 4, 8, 2], jnp.int32)
    got = np.asarray(segmented_lora(x, w, a, b, idx, ranks).astype(jnp.float32))
    ref = np.asarray(segmented_lora(x, w, a, b, idx, ranks, impl="xla").astype(jnp.float32))
    err = float(np.max(np.abs(got - ref)))
    print(f"segmented_lora ({m}x{k} @ {k}x{n}, r_max {r_max}): max_abs_err={err!r}", flush=True)
    check(got.shape == (m, n) and bool(np.all(np.isfinite(got))), "finite kernel output")
    # bf16 output: allow one unit in the last place of the reference
    check(bool(np.allclose(got, ref, rtol=1e-2, atol=1e-2)), "kernel matches XLA reference")


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found {dev.platform} ({dev.device_kind}). "
            "There is no CPU fallback.",
            file=sys.stderr,
        )
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import api, configs
    from repro.analysis.recompile_guard import CompilationCounter
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    with CompilationCounter() as train_compiles:
        runner = train(api, configs)
    print(
        f"train compile: programs={train_compiles.count} "
        f"compile_s={train_compiles.seconds!r}",
        flush=True,
    )
    print_memory(dev, "train")
    with CompilationCounter() as serve_compiles:
        serve(api, runner)
    print(
        f"serve compile: programs={serve_compiles.count} "
        f"compile_s={serve_compiles.seconds!r}",
        flush=True,
    )
    print_memory(dev, "serve")
    kernel_parity(runner.ctx.cfg)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
