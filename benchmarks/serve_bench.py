"""Multi-tenant serving bench: batched heterogeneous-LoRA decode vs
per-request adapter switching.

The tentpole claim of the serving subsystem: admitting every tenant's
request into ONE fused decode batch (per-row adapters via the segmented
gather kernel, continuous batching) beats the naive server that processes
requests one at a time, switching the active adapter between requests.
Both sides run the *same* compiled pooled decode program — the baseline is
simply batch=1 with sequential requests — so the measured gap is the
batching win, not a kernel difference.

Also asserted: adapter hot-swap into a recycled pool slot causes ZERO
steady-state recompiles (pool shapes static, slot index + contents traced).

Measurement discipline per the container profile: interleaved min-of-N
trials and an explicit margin before the claim is asserted.  Outputs: CSV
rows, one JSON summary line, and ``BENCH_serve.json`` for CI artifacts.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, sim_model_cfg
from repro.analysis.recompile_guard import CompilationCounter
from repro.configs import PEFTConfig
from repro.core import peft as peft_lib
from repro.launch.steps import make_serve_step
from repro.models.registry import init_params
from repro.serving.adapters import AdapterPoolCache, AdapterRegistry
from repro.serving.batcher import ContinuousBatcher, Request

MARGIN = 0.05
CLAIM_SPEEDUP = 2.0  # batched multi-adapter >= 2x per-request switching
_BATCH = 4
_TENANTS = 6  # > n_slots so steady state exercises hot-swap eviction
_PROMPT = 4


def _registry(cfg, key):
    reg = AdapterRegistry()
    for i in range(_TENANTS):
        rank = (4, 8)[i % 2]  # hetlora mixed ranks share one pool
        pcfg = PEFTConfig(method="lora", lora_rank=rank, lora_targets=("q", "v"))
        tree = peft_lib.init_peft(jax.random.fold_in(key, 100 + i), cfg, pcfg)
        reg.register(f"tenant{i}", tree)
    return reg


def _submit(batcher, cfg, key, gen_len, tenants):
    for j, t in enumerate(tenants):
        prompt = jax.random.randint(
            jax.random.fold_in(key, j), (_PROMPT,), 0, cfg.vocab_size
        ).tolist()
        batcher.submit(
            Request(prompt=prompt, adapter=f"tenant{t}", max_new_tokens=gen_len, uid=j)
        )


def run(quick: bool = False):
    gen_len = 8 if quick else 32
    trials = 2 if quick else 5
    cfg = sim_model_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    serve = make_serve_step(cfg)
    reg = _registry(cfg, key)
    max_len = _PROMPT + gen_len + 1

    def make_batcher(batch):
        pool = AdapterPoolCache(reg, n_slots=_BATCH)
        return ContinuousBatcher(
            serve, params, cfg, pool,
            batch=batch, max_len=max_len, cache_dtype=jnp.float32,
        )

    # one batcher per mode, reused across trials: jit caches live on the
    # batcher's step closure, so fresh batchers would re-pay compilation
    batched = make_batcher(_BATCH)
    switching = make_batcher(1)

    def run_batched(tenants):
        _submit(batched, cfg, key, gen_len, tenants)
        return batched.run()

    def run_switching(tenants):
        # per-request serving: one request at a time, adapter switched
        # (pool slot swap) between requests
        done = []
        for t in tenants:
            _submit(switching, cfg, key, gen_len, [t])
            done += switching.run()
        return done

    tenant_sets = [[0, 1, 2, 3], [2, 3, 4, 5]]  # second set forces hot-swaps
    # warm both compiled programs (and the slot-write program)
    n0 = len(run_batched(tenant_sets[0]))
    n1 = len(run_switching(tenant_sets[0]))
    assert n0 == len(tenant_sets[0]) and n1 == len(tenant_sets[0])

    # steady state: rotating the tenant mix (adapter hot-swap into recycled
    # slots) must not trigger a single compile
    with CompilationCounter() as cc:
        out = run_batched(tenant_sets[1])
    steady_recompiles = cc.count
    assert len(out) == len(tenant_sets[1])

    best = {"batched": float("inf"), "switching": float("inf")}
    tokens = {}
    for trial in range(trials):
        tenants = tenant_sets[trial % len(tenant_sets)]
        for name, fn in (("batched", run_batched), ("switching", run_switching)):
            t0 = time.perf_counter()
            done = fn(tenants)
            dt = time.perf_counter() - t0
            tokens[name] = sum(len(c.tokens) for c in done)
            best[name] = min(best[name], dt / max(tokens[name], 1))

    tps = {name: 1.0 / best[name] for name in best}
    for name in tps:
        emit(
            f"serve/{name}_tok_s", best[name] * 1e6,
            f"tok_s={tps[name]:.1f};batch={_BATCH};gen={gen_len};trials={trials}",
        )
    speedup = tps["batched"] / tps["switching"]
    claim_ok = speedup >= CLAIM_SPEEDUP * (1.0 - MARGIN)
    emit("serve/batched_speedup", 0.0, f"x{speedup:.2f};claim>={CLAIM_SPEEDUP}")
    emit("serve/steady_state_recompiles", 0.0, f"n={steady_recompiles}")

    summary = {
        "bench": "serve",
        "batch": _BATCH,
        "tenants": _TENANTS,
        "gen_len": gen_len,
        "batched_tok_s": round(tps["batched"], 2),
        "switching_tok_s": round(tps["switching"], 2),
        "speedup_min_of_trials": round(speedup, 3),
        "margin": MARGIN,
        "claim_batched_2x": claim_ok,
        "steady_state_recompiles": steady_recompiles,
        "pool_swaps": batched.pool.swaps,
        "trials": trials,
    }
    print(json.dumps(summary))
    out_path = os.environ.get("BENCH_SERVE_JSON", "BENCH_serve.json")
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=2)

    assert steady_recompiles == 0, (
        f"adapter hot-swap must reuse the compiled serving step; "
        f"counted {steady_recompiles} steady-state compiles"
    )
    # the speedup claim is wall-clock and flakes on shared CI runners:
    # always recorded in BENCH_serve.json, asserted only in strict mode
    # (the default locally; on CI it downgrades to a warning unless
    # BENCH_SERVE_STRICT=1 opts back in)
    strict = os.environ.get(
        "BENCH_SERVE_STRICT", "0" if os.environ.get("CI") else "1"
    ) == "1"
    if not claim_ok:
        msg = (
            f"batched multi-adapter decode should be >= {CLAIM_SPEEDUP}x "
            f"per-request switching; got x{speedup:.2f}"
        )
        if strict:
            raise AssertionError(msg)
        print(f"# WARNING (non-strict): {msg}", file=sys.stderr)


if __name__ == "__main__":
    run()
