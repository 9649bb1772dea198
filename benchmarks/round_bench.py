"""Per-round dispatch bench: stacked-native vs list-layout client programs.

The stacked-native parameter layout changes two things about every round's
``cohort_round_eval`` dispatch on exactly the same math:

* the traced program no longer calls ``jnp.stack`` over the per-layer base
  weights (list layout materializes a second full copy of the frozen base
  inside each compiled step), and
* the call signature shrinks from O(L·k) pytree leaves to O(k), so the
  per-dispatch arg flattening cost stops scaling with depth.

Both layouts are timed through the *same* jit'd ``ClientFns`` factory on the
smoke cohort workload (8 devices, 8 layers, 1 step x batch 4 x seq 8 — the
dispatch-bound regime from ``cohort_bench``).  Measurement discipline per
the container profile: interleaved min-of-N trials (background load is
additive noise that min filters out) and an explicit margin before any
claim is asserted.  The asserted claim is *stacked-native >= list-layout*
(i.e. at least parity within ``MARGIN``); the measured speedup is reported,
not asserted, because at smoke scale this 2-core container is
op-overhead-bound and the margin must not overclaim.

Outputs: CSV rows (stdout, like every bench), one JSON summary line, and a
``BENCH_round.json`` file for the CI artifact trail.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, sim_model_cfg, train_cfg
from repro.configs import PEFTConfig, STLDConfig
from repro.core import peft as peft_lib
from repro.federated.client import make_client_fns
from repro.models.registry import init_params

_DEVICES = 8
_STEPS = 1
_BATCH = 4
_SEQ = 8
MARGIN = 0.05  # claim threshold: stacked >= list within 5% measurement noise


def _cohort_args(cfg, peft_tree, key):
    """Stacked-over-devices cohort inputs for ``cohort_round_eval``."""
    n = _DEVICES
    peft_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *([peft_tree] * n))
    kb, kt, kv = jax.random.split(key, 3)
    batch_stack = {
        "tokens": jax.random.randint(kb, (n, _STEPS, _BATCH, _SEQ), 0, cfg.vocab_size),
        "targets": jax.random.randint(kt, (n, _STEPS, _BATCH, _SEQ), 0, cfg.vocab_size),
        "mask": jnp.ones((n, _STEPS, _BATCH, _SEQ), dtype=jnp.float32),
    }
    rates = jnp.full((n,), 0.5, dtype=jnp.float32)
    rngs = jnp.stack(jax.random.split(key, n))
    gsteps = jnp.arange(n, dtype=jnp.int32)
    val_tokens = jax.random.randint(kv, (n, _BATCH, _SEQ), 0, cfg.vocab_size)
    val_labels = jnp.zeros((n, _BATCH), dtype=jnp.int32)
    val_valid = jnp.ones((n, _BATCH), dtype=jnp.float32)
    num_classes = jnp.arange(4)
    return (
        peft_stack, batch_stack, rates, rngs, gsteps,
        val_tokens, val_labels, val_valid, num_classes,
    )


def run(quick: bool = False):
    reps = 5 if quick else 20
    trials = 2 if quick else 5
    cfg = sim_model_cfg()
    pcfg = PEFTConfig(method="lora", lora_rank=4)
    scfg = STLDConfig(mode="cond", mean_rate=0.5)
    fns = make_client_fns(cfg, pcfg, scfg, train_cfg(), donate=False)
    key = jax.random.PRNGKey(0)

    layouts = {}
    for layout in ("stacked", "list"):
        base = init_params(key, cfg, layout=layout)
        peft = peft_lib.init_peft(jax.random.fold_in(key, 1), cfg, pcfg, layout=layout)
        layouts[layout] = (base, _cohort_args(cfg, peft, jax.random.fold_in(key, 2)))

    # leaf-count reduction of the client call signature (O(L·k) -> O(k))
    leaves = {
        layout: len(jax.tree.leaves((base, args)))
        for layout, (base, args) in layouts.items()
    }

    # warm both compiled programs, then interleave trials; keep per-layout
    # minima (min-of-trials filters the shared container's additive noise)
    outs = {}
    for layout, (base, args) in layouts.items():
        outs[layout] = fns.cohort_round_eval(base, *args)
        jax.block_until_ready(outs[layout])
    best = {layout: float("inf") for layout in layouts}
    for _ in range(trials):
        for layout, (base, args) in layouts.items():
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fns.cohort_round_eval(base, *args))
            best[layout] = min(best[layout], (time.perf_counter() - t0) / reps)

    # same math: the two layouts must produce matching round outputs
    # (canonicalize the list-layout PEFT output to stacked leaves first —
    # the device axis leads, so stack per-layer trees along axis 1)
    def canon(out):
        peft_out, metrics, imps, accs = out
        if isinstance(peft_out, (list, tuple)):
            peft_out = jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=1), *peft_out
            )
        return (peft_out, metrics, imps, accs)

    ls, ll = (jax.tree.leaves(canon(outs[k])) for k in ("stacked", "list"))
    parity = len(ls) == len(ll) and all(
        np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        for a, b in zip(ls, ll)
    )

    for layout in best:
        emit(
            f"round/dispatch_{layout}",
            best[layout] * 1e6,
            f"devices={_DEVICES};reps={reps};trials={trials};leaves={leaves[layout]}",
        )
    speedup = best["list"] / best["stacked"]
    leaf_reduction = leaves["list"] / leaves["stacked"]
    emit("round/dispatch_speedup", 0.0, f"x{speedup:.2f};margin={MARGIN}")
    emit("round/signature_leaf_reduction", 0.0, f"x{leaf_reduction:.1f}")

    summary = {
        "bench": "round",
        "devices": _DEVICES,
        "layers": cfg.num_layers,
        "dispatch_list_ms": round(best["list"] * 1e3, 3),
        "dispatch_stacked_ms": round(best["stacked"] * 1e3, 3),
        "speedup_min_of_trials": round(speedup, 3),
        "margin": MARGIN,
        "claim_stacked_not_slower": speedup >= 1.0 - MARGIN,
        "leaves_list": leaves["list"],
        "leaves_stacked": leaves["stacked"],
        "leaf_reduction": round(leaf_reduction, 1),
        "outputs_match": parity,
        "reps": reps,
        "trials": trials,
    }
    print(json.dumps(summary))
    out_path = os.environ.get("BENCH_ROUND_JSON", "BENCH_round.json")
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=2)

    assert parity, "stacked-native and list-layout rounds diverged"
    assert leaf_reduction >= 4.0, (
        f"stacked signature should shrink the dispatch pytree by >= 4x "
        f"(O(L·k) -> O(k)); got {leaves['list']} -> {leaves['stacked']}"
    )
    # the asserted perf claim: stacked-native is at least as fast as the
    # list layout on min-of-trials wall-clock, within the stated margin
    assert speedup >= 1.0 - MARGIN, (
        f"stacked-native round dispatch slower than list layout beyond the "
        f"{MARGIN:.0%} margin: {best['stacked']*1e3:.3f}ms vs "
        f"{best['list']*1e3:.3f}ms (x{speedup:.2f})"
    )


if __name__ == "__main__":
    run()
