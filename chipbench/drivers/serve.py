"""Multi-tenant decode: ``api.serve``'s continuous batcher under open-loop load.

Set-up builds the batcher with the program's own base weights from the
seed and the mix's adapters (drawn from the seed by the benchmark), loads
every adapter into the pool, and runs one short request so that each
program the window uses is compiled or loaded.  Arrivals then start
``warm_s`` before the window opens, so the batch is in its steady state
when it does.  The harness submits each request once it is due and calls
``step()`` itself; every step ends in the host pulling its tokens.

Time to first token runs from when a request was due; a request due in the
window that has no token when it closes counts with the wait it has had.
Gaps between tokens are those whose later token falls in the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops
from chipbench.reference import model as ref
from chipbench.traffic import requests as traffic


def make_adapters(seed: int, s: dict, n: int, rank: int, b_std: float, targets=("q", "v")):
    """``n`` LoRA adapters drawn on the device in one call from the seed:
    ``a`` LeCun-normal, ``b`` normal with ``b_std``, layer-stacked."""
    out_dim = {"q": s["H"] * s["hd"], "k": s["KV"] * s["hd"], "v": s["KV"] * s["hd"]}

    @jax.jit
    def draw(key):
        trees = []
        for key_i in jax.random.split(key, n):
            tree = {}
            for t, (ka, kb) in zip(targets, jax.random.split(key_i, (len(targets), 2))):
                std_a = s["d"] ** -0.5
                tree[t] = {
                    "a": std_a * jax.random.normal(ka, (s["L"], s["d"], rank), jnp.float32),
                    "b": b_std * jax.random.normal(kb, (s["L"], rank, out_dim[t]), jnp.float32),
                }
            trees.append(tree)
        return trees

    return draw(jax.random.fold_in(jax.random.PRNGKey(seed), 1))


def run(ctx):
    from repro import api
    from repro.serving.batcher import Request

    cfg, mix, seed = ctx.model_config, ctx.traffic, ctx.seed
    peft = ctx.config["peft"]
    s = ref.sizes(ctx.config["model"])
    targets = tuple(peft["lora_targets"])
    adapters = make_adapters(seed, s, mix["adapters"], peft["lora_rank"], mix["adapter_b_std"], targets)
    names = [f"tenant{i}" for i in range(mix["adapters"])]
    batcher = api.serve(
        cfg=cfg,
        params=None,
        adapters={name: {"attn": tree} for name, tree in zip(names, adapters)},
        lora_alpha=peft["lora_alpha"],
        batch=mix["batch"],
        max_len=mix["max_len"],
        n_slots=mix["n_slots"],
        seed=seed,
    )
    for name in names:  # every tenant resident, as in a server that has run a while
        batcher.pool.acquire(name)
        batcher.pool.release(name)
    batcher.submit(Request(prompt=[1, 2], adapter=names[0], max_new_tokens=2, uid=-1))
    batcher.run()
    host_adapters = jax.device_get(adapters)
    del adapters

    arrivals = traffic.schedule(mix, seed, ctx.seconds, cfg.vocab_size)
    info = {a.uid: {"due": a.due_s, "tokens": [], "times": [], "adapter": a.adapter,
                    "prompt": a.prompt, "max_new": a.max_new_tokens} for a in arrivals}
    # what each step computed, read as the program's step is called
    steps = []
    step_fn = batcher._step

    def recorded_step(params, peft_tree, token, pos, caches):
        live = [i for i, r in enumerate(batcher.rows) if r is not None]
        steps.append(([int(batcher._pos[i]) + 1 for i in live], len({batcher.rows[i].slot for i in live})))
        return step_fn(params, peft_tree, token, pos, caches)

    batcher._step = recorded_step
    def collect(now):
        for row in batcher.rows:
            if row is not None:
                _note(row.req.uid, row.generated, now)
        for c in batcher.done:
            _note(c.uid, c.tokens, now)
        batcher.done.clear()

    def _note(uid, tokens, now):
        rec = info[uid]
        for tok in tokens[len(rec["tokens"]):]:
            rec["tokens"].append(int(tok))
            rec["times"].append(now)

    refused, nxt, late, queue = 0, 0, [], []
    clock0 = time.perf_counter() + mix["warm_s"]  # schedule time 0 = window opens
    now = lambda: time.perf_counter() - clock0
    opened = False
    steps_in_window = 0
    while True:
        t = now()
        if not opened and t >= 0.0:
            ctx.open_window()
            opened, steps_in_window = True, len(steps)
        if t >= ctx.seconds:
            break
        if opened and t >= len(queue):  # backlog once a second, for finding the knee
            queue.append(len(batcher.queue))
        with ctx.span("submit"):
            while nxt < len(arrivals) and arrivals[nxt].due_s <= t:
                a = arrivals[nxt]
                try:
                    batcher.submit(Request(prompt=list(a.prompt), adapter=names[a.adapter],
                                           max_new_tokens=a.max_new_tokens, uid=a.uid))
                except ValueError:
                    refused += int(a.due_s >= 0)
                if a.due_s >= 0:
                    late.append((now() - a.due_s) * 1e3)
                nxt += 1
        if batcher.queue or any(r is not None for r in batcher.rows):
            with ctx.span("step"):
                batcher.step()
            collect(now())
        else:
            wait = (arrivals[nxt].due_s if nxt < len(arrivals) else ctx.seconds) - now()
            with ctx.span("idle_wait"):
                time.sleep(max(0.0, min(wait, ctx.seconds - now())))
    close = now()
    ctx.close_window()

    due = [r for r in info.values() if 0.0 <= r["due"] < ctx.seconds]
    ttft = [((r["times"][0] if r["times"] and r["times"][0] <= close else close) - r["due"]) * 1e3 for r in due]
    itl = [(b - a) * 1e3 for r in info.values() for a, b in zip(r["times"], r["times"][1:]) if 0.0 <= b <= close]
    shape = flops.Shape.of(ctx.config["model"])
    window_steps = steps[steps_in_window:]
    work = [flops.decode_step(shape, contexts=ctx_lens, adapters_in_use=n_ad, rank=peft["lora_rank"],
                              targets=targets) for ctx_lens, n_ad in window_steps]
    kernel = []
    for ctx_lens, n_ad in window_steps:
        for n_out in (shape.q_out, shape.kv_out):  # one q and one v projection per layer
            f, b = flops.segmented_lora_call(rows=mix["batch"], k=shape.d, n=n_out,
                                             rank=peft["lora_rank"], adapters_in_use=n_ad)
            kernel.append((f * shape.layers, b * shape.layers))
    finished = [r for r in info.values() if len(r["tokens"]) == r["max_new"]]
    record = {
        "window_s": close,
        "serve": {
            "requests_due": len(due),
            "finished": len(finished),
            "ttft_ms": ttft,
            "itl_ms": itl,
            "steps": len(window_steps),
            "step_flops": [w[0] for w in work],
            "step_bytes": [w[1] for w in work],
            "kernel_flops": sum(k[0] for k in kernel),
            "kernel_bytes": sum(k[1] for k in kernel),
            "kernel_calls": 2 * shape.layers * len(window_steps),
            "late_submit_ms_p99": float(np.percentile(late, 99)) if late else 0.0,
            "queue": queue,
        },
    }
    ctx.check_inputs = (_sample(finished, seed, mix["check_requests"]), host_adapters)
    return record, len(due), refused


def _sample(finished, seed, k):
    """The longest finished request and ``k - 1`` others drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    pick = [order[0]] + [rest[i] for i in rng.permutation(len(rest))[: k - 1]]
    return [{"prompt": finished[i]["prompt"], "tokens": finished[i]["tokens"], "adapter": finished[i]["adapter"]}
            for i in pick]


def check(ctx) -> list:
    """The sampled requests against the reference: (name, value) pairs."""
    from chipbench.reference import compare

    sample, adapters = ctx.check_inputs
    if not sample:
        return [("logit_gap", float("inf"))]  # nothing finished: nothing served is correct
    gap = compare.serve_gaps(ctx, sample, adapters)["highest"]
    return [("logit_gap", gap)]
