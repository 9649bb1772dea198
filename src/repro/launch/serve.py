"""Serving driver: prefill a prompt batch, then greedy-decode with KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --smoke \
        --prompt-len 64 --gen-len 32 --batch 4

Exercises the full serving path (prefill_step -> serve_step loop) for any
assigned architecture, including recurrent-state archs and the whisper
encoder-decoder.  With ``--merge-lora`` a trained LoRA checkpoint is folded
into the base weights first (deployment path).

Multi-tenant mode — ``--adapters N`` serves N tenants' LoRA adapters
(mixed hetlora ranks) through the continuous batcher and the segmented
gather kernel, one compiled decode step for the whole mix::

    PYTHONPATH=src python -m repro.launch.serve --smoke --adapters 3 \
        --batch 4 --gen-len 16
    PYTHONPATH=src python -m repro.launch.serve --smoke \
        --checkpoint-dir ckpts --batch 4    # federated client adapters
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, PEFTConfig, get_config
from repro.core import peft as peft_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models.registry import init_params
from repro.models.transformer import init_caches
from repro.serving.decode import generate


def _serve_multi_adapter(cfg, params, key, args):
    """Continuous-batching decode over per-tenant adapters."""
    from repro import api
    from repro.serving.batcher import Request

    adapters = None
    if args.checkpoint_dir is None:
        # synthetic tenants with alternating hetlora ranks
        adapters = {}
        for i in range(args.adapters):
            rank = (4, 8)[i % 2]
            pcfg = PEFTConfig(method="lora", lora_rank=rank, lora_targets=("q", "v"))
            tree = peft_lib.init_peft(jax.random.fold_in(key, 100 + i), cfg, pcfg)
            adapters[f"tenant{i}"] = tree
    batcher = api.serve(
        cfg=cfg,
        params=params,
        checkpoint_dir=args.checkpoint_dir,
        adapters=adapters,
        batch=args.batch,
        max_len=args.prompt_len + args.gen_len,
        cache_dtype=cfg.dtype,
    )
    names = batcher.pool.registry.names()
    for j in range(max(args.batch, len(names))):
        prompt = jax.random.randint(
            jax.random.fold_in(key, j), (args.prompt_len,), 0, cfg.vocab_size
        )
        batcher.submit(
            Request(
                prompt=prompt.tolist(),
                adapter=names[j % len(names)],
                max_new_tokens=args.gen_len,
                uid=j,
            )
        )
    t0 = time.time()
    done = batcher.run()
    dt = time.time() - t0
    total = sum(len(c.tokens) for c in done)
    print(f"arch={cfg.name} tenants={len(names)} requests={len(done)} "
          f"slots={batcher.pool.n_slots} swaps={batcher.pool.swaps}")
    print(f"decode: {dt*1e3:.1f} ms ({total/max(dt,1e-9):.1f} tok/s)")
    for c in done[: args.batch]:
        print(f"  req {c.uid} [{c.adapter}] {c.finish_reason}: {c.tokens[:8]}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--merge-lora", action="store_true")
    ap.add_argument("--adapters", type=int, default=0,
                    help="serve N synthetic tenant adapters (multi-tenant mode)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="serve the client adapters of a federated checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)

    if args.adapters > 0 or args.checkpoint_dir is not None:
        _serve_multi_adapter(cfg, params, key, args)
        return

    if args.merge_lora:
        peft_cfg = PEFTConfig(method="lora")
        peft_tree = peft_lib.init_peft(jax.random.fold_in(key, 1), cfg, peft_cfg)
        params = dict(params, layers=peft_lib.merge_lora_into_base(
            params["layers"], peft_tree, peft_lib.lora_scale(peft_cfg)))
        print("merged LoRA into base weights")

    max_len = args.prompt_len + args.gen_len
    if cfg.modality == "vision":
        max_len += cfg.frontend_seq  # cache also holds the patch prefix
    prefill = jax.jit(make_prefill_step(cfg))
    serve = jax.jit(make_serve_step(cfg))

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.modality == "vision":
        batch["patches"] = jnp.zeros((args.batch, cfg.frontend_seq, cfg.d_model), cfg.dtype)
    if cfg.modality == "audio":
        batch["frames"] = jnp.zeros((args.batch, cfg.frontend_seq, cfg.d_model), cfg.dtype)

    caches = init_caches(cfg, args.batch, max_len, dtype=jnp.dtype(cfg.dtype))
    t0 = time.time()
    enc_kvs = None
    if cfg.is_encoder_decoder:
        last_logits, caches, enc_kvs = prefill(params, batch, caches)
    else:
        last_logits, caches = prefill(params, batch, caches)
    t_prefill = time.time() - t0
    first = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)

    start_pos = args.prompt_len + (cfg.frontend_seq if cfg.modality == "vision" else 0)
    t0 = time.time()
    toks, caches = generate(serve, params, caches, first, start_pos, args.gen_len, enc_kvs=enc_kvs)
    toks.block_until_ready()
    t_decode = time.time() - t0

    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} gen={args.gen_len}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   decode: {t_decode*1e3:.1f} ms "
          f"({args.gen_len*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("sample tokens:", toks[0, :16].tolist())


if __name__ == "__main__":
    main()
