"""Model registry: uniform init/apply across all 10 assigned architectures.

``init_params(key, cfg)``     -> param pytree
``model_apply(params, cfg, batch, **kw)`` -> (logits, aux, caches)

``batch`` keys by modality:
  text   : {"tokens": (B, S)}
  vision : {"tokens": (B, S), "patches": (B, P, d)}   (stub frontend)
  audio  : {"tokens": (B, S_dec), "frames": (B, S_enc, d)}  (stub frontend)

Layer stacks are emitted **stacked-native** — one leaf per param kind with a
leading ``(L, ...)`` layer axis — whenever the stack is homogeneous;
heterogeneous stacks (hybrid interleaves) keep the per-layer list layout.
``stack_params``/``unstack_params`` (re-exported from
:mod:`repro.models.stacking`) convert between the two for the
heterogeneous and hetlora paths.
"""
from __future__ import annotations

import jax

from repro.models import encdec, transformer
from repro.models.stacking import (  # noqa: F401  (public converter API)
    is_stacked,
    stack_params,
    unstack_params,
)


def build_model(cfg):
    """Return (init_fn, apply_fn) for the architecture family."""
    return init_params, model_apply


def init_params(key, cfg, layout: str = "auto"):
    if cfg.is_encoder_decoder:
        return encdec.init_encdec(key, cfg, layout)
    return transformer.init_lm(key, cfg, layout)


def model_apply(
    params,
    cfg,
    batch,
    *,
    drops=None,
    caches=None,
    enc_kvs=None,
    positions=None,
    peft=None,
    lora_scale: float = 1.0,
    active_idx=None,
    remat: bool = False,
    tail=None,
):
    if cfg.is_encoder_decoder:
        if enc_kvs is None:
            enc_out = encdec.encode(params, cfg, batch["frames"], peft=None)
            enc_kvs = encdec.encoder_cross_kvs(params, cfg, enc_out)
        return encdec.decode(
            params,
            cfg,
            batch["tokens"],
            enc_kvs,
            positions=positions,
            drops=drops,
            caches=caches,
            peft=peft,
            lora_scale=lora_scale,
            remat=remat,
            tail=tail,
        )
    prefix = batch.get("patches") if cfg.modality == "vision" else None
    return transformer.lm_apply(
        params,
        cfg,
        batch["tokens"],
        positions=positions,
        prefix_embeds=prefix,
        drops=drops,
        caches=caches,
        peft=peft,
        lora_scale=lora_scale,
        active_idx=active_idx,
        remat=remat,
        tail=tail,
    )


def gated_saved_bytes(params, cfg, tokens, *, peft=None) -> int:
    """Bytes :func:`model_apply` with ``drops`` and ``remat`` keeps across
    its layers' STLD gates for ``tokens`` (B, S) and the modality's stub
    frontend: :func:`transformer.gated_saved_bytes` at the stack's input.
    Reckoned from shapes (arrays or ``jax.ShapeDtypeStruct``s); nothing
    runs."""
    b, seq = tokens.shape
    if cfg.is_encoder_decoder:
        layers = params["decoder"]["layers"]
        frames = jax.ShapeDtypeStruct((b, cfg.frontend_seq, cfg.d_model), cfg.dtype)
        enc_kvs = jax.eval_shape(
            lambda p, f: encdec.encoder_cross_kvs(p, cfg, encdec.encode(p, cfg, f)),
            params,
            frames,
        )
    else:
        layers, enc_kvs = params["layers"], None
        if cfg.modality == "vision":
            seq += cfg.frontend_seq
    h = jax.ShapeDtypeStruct((b, seq, cfg.d_model), cfg.dtype)
    positions = jax.ShapeDtypeStruct((seq,), "int32")
    return transformer.gated_saved_bytes(
        layers, cfg, h, positions=positions, enc_kvs=enc_kvs, peft=peft
    )
