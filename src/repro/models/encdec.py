"""Whisper-style encoder-decoder (audio backbone; conv frontend stubbed).

The mel-spectrogram + conv feature extractor is a STUB per the assignment
carve-out: the encoder consumes precomputed frame embeddings
(B, frontend_seq, d_model) from ``input_specs``.  Everything downstream —
bidirectional encoder, causal decoder with cross-attention, KV-cache decode —
is fully implemented.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.models import stacking
from repro.models.layers import init_layer, init_layer_cache
from repro.models.transformer import _norm_apply, _norm_init, stack_apply
from repro.nn.attention import encode_cross_kv
from repro.nn.initializers import normal_init


def sinusoidal_positions(length: int, dim: int):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / dim))
    pe = jnp.zeros((length, dim), dtype=jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def init_encdec(key, cfg, layout: str = "auto"):
    k_enc, k_dec, k_emb, k_pos = jax.random.split(key, 4)
    enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    enc_layers = [
        init_layer(enc_keys[l], cfg, l, force_kind="attn")
        for l in range(cfg.num_encoder_layers)
    ]
    dec_layers = [init_layer(dec_keys[l], cfg, l) for l in range(cfg.num_layers)]
    return {
        "encoder": {
            "layers": stacking.maybe_stack(enc_layers, layout),
            "final_norm": _norm_init(cfg, cfg.d_model),
        },
        "decoder": {
            "embed": normal_init(k_emb, (cfg.vocab_size, cfg.d_model)),
            "pos_embed": normal_init(k_pos, (cfg.max_seq_len, cfg.d_model)),
            "layers": stacking.maybe_stack(dec_layers, layout),
            "final_norm": _norm_init(cfg, cfg.d_model),
        },
    }


def encode(
    params,
    cfg,
    frames,
    *,
    drops=None,
    peft: Optional[Sequence] = None,
    lora_scale: float = 1.0,
):
    """frames: (B, S_enc, d) stub embeddings -> (B, S_enc, d) encoder states."""
    compute_dtype = jnp.dtype(cfg.dtype)
    s = frames.shape[1]
    h = frames.astype(compute_dtype) + sinusoidal_positions(s, cfg.d_model).astype(
        compute_dtype
    )
    h, _, _ = stack_apply(
        params["encoder"]["layers"],
        cfg,
        h,
        positions=jnp.arange(s),
        causal=False,
        drops=drops,
        peft=peft,
        lora_scale=lora_scale,
    )
    return _norm_apply(cfg, params["encoder"]["final_norm"], h)


def encoder_cross_kvs(params, cfg, enc_out):
    """Precompute per-decoder-layer cross K/V once per sequence.  Returns a
    tree in the same layout as the decoder stack: one vmapped projection
    over the stacked layer axis, or a per-layer list."""
    layers = params["decoder"]["layers"]
    if stacking.is_stacked(layers):
        return jax.vmap(lambda cross: encode_cross_kv(cross, cfg, enc_out))(
            layers["cross"]
        )
    return [encode_cross_kv(layer["cross"], cfg, enc_out) for layer in layers]


def decode(
    params,
    cfg,
    tokens,
    enc_kvs,
    *,
    positions=None,
    drops=None,
    caches=None,
    peft: Optional[Sequence] = None,
    lora_scale: float = 1.0,
    remat: bool = False,
    tail: Optional[int] = None,
):
    """tokens: (B, S_dec).  ``tail`` runs the final norm and the output
    projection on the last ``tail`` positions only.  Returns (logits, aux,
    new_caches)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    dec = params["decoder"]
    h = dec["embed"][tokens].astype(compute_dtype)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    h = h + dec["pos_embed"].astype(compute_dtype)[positions]

    h, aux, new_caches = stack_apply(
        dec["layers"],
        cfg,
        h,
        positions=positions,
        causal=True,
        drops=drops,
        caches=caches,
        enc_kvs=enc_kvs,
        peft=peft,
        lora_scale=lora_scale,
        remat=remat,
    )
    if tail is not None:
        h = h[:, -tail:]
    h = _norm_apply(cfg, dec["final_norm"], h)
    logits = h @ dec["embed"].T.astype(compute_dtype)  # whisper ties output proj
    return logits, aux, new_caches


def init_decoder_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return [init_layer_cache(cfg, l, batch, max_len, dtype) for l in range(cfg.num_layers)]
