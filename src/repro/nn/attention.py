"""GQA attention: causal / bidirectional / sliding-window, KV-cache decode.

Two execution paths:

* ``naive`` — full (Sq, Skv) score matrix.  Used when the score tensor is
  small enough; FLOP-exact for ``cost_analysis``.
* ``q-blocked`` — python loop over query blocks (NOT ``lax.scan``) so
  ``cost_analysis`` still counts every block.  Bounds the transient score
  tensor for 32k-prefill shapes.

The Pallas flash-attention kernel (``repro.kernels.flash_attention``) is the
TPU production path, selected with ``attention_impl='pallas'``; the XLA paths
here are the portable reference used for CPU smoke tests (Pallas does not
lower to the CPU backend).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn.linear import apply_linear, init_linear
from repro.nn.norms import apply_rmsnorm, init_rmsnorm
from repro.nn.rotary import apply_rotary

NEG_INF = -1e30
# largest Sq*Skv score tile (per head, per batch element) before q-blocking
_MAX_NAIVE_SCORES = 8192 * 8192


def init_attention(key, cfg):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_linear(kq, d, cfg.num_heads * hd, bias=cfg.attention_bias),
        "wk": init_linear(kk, d, cfg.num_kv_heads * hd, bias=cfg.attention_bias),
        "wv": init_linear(kv, d, cfg.num_kv_heads * hd, bias=cfg.attention_bias),
        "wo": init_linear(ko, cfg.num_heads * hd, d, bias=False),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int]):
    """Additive mask bias from absolute positions.

    1D positions give (Sq, Skv); batched 2D positions — (B, Sq) / (B, Skv),
    the serving path where every row decodes at its own depth — give
    (B, Sq, Skv).
    """
    qe, ke = q_pos[..., :, None], k_pos[..., None, :]
    shape = jnp.broadcast_shapes(qe.shape, ke.shape)
    ok = jnp.broadcast_to(jnp.asarray(True), shape)
    if causal:
        ok = ok & (ke <= qe)
    if window is not None:
        ok = ok & (ke > qe - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias):
    """Grouped-GQA attention without materialising repeated KV heads.

    q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd); bias: (Sq,Skv) additive fp32, or
    (B,Sq,Skv) for per-row masks (batched serving decode).
    The einsum carries a (kv-group, repeat) split of the query heads, so the
    KV tensors are contracted directly — no (B,S,KV,rep,hd) broadcast copy
    (which GSPMD could not reshard efficiently for head_dim-sharded caches).
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    qg = q.reshape(b, sq, kv, rep, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
    bias = bias[:, None, None] if bias.ndim == 3 else bias[None, None, None]
    scores = scores * (hd**-0.5) + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return out.reshape(b, sq, h, hd)


def multi_head_attention(
    q,
    k,
    v,
    *,
    q_positions,
    k_positions,
    causal: bool = True,
    window: Optional[int] = None,
):
    """Blocked-or-naive masked attention.  q: (B,S,H,hd); k,v: (B,S,KV,hd).

    Batched (B, S) positions take the naive path only — serving decode is
    one query token per row, so the score tile is always small.
    """
    sq, skv = q.shape[1], k.shape[1]
    if jnp.ndim(q_positions) == 2:
        bias = _mask_bias(q_positions, k_positions, causal, window)
        return _sdpa(q, k, v, bias)
    if sq * skv <= _MAX_NAIVE_SCORES or sq < 2:
        bias = _mask_bias(q_positions, k_positions, causal, window)
        return _sdpa(q, k, v, bias)
    # q-blocked path: python loop keeps cost_analysis exact (no scan).
    block = max(1, _MAX_NAIVE_SCORES // skv)
    block = min(block, sq)
    outs = []
    for start in range(0, sq, block):
        stop = min(start + block, sq)
        bias = _mask_bias(q_positions[start:stop], k_positions, causal, window)
        outs.append(_sdpa(q[:, start:stop], k, v, bias))
    return jnp.concatenate(outs, axis=1)


def attention_apply(
    params,
    cfg,
    x,
    positions,
    *,
    causal: bool = True,
    cache: Optional[dict] = None,
    peft: Optional[dict] = None,
    lora_scale: float = 1.0,
):
    """Self-attention over ``x`` (B, S, d).

    ``cache``: ``{"k": (B, S_max, kv, hd), "v": ..., "pos": ()}``; when given,
    S is the number of new tokens (1 for decode) written at ``cache["pos"]``
    and attention runs against the whole cache.  Returns (out, new_cache).
    """
    peft = peft or {}
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads

    q = apply_linear(params["wq"], x, peft.get("q"), lora_scale).reshape(b, s, h, hd)
    k = apply_linear(params["wk"], x, peft.get("k"), lora_scale).reshape(b, s, kvh, hd)
    v = apply_linear(params["wv"], x, peft.get("v"), lora_scale).reshape(b, s, kvh, hd)

    if cfg.qk_norm:
        q = apply_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(params["k_norm"], k, cfg.norm_eps)

    q = apply_rotary(q, positions, cfg.rope_theta)
    k = apply_rotary(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        # Ring-buffer cache: for sliding-window archs the cache holds only
        # ``window`` slots and writes wrap modulo the cache length.  For
        # global-attention archs cache_len == max_len and the modulo is a
        # no-op.  Multi-token writes (prefill) assume no wrap within the
        # write (pos + s <= cache_len).
        pos = cache["pos"]
        cache_len = cache["k"].shape[1]
        if jnp.ndim(pos) == 1:
            # Batched serving cache: one write position per row, because
            # continuous batching runs rows at different sequence depths.
            # Token-level admission keeps this to one new token per row.
            if s != 1:
                raise ValueError(
                    "batched KV cache (per-row positions) decodes one token "
                    f"per row per step, got S={s}"
                )
            rows = jnp.arange(b)
            write_pos = pos % cache_len
            ck = cache["k"].at[rows, write_pos].set(k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, write_pos].set(v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv, "pos": pos + s}
            k_full, v_full = ck.astype(x.dtype), cv.astype(x.dtype)
            last_pos = pos + s - 1  # (B,)
            slots = jnp.arange(cache_len)
            k_positions = last_pos[:, None] - jnp.mod(
                last_pos[:, None] - slots[None, :], cache_len
            )
            # Negative = slot not yet written *by this request*: a recycled
            # row still holds the previous tenant's K/V in the ring, and the
            # position mask keeps it inert without a cache clear.
            k_positions = jnp.where(
                k_positions < 0, jnp.iinfo(jnp.int32).max, k_positions
            )
            out = multi_head_attention(
                q,
                k_full,
                v_full,
                q_positions=positions,
                k_positions=k_positions,
                causal=True,
                window=cfg.sliding_window,
            )
            out = out.reshape(b, s, h * hd)
            out = apply_linear(params["wo"], out, peft.get("o"), lora_scale)
            return out, new_cache
        if s >= cache_len:
            # Prefill longer than the ring (SWA window): attention runs over
            # the full in-sequence K/V (early queries need keys the ring
            # discards); the ring then keeps only the last cache_len tokens,
            # rolled so absolute position p lands in slot p % cache_len.
            shift = (s % cache_len) if s > cache_len else 0
            ck = jnp.roll(k[:, -cache_len:].astype(cache["k"].dtype), shift, axis=1)
            cv = jnp.roll(v[:, -cache_len:].astype(cache["v"].dtype), shift, axis=1)
            new_cache = {"k": ck, "v": cv, "pos": pos + s}
            out = multi_head_attention(
                q, k, v,
                q_positions=positions,
                k_positions=positions,
                causal=True,
                window=cfg.sliding_window,
            )
            out = out.reshape(b, s, h * hd)
            out = apply_linear(params["wo"], out, peft.get("o"), lora_scale)
            return out, new_cache
        else:
            write_pos = pos % cache_len
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, write_pos, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, write_pos, 0, 0)
            )
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        k_full, v_full = ck.astype(x.dtype), cv.astype(x.dtype)
        # absolute position held by ring slot i: the unique p <= last_pos
        # with p == i (mod cache_len) and p > last_pos - cache_len.
        last_pos = pos + s - 1
        slots = jnp.arange(cache_len)
        k_positions = last_pos - jnp.mod(last_pos - slots, cache_len)
        # slots never written (cold start) sit at negative positions only when
        # last_pos < cache_len; causality masks them since q >= 0 > p is false
        # -- mask them explicitly instead:
        k_positions = jnp.where(k_positions < 0, jnp.iinfo(jnp.int32).max, k_positions)
        out = multi_head_attention(
            q,
            k_full,
            v_full,
            q_positions=positions,
            k_positions=k_positions,
            causal=True,
            window=cfg.sliding_window,
        )
    else:
        out = multi_head_attention(
            q,
            k,
            v,
            q_positions=positions,
            k_positions=positions,
            causal=causal,
            window=cfg.sliding_window,
        )

    out = out.reshape(b, s, h * hd)
    out = apply_linear(params["wo"], out, peft.get("o"), lora_scale)
    return out, new_cache


def init_cross_attention(key, cfg):
    """Cross-attention (whisper decoder): q from decoder, kv from encoder."""
    return init_attention(key, cfg)


def cross_attention_apply(
    params,
    cfg,
    x,
    enc_kv,
    *,
    peft: Optional[dict] = None,
    lora_scale: float = 1.0,
):
    """``enc_kv``: precomputed {"k","v"} (B, S_enc, kv, hd) from encoder out."""
    peft = peft or {}
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = apply_linear(params["wq"], x, peft.get("q"), lora_scale).reshape(
        b, s, cfg.num_heads, hd
    )
    out = multi_head_attention(
        q,
        enc_kv["k"].astype(x.dtype),
        enc_kv["v"].astype(x.dtype),
        q_positions=jnp.arange(s),
        k_positions=jnp.arange(enc_kv["k"].shape[1]),
        causal=False,
        window=None,
    )
    out = out.reshape(b, s, cfg.num_heads * hd)
    return apply_linear(params["wo"], out, peft.get("o"), lora_scale)


def encode_cross_kv(params, cfg, enc_out):
    """Precompute encoder K/V once per sequence (whisper serving hot path)."""
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = apply_linear(params["wk"], enc_out).reshape(b, s, cfg.num_kv_heads, hd)
    v = apply_linear(params["wv"], enc_out).reshape(b, s, cfg.num_kv_heads, hd)
    return {"k": k, "v": v}
