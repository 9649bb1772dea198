"""Flash-decode kernel: single-query attention against a long KV cache.

The serving hot path (decode_32k / long_500k).  TPU adaptation of
flash-decoding: the grid walks (batch*kv_head, kv_blocks) with the kv axis
innermost (sequential on TPU), carrying the online-softmax statistics for
the whole q-head GROUP in VMEM scratch — the GQA group shares its KV block
loads, so HBM traffic is exactly one cache read per step (the roofline
floor for decode, EXPERIMENTS.md §Roofline).

Masking: ``k_positions`` carries each slot's absolute position (ring-buffer
aware), so causal + sliding-window checks work on wrapped caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _decode_kernel(
    q_ref,        # (1, rep, d)     — the kv-head's query group
    k_ref,        # (1, block_k, d)
    v_ref,        # (1, block_k, d)
    kpos_ref,     # (1, block_k)
    o_ref,        # (1, rep, d)
    m_scratch,    # (rep, 1)
    l_scratch,    # (rep, 1)
    acc_scratch,  # (rep, d)
    *,
    scale: float,
    num_kv_blocks: int,
    q_position: int,
    window: int,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q = q_ref[0].astype(jnp.float32)            # (rep, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    kpos = kpos_ref[0]                          # (bk,)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                    # (rep, bk)
    ok = kpos <= q_position
    if window > 0:
        ok = jnp.logical_and(ok, kpos > q_position - window)
    s = jnp.where(ok[None, :], s, NEG_INF)

    m_prev = m_scratch[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scratch[...] = alpha * l_scratch[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scratch[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scratch[...] / jnp.maximum(l_scratch[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_pallas(
    q,
    k_cache,
    v_cache,
    k_positions,
    q_position,
    *,
    window: int | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret=None,
):
    """q: (B, H, D); k_cache/v_cache: (B, S, KV, D); k_positions: (S,) abs
    slot positions; q_position: int.  Returns (B, H, D)."""
    if interpret is None:
        from repro.kernels.ops import is_cpu_backend

        interpret = is_cpu_backend()
    b, h, d = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    rep = h // kv
    block_k = min(block_k, s)
    s_pad = -(-s // block_k) * block_k
    if s_pad != s:
        pad4 = lambda t: jnp.pad(t, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        k_cache, v_cache = pad4(k_cache), pad4(v_cache)
        k_positions = jnp.pad(k_positions, (0, s_pad - s), constant_values=jnp.iinfo(jnp.int32).max)
    nk = s_pad // block_k

    # regroup: (B*KV, rep, d) queries; (B*KV, S, d) caches
    qg = q.reshape(b, kv, rep, d).reshape(b * kv, rep, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * kv, s_pad, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * kv, s_pad, d)
    kp = jnp.broadcast_to(k_positions[None], (b * kv, s_pad)).astype(jnp.int32)

    kernel = functools.partial(
        _decode_kernel,
        scale=d**-0.5,
        num_kv_blocks=nk,
        q_position=int(q_position) if not hasattr(q_position, "dtype") else q_position,
        window=window or 0,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * kv, nk),
        in_specs=[
            pl.BlockSpec((1, rep, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k), lambda bh, ik: (bh, ik)),
        ],
        out_specs=pl.BlockSpec((1, rep, d), lambda bh, ik: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv, rep, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kf, vf, kp)
    return out.reshape(b, kv, rep, d).reshape(b, h, d)
