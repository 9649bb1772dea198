"""Blocked online-softmax (flash) attention — causal / sliding-window, GQA.

TPU adaptation: the grid is (batch*heads, q_blocks, kv_blocks) with the kv
dimension innermost; TPU grids execute sequentially over the last axis, so
the running (m, l, acc) statistics live in VMEM scratch and are carried
across kv iterations without HBM traffic.  Block shapes are MXU-aligned
(block_q x head_dim and block_k x head_dim tiles, head_dim padded to 128 by
the wrapper when needed).  Blocks strictly above the causal diagonal (or
outside the sliding window) are skipped with ``pl.when`` — no MXU work is
issued for them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
    causal: bool,
    window: int,
    seq_len: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    q_start = iq * block_q
    k_start = ik * block_k

    @pl.when(ik == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # block-level relevance: skip fully-masked blocks
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1
    if window > 0:
        # newest query in the block attends back `window`; if the whole kv
        # block is older than that, skip.
        relevant = jnp.logical_and(relevant, k_start + block_k > q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]  # (block_q, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_scratch[...]
        o_ref[0] = (acc_scratch[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret=None,
):
    """q: (B, H, S, D); k, v: (B, H, S, D) (GQA repeat done by the wrapper).

    Returns (B, H, S, D).
    """
    if interpret is None:
        from repro.kernels.ops import is_cpu_backend

        interpret = is_cpu_backend()
    b, h, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    s_pad = -(-s // max(block_q, block_k)) * max(block_q, block_k)
    if s_pad != s:
        pad = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        q, k, v = pad(q), pad(k), pad(v)
    nq = s_pad // block_q
    nk = s_pad // block_k

    kernel = functools.partial(
        _attn_kernel,
        scale=d**-0.5,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
        causal=causal,
        window=window or 0,
        seq_len=s,
    )
    qf = q.reshape(b * h, s_pad, d)
    kf = k.reshape(b * h, s_pad, d)
    vf = v.reshape(b * h, s_pad, d)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s_pad, d)[:, :, :s]
