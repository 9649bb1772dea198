"""RWKV6 chunked WKV recurrence kernel.

TPU adaptation of the (sequential, SM-local) CUDA WKV kernel: the per-head
state S (K x V) stays resident in VMEM scratch while the grid walks the
sequence chunk-by-chunk (TPU grids are sequential over the last axis).
Within a chunk everything is MXU matmuls via the bounded log-decay division
trick (per-step log decay clamped to [-DECAY_CLAMP, 0), see
``repro.nn.rwkv``); across chunks only the (K, V) state carries — no
(B, S, K, V) tensor ever exists in HBM, which is the whole point of the
kernel (the XLA fallback materialises per-chunk states).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 16


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scratch, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scratch[...] = jnp.zeros_like(state_scratch)

    r = r_ref[0].astype(jnp.float32)    # (C, K)
    kk = k_ref[0].astype(jnp.float32)   # (C, K)
    v = v_ref[0].astype(jnp.float32)    # (C, V)
    lw = lw_ref[0].astype(jnp.float32)  # (C, K), < 0
    u = u_ref[0].astype(jnp.float32)    # (1, K) bonus

    lcum = jnp.cumsum(lw, axis=0)       # inclusive within-chunk decay prefix
    lprev = lcum - lw                   # exclusive
    ltot = lcum[-1:]                    # (1, K)

    q_ = r * jnp.exp(lprev)             # bounded
    kappa = kk * jnp.exp(-lcum)         # bounded by e^{C*clamp}
    kappa_end = kk * jnp.exp(ltot - lcum)

    amat = jax.lax.dot_general(
        q_, kappa, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, C)
    ii = jax.lax.broadcasted_iota(jnp.int32, amat.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, amat.shape, 1)
    amat = jnp.where(jj < ii, amat, 0.0)  # strictly lower triangular
    diag = jnp.sum(r * u * kk, axis=-1, keepdims=True)  # (C, 1) bonus term

    s_in = state_scratch[...]  # (K, V)
    intra = jax.lax.dot(amat, v, preferred_element_type=jnp.float32)
    inter = jax.lax.dot(q_, s_in, preferred_element_type=jnp.float32)
    o_ref[0] = (intra + diag * v + inter).astype(o_ref.dtype)

    state_scratch[...] = jnp.exp(ltot).T * s_in + jax.lax.dot_general(
        kappa_end, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def wkv6_pallas(r, k, v, logw, u, *, chunk: int = DEFAULT_CHUNK, interpret=None):
    """r,k,v,logw: (B, S, H, K); u: (H, K).  Returns out (B, S, H, K).

    logw must already be clamped to [-DECAY_CLAMP, 0) by the caller
    (``repro.nn.rwkv`` does this); the division trick inside the kernel is
    only numerically safe under that contract.
    """
    if interpret is None:
        from repro.kernels.ops import is_cpu_backend

        interpret = is_cpu_backend()
    b, s, h, kd = r.shape
    s_pad = -(-s // chunk) * chunk
    if s_pad != s:
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        r, k, v = zpad(r), zpad(k), zpad(v)
        logw = jnp.pad(logw, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    nc = s_pad // chunk
    # (B*H, S, K) layout: head-major so each grid row owns one head's stream
    tr = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s_pad, kd)
    rf, kf, vf, lwf = tr(r), tr(k), tr(v), tr(logw)
    uf = jnp.broadcast_to(u[None], (b, h, kd)).reshape(b * h, 1, kd)

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, kd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, kd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, kd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, kd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, kd), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, kd), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, kd), r.dtype),
        scratch_shapes=[pltpu.VMEM((kd, kd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf)
    return out.reshape(b, h, s_pad, kd).transpose(0, 2, 1, 3)[:, :s]
