"""Public jit'd wrappers around the Pallas kernels.

``impl='pallas'`` runs the kernels (interpret mode on CPU, native on TPU);
``impl='xla'`` dispatches to the pure-jnp reference path, since Pallas does
not lower to the XLA CPU backend.

The public functions resolve the backend question (``interpret`` =
running-on-CPU) *outside* the traced region and pass the answer through a
static argument of the inner jitted program.  Querying ``jax.devices()``
during trace would bake the platform into the compiled program without
making it part of the cache key — a stale answer after a backend switch.
"""
from __future__ import annotations

from functools import cache, partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lora_matmul import lora_matmul_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas


@cache
def is_cpu_backend() -> bool:
    """Cached backend query: does Pallas need interpret mode here?

    Safe to cache for the process lifetime — JAX fixes the default backend
    at first use.  The kernels' ``interpret=None`` defaults resolve through
    this, so GPU/TPU runs compile the real kernels while CPU CI keeps the
    interpret path, without baking an uncached env query into traced code.
    """
    return jax.devices()[0].platform == "cpu"


_is_cpu = is_cpu_backend


@partial(
    jax.jit,
    static_argnames=("causal", "window", "impl", "block_q", "block_k", "interpret"),
)
def _flash_attention(q, k, v, *, causal, window, impl, block_q, block_k, interpret):
    h, kv = q.shape[1], k.shape[1]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if impl == "xla":
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window=None,
    impl: str = "pallas",
    block_q: int = 128,
    block_k: int = 128,
):
    """q: (B, H, S, D); k, v: (B, KV, S, D) — GQA broadcast handled here."""
    return _flash_attention(
        q, k, v, causal=causal, window=window, impl=impl,
        block_q=block_q, block_k=block_k, interpret=_is_cpu(),
    )


@partial(jax.jit, static_argnames=("impl", "chunk", "interpret"))
def _wkv6(r, k, v, logw, u, *, impl, chunk, interpret):
    if impl == "xla":
        return ref.wkv6_ref(r, k, v, logw, u)
    return wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=interpret)


def wkv6(r, k, v, logw, u, *, impl: str = "pallas", chunk: int = 16):
    return _wkv6(r, k, v, logw, u, impl=impl, chunk=chunk, interpret=_is_cpu())


@partial(jax.jit, static_argnames=("impl", "chunk", "d_block", "interpret"))
def _mamba_scan(dt, x, bmat, cmat, a, dvec, *, impl, chunk, d_block, interpret):
    if impl == "xla":
        return ref.mamba_scan_ref(dt, x, bmat, cmat, a, dvec)
    d = x.shape[-1]
    d_block = min(d_block, d)
    while d % d_block:
        d_block //= 2
    return mamba_scan_pallas(
        dt, x, bmat, cmat, a, dvec, chunk=chunk, d_block=max(1, d_block),
        interpret=interpret,
    )


def mamba_scan(dt, x, bmat, cmat, a, dvec, *, impl: str = "pallas", chunk: int = 64, d_block: int = 256):
    return _mamba_scan(
        dt, x, bmat, cmat, a, dvec, impl=impl, chunk=chunk, d_block=d_block,
        interpret=_is_cpu(),
    )


@partial(jax.jit, static_argnames=("alpha", "impl", "block_m", "block_n", "interpret"))
def _lora_matmul(x, w, a, b, *, alpha, impl, block_m, block_n, interpret):
    if impl == "xla":
        return ref.lora_matmul_ref(x, w, a, b, alpha=alpha)
    return lora_matmul_pallas(
        x, w, a, b, alpha=alpha, block_m=block_m, block_n=block_n,
        interpret=interpret,
    )


def lora_matmul(x, w, a, b, *, alpha: float = 1.0, impl: str = "pallas", block_m: int = 128, block_n: int = 128):
    return _lora_matmul(
        x, w, a, b, alpha=alpha, impl=impl, block_m=block_m, block_n=block_n,
        interpret=_is_cpu(),
    )


@partial(jax.jit, static_argnames=("impl", "block_n", "interpret"))
def _segmented_lora(x, w, a, b, idx, ranks, *, impl, block_n, interpret):
    if impl == "xla":
        # gather formulation: one batched matmul chain over per-row adapters
        ar = a[idx].astype(x.dtype)          # (M, K, r_max)
        br = b[idx].astype(x.dtype)          # (M, r_max, N)
        t = jnp.einsum("mk,mkr->mr", x.astype(jnp.float32), ar.astype(jnp.float32))
        rmask = jnp.arange(a.shape[-1])[None, :] < ranks[idx][:, None]
        t = jnp.where(rmask, t, 0.0)
        side = jnp.einsum(
            "mr,mrn->mn", t.astype(x.dtype).astype(jnp.float32), br.astype(jnp.float32)
        )
        main = x.astype(jnp.float32) @ w.astype(jnp.float32)
        return (main + side).astype(x.dtype)
    from repro.kernels.segmented_lora import segmented_lora_pallas

    return segmented_lora_pallas(
        x, w, a, b, idx, ranks, block_n=block_n, interpret=interpret
    )


def segmented_lora(x, w, a, b, idx, ranks, *, impl: str = "pallas", block_n: int = 128):
    """Multi-tenant LoRA matmul: row i uses adapter ``idx[i]`` from the
    stacked pool.  x: (M, K); w: (K, N); a: (NA, K, r_max);
    b: (NA, r_max, N) with per-adapter scale pre-folded in; idx: (M,);
    ranks: (NA,)."""
    return _segmented_lora(
        x, w, a, b, idx, ranks, impl=impl, block_n=block_n,
        interpret=_is_cpu(),
    )
