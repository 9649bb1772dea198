"""Serving utilities.

``sharded_decode_attention`` — beyond-paper distributed decode for
``long_500k``-class workloads: the KV cache is sharded along the *sequence*
dimension across the ``data`` mesh axis; each shard computes its partial
attention and the partials merge with a log-sum-exp ``psum`` combine under
``shard_map``.  Per-token decode traffic is O(heads x head_dim) instead of
all-gathering an O(seq) cache.

``generate`` — simple greedy KV-cache generation driver used by examples
and integration tests (single host, any arch via serve_step).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _partial_attention(q, k, v, k_positions, q_position, window):
    """Unnormalised attention over one KV shard.

    q: (B, H, D); k, v: (B, S_shard, KV, D).  Returns (acc (B,H,D), m, l).
    """
    n_rep = q.shape[1] // k.shape[2]
    kk = jnp.repeat(k, n_rep, axis=2)  # (B, S, H, D)
    vv = jnp.repeat(v, n_rep, axis=2)
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kk.astype(jnp.float32))
    scores = scores * (q.shape[-1] ** -0.5)
    ok = k_positions <= q_position
    if window is not None and window > 0:
        ok = ok & (k_positions > q_position - window)
    scores = jnp.where(ok[None, None, :], scores, -1e30)
    m = jnp.max(scores, axis=-1)  # (B, H)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhs,bshd->bhd", p, vv.astype(jnp.float32))
    return acc, m, l


def sharded_decode_attention(mesh, q, k_cache, v_cache, k_positions, q_position, *, window=None, axis: str = "data"):
    """Flash-decode over a sequence-sharded KV cache.

    q: (B, H, D) replicated; k_cache/v_cache: (B, S, KV, D) sharded on S over
    ``axis``; k_positions: (S,) absolute slot positions (sharded alike).
    Returns (B, H, D) attention output, replicated.
    """
    def local(q, k, v, kpos):
        acc, m, l = _partial_attention(q, k, v, kpos, q_position, window)
        # log-sum-exp combine across sequence shards
        m_glob = jax.lax.pmax(m, axis)
        scale = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * scale, axis)
        acc_glob = jax.lax.psum(acc * scale[..., None], axis)
        return (acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]).astype(q.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), P(axis)),
        out_specs=P(),
        check_vma=False,
    )(q, k_cache, v_cache, k_positions)


def generate(
    serve_step,
    params,
    prompt_caches,
    first_token,
    start_pos: int,
    num_tokens: int,
    enc_kvs=None,
    *,
    eos_id=None,
    max_new_tokens=None,
    pad_id: int = 0,
):
    """Greedy generation loop.  Returns (tokens (B, num_tokens), caches).

    Per-sequence stop handling: once a row emits ``eos_id`` or reaches its
    ``max_new_tokens`` budget (scalar or per-row ``(B,)``), that row is
    frozen — subsequent output positions hold ``pad_id`` and the frozen
    row keeps feeding its last live token so cache writes stay inert for
    ranking purposes (the scan still runs ``num_tokens`` steps; rows stop
    independently, the batch shape never changes).  With both ``eos_id``
    and ``max_new_tokens`` unset this is exactly the unconditional loop.
    """
    if eos_id is None and max_new_tokens is None:

        def body(carry, _):
            token, pos, caches = carry
            if enc_kvs is None:
                _, nxt, caches = serve_step(params, token, pos, caches)
            else:
                _, nxt, caches = serve_step(params, token, pos, caches, enc_kvs)
            return (nxt, pos + 1, caches), nxt[:, 0]

        (_, _, caches), toks = jax.lax.scan(
            body, (first_token, jnp.asarray(start_pos, jnp.int32), prompt_caches), None, length=num_tokens
        )
        return toks.swapaxes(0, 1), caches

    batch = first_token.shape[0]
    budget = None
    if max_new_tokens is not None:
        budget = jnp.broadcast_to(jnp.asarray(max_new_tokens, jnp.int32), (batch,))

    def body(carry, step):
        token, pos, caches, done = carry
        if enc_kvs is None:
            _, nxt, caches = serve_step(params, token, pos, caches)
        else:
            _, nxt, caches = serve_step(params, token, pos, caches, enc_kvs)
        emitted = jnp.where(done, jnp.asarray(pad_id, nxt.dtype), nxt[:, 0])
        new_done = done
        if eos_id is not None:
            new_done = new_done | (~done & (nxt[:, 0] == eos_id))
        if budget is not None:
            new_done = new_done | (step + 1 >= budget)
        # frozen rows re-feed their previous token (value is irrelevant —
        # their outputs are masked; keeping shapes fixed avoids recompiles)
        nxt = jnp.where(done[:, None], token, nxt)
        return (nxt, pos + 1, caches, new_done), emitted

    init = (
        first_token,
        jnp.asarray(start_pos, jnp.int32),
        prompt_caches,
        jnp.zeros((batch,), bool),
    )
    (_, _, caches, _), toks = jax.lax.scan(
        body, init, jnp.arange(num_tokens, dtype=jnp.int32)
    )
    return toks.swapaxes(0, 1), caches
