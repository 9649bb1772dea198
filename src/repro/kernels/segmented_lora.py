"""Segmented (gathered) multi-adapter LoRA matmul — the punica/SGMV-style
serving hot path: ``y[i] = x[i] @ W + (x[i] @ A[idx[i]]) @ B[idx[i]]``.

Every row of the batch indexes its own LoRA adapter out of a stacked pool
``a: (n_adapters, K, r_max)`` / ``b: (n_adapters, r_max, N)``, so one kernel
launch serves a whole continuous batch of heterogeneous tenants.  The
adapter row indices arrive as a *scalar-prefetch* operand
(:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`): the
``BlockSpec`` index maps read ``idx[i]`` to DMA exactly the one adapter each
row needs — the pool never streams through VMEM wholesale.

Rank heterogeneity (hetlora cohorts train clients at different ranks) is
served from a single pool: adapters are zero-padded to ``r_max`` and an
in-kernel rank mask zeroes the padded tail of the rank-bottleneck
intermediate.  The mask is load-bearing for slot hot-swap: a recycled pool
slot may still hold the stale tail of a higher-rank adapter, and the mask
keeps it inert without a device round-trip to zero it.

The per-adapter LoRA scaling (alpha / rank, heterogeneous under hetlora) is
**pre-folded into the pooled ``b``** when a slot is written — deliberately
not a kernel operand.  A scalar multiply adjacent to a dot is rewritten
freely by XLA (FMA fusion of ``main + s*side``, hoisting ``dot(s*t, b)`` to
``s*dot(t, b)``), each with different rounding, which breaks the bit-parity
contract between the batched kernel and the per-request reference.  With
the scale folded at swap time the traced program is dots + mask + add only.

The grid is (M rows, N blocks) — decode batches are short (M = batch), so a
one-row query block per adapter gather keeps the indexing exact; K is kept
whole per block like ``lora_matmul``.  Rows travel as ``(M, 1, K)`` /
``(M, 1, N)`` so every block's last two dimensions equal the array's own
(``(1, K)``, ``(1, block_n)``): Mosaic refuses a ``(1, K)`` block of an
``(M, K)`` array for any M > 1, because a block's second-to-last dimension
must be a multiple of 8 or span the whole axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 128


def _segmented_kernel(idx_ref, ranks_ref, x_ref, w_ref, a_ref, b_ref, o_ref):
    i = pl.program_id(0)
    slot = idx_ref[i]
    x = x_ref[0]  # (1, K)
    main = jax.lax.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    t = jax.lax.dot(x, a_ref[0], preferred_element_type=jnp.float32)  # (1, r_max)
    # zero the padded rank tail: 2D iota (TPU requires >= 2D) vs this
    # adapter's true rank — stale values beyond it must not contribute
    rmask = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1) < ranks_ref[slot]
    t = jnp.where(rmask, t, 0.0)
    side = jax.lax.dot(t.astype(x.dtype), b_ref[0], preferred_element_type=jnp.float32)
    o_ref[0] = (main + side).astype(o_ref.dtype)


def segmented_lora_pallas(
    x,
    w,
    a,
    b,
    idx,
    ranks,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret=None,
):
    """x: (M, K); w: (K, N); a: (NA, K, r_max); b: (NA, r_max, N) with the
    per-adapter alpha/rank scale pre-folded in; idx: (M,) int32 adapter id
    per row; ranks: (NA,) int32 true ranks.  Returns (M, N)."""
    if interpret is None:
        from repro.kernels.ops import is_cpu_backend

        interpret = is_cpu_backend()
    m, kdim = x.shape
    n = w.shape[1]
    r_max = a.shape[-1]
    block_n = min(block_n, n)
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        w = jnp.pad(w, ((0, 0), (0, n_pad - n)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, n_pad - n)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((1, 1, kdim), lambda i, j, idx, rk: (i, 0, 0)),
            pl.BlockSpec((kdim, block_n), lambda i, j, idx, rk: (0, j)),
            pl.BlockSpec((1, kdim, r_max), lambda i, j, idx, rk: (idx[i], 0, 0)),
            pl.BlockSpec((1, r_max, block_n), lambda i, j, idx, rk: (idx[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda i, j, idx, rk: (i, 0, j)),
    )
    out = pl.pallas_call(
        _segmented_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, n_pad), x.dtype),
        interpret=interpret,
    )(
        idx.astype(jnp.int32),
        ranks.astype(jnp.int32),
        x[:, None, :],
        w,
        a,
        b,
    )
    return out[:, 0, :n]
