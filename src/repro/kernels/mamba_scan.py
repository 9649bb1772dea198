"""Mamba selective-scan kernel.

TPU adaptation of the CUDA selective-scan: the hidden state h (d_block x N)
is VMEM-resident while the grid walks (batch, d_inner blocks, time chunks);
discretisation (a = exp(dt*A), b = dt*B*x) happens inside the kernel so the
(B, S, D, N) tensors the XLA associative-scan path materialises never touch
HBM.  The inner time loop is a ``fori_loop`` over the chunk — elementwise
VPU work on (d_block, N) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
DEFAULT_D_BLOCK = 256


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, d_ref, o_ref, h_scratch, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    dt = dt_ref[0].astype(jnp.float32)   # (C, bd)
    x = x_ref[0].astype(jnp.float32)     # (C, bd)
    bmat = b_ref[0].astype(jnp.float32)  # (C, N)
    cmat = c_ref[0].astype(jnp.float32)  # (C, N)
    a = a_ref[...].astype(jnp.float32)   # (bd, N)
    dvec = d_ref[...].astype(jnp.float32)  # (1, bd)

    def step(t, carry):
        h, ys = carry
        dt_t = dt[t][:, None]             # (bd, 1)
        a_t = jnp.exp(dt_t * a)           # (bd, N)
        b_t = (dt_t * x[t][:, None]) * bmat[t][None, :]
        h = a_t * h + b_t
        y_t = jnp.sum(h * cmat[t][None, :], axis=-1) + dvec[0] * x[t]
        ys = jax.lax.dynamic_update_slice(ys, y_t[None, :], (t, 0))
        return h, ys

    h0 = h_scratch[...]
    ys0 = jnp.zeros_like(o_ref[0], dtype=jnp.float32)
    h_final, ys = jax.lax.fori_loop(0, chunk, step, (h0, ys0))
    h_scratch[...] = h_final
    o_ref[0] = ys.astype(o_ref.dtype)


def mamba_scan_pallas(
    dt,
    x,
    bmat,
    cmat,
    a,
    dvec,
    *,
    chunk: int = DEFAULT_CHUNK,
    d_block: int = DEFAULT_D_BLOCK,
    interpret=None,
):
    """Selective scan.

    dt, x: (B, S, D); bmat, cmat: (B, S, N); a: (D, N) (negative); dvec: (D,).
    Returns y (B, S, D) = C_t . h_t + D*x with h_t = exp(dt A) h_{t-1} + dt B x.
    """
    if interpret is None:
        from repro.kernels.ops import is_cpu_backend

        interpret = is_cpu_backend()
    b, s, d = x.shape
    n = bmat.shape[-1]
    d_block = min(d_block, d)
    if d % d_block:
        raise ValueError(f"d_inner {d} must be divisible by d_block {d_block}")
    chunk = min(chunk, s)
    s_pad = -(-s // chunk) * chunk
    if s_pad != s:
        pad3 = lambda t: jnp.pad(t, ((0, 0), (0, s_pad - s), (0, 0)))
        dt, x, bmat, cmat = pad3(dt), pad3(x), pad3(bmat), pad3(cmat)
    nd = d // d_block
    nc = s_pad // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda ib, idb, ic: (ib, ic, idb)),
            pl.BlockSpec((1, chunk, d_block), lambda ib, idb, ic: (ib, ic, idb)),
            pl.BlockSpec((1, chunk, n), lambda ib, idb, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, idb, ic: (ib, ic, 0)),
            pl.BlockSpec((d_block, n), lambda ib, idb, ic: (idb, 0)),
            pl.BlockSpec((1, d_block), lambda ib, idb, ic: (0, idb)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda ib, idb, ic: (ib, ic, idb)),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((d_block, n), jnp.float32)],
        interpret=interpret,
    )(dt, x, bmat, cmat, a, dvec.reshape(1, d))
    return out[:, :s]
