"""Stochastic Transformer Layer Dropout (STLD) — paper §3.2.

``H_{l+1} = (1 - d_l) · Block_l(H_l) + d_l · H_l``, ``d_l ~ Bernoulli(P_l)``.

Two execution modes (DESIGN.md §2):

* ``cond``   — paper-faithful: a traced ``lax.cond`` per layer.  One compiled
  graph; at runtime XLA executes only the taken branch, so a dropped layer
  costs neither forward nor backward compute.  Per-batch dynamic, exactly the
  paper's semantics.
* ``gather`` — TPU-native (beyond paper): a *static* active-layer count
  ``k = round(L · (1 - mean_rate))`` with *traced* active indices.  Stacked
  layer params are gathered (``jnp.take``) into a shorter stack and scanned;
  the compiled graph itself has ``k/L`` of the FLOPs and activation footprint.
  Gradients scatter back through the gather, so dropped layers receive exact
  zero updates — numerically identical in expectation to ``cond`` when the
  index distribution matches.

``sample_drops`` draws the paper's independent Bernoulli gates (with a
guaranteed minimum number of active layers); ``sample_active_indices`` draws a
fixed-size active set with inclusion probabilities proportional to
``1 - P_l`` (Gumbel top-k weighted sampling without replacement), the
gather-mode analogue.

Key discipline
--------------
Every sampler here consumes its ``key`` argument *whole* (exactly one
``jax.random`` draw per call) and never splits or folds internally.  Callers
own the stream: the client step does ``rng, kd = jax.random.split(rng)`` per
local step and passes ``kd`` to exactly one sampler, and the cohort engine
fans out one ``jax.random.split(key, n + 1)`` per round so no two devices —
and no two rounds — ever share a key path (regression-tested in
``tests/test_key_discipline.py``).  Passing the same key to two samplers
would correlate their gates; the JXH001 lint rule flags that pattern.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero


def expected_active_layers(rates) -> jnp.ndarray:
    """E[L-tilde] = sum_l (1 - P_l)   (paper Eq. 4)."""
    return jnp.sum(1.0 - rates)


def _force_min_active(drops, rates, min_active: int):
    """Enforce the active-layer floor: if fewer than ``min_active`` layers
    survive, force-activate the dropped layers with the smallest rates."""
    active = jnp.sum(~drops)
    need = jnp.maximum(min_active - active, 0)
    order = jnp.argsort(jnp.where(drops, rates, jnp.inf))
    rank_of = jnp.argsort(order)
    force = drops & (rank_of < need)
    return drops & ~force


def sample_drops(key, rates, min_active: int = 1):
    """Independent Bernoulli gates d_l (True = dropped), with a floor on the
    number of active layers: if fewer than ``min_active`` layers survive,
    the lowest-rate layers are force-activated."""
    num_layers = rates.shape[0]
    u = jax.random.uniform(key, (num_layers,))
    drops = u < rates
    return _force_min_active(drops, rates, min_active)


def sample_active_indices(key, rates, k: int):
    """Gather-mode: sample k distinct layer indices with probability
    proportional to keep-probability (Gumbel top-k), returned sorted so the
    gathered sub-stack preserves depth order."""
    logits = jnp.log(jnp.clip(1.0 - rates, 1e-6, 1.0))
    g = logits + jax.random.gumbel(key, rates.shape)
    _, idx = jax.lax.top_k(g, k)
    return jnp.sort(idx)


def static_active_count(mean_rate: float, num_layers: int, bucket: int = 1, min_active: int = 1) -> int:
    """Static k for gather mode, rounded up to a bucket to bound recompiles."""
    k = round(num_layers * (1.0 - mean_rate))
    if bucket > 1:
        k = -(-k // bucket) * bucket
    return int(min(num_layers, max(min_active, k)))


def sample_drops_block(key, rates, block_size: int, min_active: int = 1):
    """Structured (LayerDrop-style) variant: contiguous blocks of
    ``block_size`` layers share one Bernoulli gate.  Coarser than the
    paper's per-layer gates but TPU-friendlier in gather mode (gathered
    sub-stacks stay contiguous); used as an ablation."""
    num_layers = rates.shape[0]
    n_blocks = -(-num_layers // block_size)
    # per-block mean rate via one padded reshape-mean (zero-padding keeps
    # block sums exact; divide by the true per-block lengths) instead of a
    # python list of per-slice jnp.mean ops
    padded = jnp.pad(rates, (0, n_blocks * block_size - num_layers))
    counts = jnp.full((n_blocks,), block_size, dtype=rates.dtype).at[-1].set(
        num_layers - (n_blocks - 1) * block_size
    )
    block_rates = padded.reshape(n_blocks, block_size).sum(axis=1) / counts
    block_drops = sample_drops(key, block_rates, min_active=1)
    drops = jnp.repeat(block_drops, block_size)[:num_layers]
    return _force_min_active(drops, rates, min_active)


def gate(block_fn: Callable, drop, h, cache=None):
    """The STLD gate: ``lax.cond(drop, identity, block_fn)``.

    ``block_fn(h, cache) -> (h', aux, cache')``; the identity branch passes
    ``h`` and ``cache`` through with aux = 0, so both branches have identical
    output structure (required by ``lax.cond``) and a skipped layer stores no
    activations for the backward pass — XLA executes only the taken branch.

    ``drop`` must be unbatched: under ``vmap`` a ``cond`` with a batched
    predicate becomes a select that runs the block for every member, so the
    batched cohort maps its clients in turn (``repro.federated.client``).
    """

    def skip_branch(operands):
        h, cache = operands
        return h, jnp.zeros((), dtype=jnp.float32), cache

    def active_branch(operands):
        h, cache = operands
        return block_fn(h, cache)

    return jax.lax.cond(drop, skip_branch, active_branch, (h, cache))


def gate_remat(block_fn: Callable, drop, h, frozen, trained):
    """:func:`gate` for training, rematerialized as one unit.

    ``block_fn(h, frozen, trained) -> (h', aux)``.  The forward runs the gate
    and saves only its inputs.  The backward is one ``cond``: the kept
    branch recomputes the block and takes its VJP in the same branch, the
    dropped branch passes the cotangent of ``h`` through.  A dropped layer
    so costs nothing either way, and no weight or activation crosses a
    branch boundary: ``jax.checkpoint`` around :func:`gate` would split the
    backward into a recompute ``cond`` and a VJP ``cond``, the first writing
    every weight slice and activation the second reads (zeros, when the
    layer is dropped).  ``frozen`` (the layer's weights) takes no gradient,
    and differentiating it raises; ``trained`` (its adapters) does.
    """

    def run(drop, h, frozen, trained):
        h_new, aux, _ = gate(lambda hh, cc: (*block_fn(hh, frozen, trained), cc), drop, h)
        return h_new, aux

    def fwd(drop, h, frozen, trained):
        if any(p.perturbed for p in jax.tree.leaves(frozen)):
            raise ValueError("the frozen layer weights take no gradient through gate_remat")
        res = jax.tree.map(lambda p: p.value, (drop, h, frozen, trained))
        return run(*res), res

    def bwd(res, cts):
        drop, h, frozen, trained = res
        cts = jax.tree.map(
            lambda c: jnp.zeros(c.aval.shape, c.aval.dtype) if isinstance(c, SymbolicZero) else c,
            cts,
            is_leaf=lambda c: isinstance(c, SymbolicZero),
        )

        def kept(cts):
            _, vjp = jax.vjp(lambda hh, tt: block_fn(hh, frozen, tt), h, trained)
            return vjp(cts)

        def dropped(cts):
            return cts[0], jax.tree.map(jnp.zeros_like, trained)

        dh, dtrained = jax.lax.cond(drop, dropped, kept, cts)
        return None, dh, None, dtrained

    gated = jax.custom_vjp(run)
    gated.defvjp(fwd, bwd, symbolic_zeros=True)
    return gated(drop, h, frozen, trained)
