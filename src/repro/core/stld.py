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


# what the kept block keeps across the gate: the outputs of its weight
# products (matmuls with no batch dimension), not the attention core's
# batched einsums, which the backward recomputes with the elementwise work
_SAVE_PRODUCTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def _kept_block(block_fn):
    """``kept(h, frozen, trained) -> ((h', aux), saved)``: the block's
    output and its pullback's residuals less the block's own inputs, which
    the backward has already; ``spec`` (filled in when ``kept`` is traced)
    rebuilds the pullback from the inputs and ``saved``."""
    spec = {}

    def kept(h, frozen, trained):
        # no optimization barrier around the recomputation's inputs: it runs
        # in its own backward ``cond``, with nothing to be merged with, and a
        # barrier would make the backward copy every weight and saved slice
        block = jax.checkpoint(
            lambda hh, tt: block_fn(hh, frozen, tt), policy=_SAVE_PRODUCTS, prevent_cse=False
        )
        out, pullback = jax.vjp(block, h, trained)
        inputs = {id(x): i for i, x in enumerate(jax.tree.leaves((h, frozen, trained)))}
        leaves, spec["tree"] = jax.tree.flatten(pullback)
        spec["slots"], saved = [], []
        for x in leaves:
            if id(x) in inputs:
                spec["slots"].append(("input", inputs[id(x)]))
            elif isinstance(x, jax.Array):
                spec["slots"].append(("saved", len(saved)))
                saved.append(x)
            else:
                spec["slots"].append(("static", x))
        return out, saved

    def pullback(h, frozen, trained, saved):
        inputs = jax.tree.leaves((h, frozen, trained))
        pick = {"input": inputs.__getitem__, "saved": saved.__getitem__, "static": lambda x: x}
        return jax.tree.unflatten(spec["tree"], [pick[k](v) for k, v in spec["slots"]])

    return kept, pullback


def saved_bytes(block_fn, h, frozen, trained) -> int:
    """Bytes :func:`scan_remat` keeps across one gate beyond the layer's
    input (the outputs of the block's weight products), reckoned from shapes
    (``jax.ShapeDtypeStruct`` or arrays; nothing runs)."""
    kept, _ = _kept_block(block_fn)
    _, saved = jax.eval_shape(kept, h, frozen, trained)
    return sum(x.size * x.dtype.itemsize for x in saved)


def scan_remat(block_fn: Callable, drops, h, frozen, shared, trained):
    """The layer loop of :func:`gate`-ed layers for training, with the kept
    layers' products saved in place across the gates.

    One ``lax.scan`` runs over groups of ``len(drops)`` layers; each layer
    runs ``block_fn(h, (frozen_l, shared), trained_l) -> (h', aux)``.
    ``drops``, ``frozen`` and ``trained`` are per-slot tuples of trees with
    a leading group axis (slot ``s`` holds layers ``s, s + period, ...``);
    ``shared`` (the positions) is every layer's.  Returns ``(h, aux summed
    over layers)``.

    The forward scan carries, per slot, one buffer per saved array with a
    leading group axis: each layer's input and the outputs of its weight
    products (q, k, v, o, gate, up and the LoRA projections), what its VJP
    reads.  Each gate is one ``cond`` that takes the buffers as operands:
    the kept branch runs the block and writes its input and products into
    the group's slot in place; the dropped branch passes ``h`` and the
    buffers through, writing nothing.  The backward scan runs the groups in
    reverse, each gate one ``cond``: the kept branch reads its slot and
    replays the block's VJP from it, recomputing only elementwise work and
    the attention core, with no weight product of the forward; the dropped
    branch passes the cotangent of ``h`` through.  A dropped layer so costs
    no matmul and no residual traffic either way.

    No weight leaves a ``cond``: the layer's weights and adapters reach
    the backward as the scan's slices, and what is computed from the
    weights alone (their casts) is recomputed there.  Residuals returned by
    a ``cond`` would be copied into the scan's stacked outputs, and a
    ``cond`` around a checkpointed block would stack a copy of every
    layer's weights.  ``frozen`` and ``shared`` take no gradient, and
    differentiating them raises; ``trained`` (the adapters) does.
    """
    period = len(drops)
    # one per slot: each traces its own slot's layer structure
    blocks = [_kept_block(block_fn) for _ in range(period)]

    def run(drops, h, frozen, shared, trained):
        def body(h, xs):
            d, fz, tr = xs
            auxs = []
            for s in range(period):
                step = lambda hh, cc, fz=fz[s], tr=tr[s]: (*block_fn(hh, (fz, shared), tr), cc)
                h, aux, _ = gate(step, d[s], h)
                auxs.append(aux)
            return h, sum(auxs)

        h, auxs = jax.lax.scan(body, h, (drops, frozen, trained))
        return h, jnp.sum(auxs)

    def fwd(drops, h, frozen, shared, trained):
        if any(p.perturbed for p in jax.tree.leaves((frozen, shared))):
            raise ValueError("the frozen layer weights take no gradient through scan_remat")
        drops, h, frozen, shared, trained = jax.tree.map(
            lambda p: p.value, (drops, h, frozen, shared, trained)
        )
        n_groups = drops[0].shape[0]
        layer = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        shapes = [
            jax.eval_shape(kept, h, (layer(frozen[s]), shared), layer(trained[s]))
            for s, (kept, _) in enumerate(blocks)
        ]
        bufs = tuple(
            [jax.lax.empty((n_groups,) + x.shape, x.dtype) for x in [h, *saved]]
            for _, saved in shapes
        )

        def body(carry, xs):
            h, bufs = carry
            g, d, fz, tr = xs
            bufs, auxs = list(bufs), []
            for s, (kept, _) in enumerate(blocks):
                (_, aux), _ = shapes[s]

                def kept_branch(h, fz, shared, tr, buf, g, kept=kept):
                    (h_new, aux), saved = kept(h, (fz, shared), tr)
                    buf = [jax.lax.dynamic_update_index_in_dim(b, x, g, 0)
                           for b, x in zip(buf, [h, *saved])]
                    return h_new, aux, buf

                def dropped_branch(h, fz, shared, tr, buf, g, aux=aux):
                    return h, jnp.zeros(aux.shape, aux.dtype), buf

                # the layer's inputs are operands, so that the kept branch sees
                # them as its own and leaves them out of what it saves
                h, aux, bufs[s] = jax.lax.cond(
                    d[s], dropped_branch, kept_branch, h, fz[s], shared, tr[s], bufs[s], g
                )
                auxs.append(aux)
            return (h, tuple(bufs)), sum(auxs)

        xs = (jnp.arange(n_groups), drops, frozen, trained)
        (h, bufs), auxs = jax.lax.scan(body, (h, bufs), xs)
        return (h, jnp.sum(auxs)), (drops, frozen, shared, trained, bufs)

    def bwd(res, cts):
        drops, frozen, shared, trained, bufs = res
        dh, daux = jax.tree.map(
            lambda c: jnp.zeros(c.aval.shape, c.aval.dtype) if isinstance(c, SymbolicZero) else c,
            cts,
            is_leaf=lambda c: isinstance(c, SymbolicZero),
        )

        def body(dh, xs):
            g, d, fz, tr = xs
            dtr = [None] * period
            for s in reversed(range(period)):
                _, pullback = blocks[s]

                def kept_ct(dh, fz, shared, tr, buf, g, pullback=pullback):
                    h, *saved = [jax.lax.dynamic_index_in_dim(b, g, 0, keepdims=False) for b in buf]
                    return pullback(h, (fz, shared), tr, saved)((dh, daux))

                def dropped_ct(dh, fz, shared, tr, buf, g):
                    return dh, jax.tree.map(jnp.zeros_like, tr)

                dh, dtr[s] = jax.lax.cond(
                    d[s], dropped_ct, kept_ct, dh, fz[s], shared, tr[s], bufs[s], g
                )
            return dh, tuple(dtr)

        xs = (jnp.arange(drops[0].shape[0]), drops, frozen, trained)
        dh, dtrained = jax.lax.scan(body, dh, xs, reverse=True)
        return None, dh, None, None, dtrained

    gated = jax.custom_vjp(run)
    gated.defvjp(fwd, bwd, symbolic_zeros=True)
    return gated(tuple(drops), h, tuple(frozen), shared, tuple(trained))
