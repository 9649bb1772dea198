"""Plain reference of one DropPEFT client round and the PTLS aggregation.

A client starts from the round's LoRA tree and a fresh AdamW state, and
takes one step per local batch: draw the layer-dropout gates, compute the
masked next-token loss and its gradient with respect to the LoRA factors,
record each layer's gradient norm for the importance of paper Eq. 6, clip by
the global norm and apply AdamW at the step's learning rate.  The server then
averages each layer over the clients that share it (paper Fig. 8); a layer
no client shares keeps its previous value.

Dropped layers are skipped (``lax.cond``); there is no vmap over clients and
no select.  The gradient is taken one layer at a time
(``model.loss_and_grad``): each layer's forward is recomputed from its kept
input, so the model's gradient fits on the chip beside its float32 weights.  The gate draw, rate shape and learning-rate
schedule follow the paper's formulas as the configurations state them, with
the same ``jax.random`` calls, so the gates are the ones the program drew
from the same key.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import model as ref


def layer_rates(distribution: str, mean_rate, num_layers: int):
    """Per-layer dropout rates: the shape scaled to ``mean_rate``, clipped."""
    ell = jnp.arange(1, num_layers + 1, dtype=jnp.float32)
    if distribution == "uniform":
        base = jnp.ones((num_layers,), jnp.float32)
    elif distribution == "incremental":
        base = ell / (num_layers + 1)
    elif distribution == "decay":
        base = 1.0 - ell / (num_layers + 1)
    else:
        raise ValueError(f"unsupported rate distribution {distribution!r}")
    return jnp.clip(base / jnp.mean(base) * mean_rate, 0.0, 0.95)


def sample_drops(key, rates, min_active: int):
    """Bernoulli gates (True = dropped), with at least ``min_active`` kept:
    the dropped layers of lowest rate are brought back first."""
    drops = jax.random.uniform(key, rates.shape) < rates
    need = jnp.maximum(min_active - jnp.sum(~drops), 0)
    rank_of = jnp.argsort(jnp.argsort(jnp.where(drops, rates, jnp.inf)))
    return drops & ~(drops & (rank_of < need))


def learning_rate(train: dict, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(train["warmup_steps"], 1))
    frac = jnp.clip(
        (step - train["warmup_steps"]) / max(train["total_steps"] - train["warmup_steps"], 1),
        0.0,
        1.0,
    )
    kind = train["schedule"]
    if kind == "cosine":
        decay = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    elif kind == "linear":
        decay = 1.0 - frac
    elif kind == "constant":
        decay = 1.0
    else:
        raise ValueError(f"unknown schedule {kind!r}")
    return train["learning_rate"] * warm * decay


def _layer_norms(tree):
    """(L,) norm of each layer's part of a stacked tree."""
    return jnp.sqrt(sum(jnp.sum(x * x, axis=tuple(range(1, x.ndim))) for x in jax.tree.leaves(tree)))


def make_client_round(s: dict, cfg: dict, mode: str = "highest"):
    """The jitted client round for sizes ``s`` and the cell's ``cfg``
    (``train``, ``stld`` and ``lora_scale``), in arithmetic ``mode``."""
    return jax.jit(partial(_client_round, s=s, cfg=cfg, mode=mode))


def _client_round(base, lora0, tokens, targets, mask, mean_rate, key, step0, *, s, cfg, mode):
    """One client's local round.  ``tokens``/``targets``/``mask`` are
    (steps, B, S).  Returns the trained LoRA tree, the mean loss over the
    steps, the per-layer importance and, per leaf and layer, the largest
    gradient norm any step saw (``(L,)`` per leaf)."""
    train, stld = cfg["train"], cfg["stld"]
    rates = layer_rates(stld["distribution"], mean_rate, s["L"])
    if not stld["enabled"]:
        rates = jnp.zeros_like(rates)
    lora_scale = cfg["lora_scale"]
    zeros = jax.tree.map(jnp.zeros_like, lora0)

    def step(carry, xs):
        p, m, v, count, g_sum, g_cnt, g_max, rng, t = carry
        tok, tgt, msk = xs
        rng, kd = jax.random.split(rng)
        drops = sample_drops(kd, rates, stld["min_active_layers"])
        loss, g = ref.loss_and_grad(base, s, tok, tgt, msk, lora=p, lora_scale=lora_scale, drops=drops, mode=mode)
        active = 1.0 - drops.astype(jnp.float32)
        g_sum = g_sum + _layer_norms(g) * active
        g_cnt = g_cnt + active
        g_max = jax.tree.map(
            lambda a, x: jnp.maximum(a, jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))),
            g_max, g,
        )
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, train["grad_clip"] / jnp.maximum(gnorm, 1e-9)), g)
        count = count + 1
        b1c = 1.0 - train["beta1"] ** count
        b2c = 1.0 - train["beta2"] ** count
        lr = learning_rate(train, t)
        m = jax.tree.map(lambda m_, g_: train["beta1"] * m_ + (1 - train["beta1"]) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: train["beta2"] * v_ + (1 - train["beta2"]) * g_ * g_, v, g)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * ((m_ / b1c) / (jnp.sqrt(v_ / b2c) + train["eps"]) + train["weight_decay"] * p_),
            p, m, v,
        )
        return (p, m, v, count, g_sum, g_cnt, g_max, rng, t + 1), loss

    L = s["L"]
    carry = (
        lora0, zeros, zeros, jnp.zeros((), jnp.float32),
        jnp.zeros((L,), jnp.float32), jnp.zeros((L,), jnp.float32),
        jax.tree.map(lambda x: jnp.zeros((L,), jnp.float32), lora0),
        key, jnp.asarray(step0, jnp.float32),
    )
    (p, _, _, _, g_sum, g_cnt, g_max, _, _), losses = jax.lax.scan(step, carry, (tokens, targets, mask))
    return p, jnp.mean(losses), g_sum / jnp.maximum(g_cnt, 1.0), g_max


def shared_masks(importances: np.ndarray, share: int) -> np.ndarray:
    """(N, L) True for the ``share`` layers of lowest importance per client
    (ties go to the lower layer index)."""
    out = np.zeros(importances.shape, bool)
    for n, imp in enumerate(importances):
        out[n, np.argsort(imp, kind="stable")[:share]] = True
    return out


def aggregate(client_trees: list, masks: np.ndarray, prev):
    """Per layer, the mean over the clients that share it; a layer no
    client shares keeps ``prev``."""
    m = masks.astype(np.float64)
    count = m.sum(axis=0)

    def one(prev_leaf, *leaves):
        stack = np.stack([np.asarray(x, np.float64) for x in leaves])
        w = m.reshape(m.shape + (1,) * (stack.ndim - 2))
        mean = (stack * w).sum(axis=0) / np.maximum(count, 1).reshape((-1,) + (1,) * (stack.ndim - 2))
        keep = (count > 0).reshape((-1,) + (1,) * (stack.ndim - 2))
        return np.where(keep, mean, np.asarray(prev_leaf, np.float64))

    return jax.tree.map(one, prev, *client_trees)
