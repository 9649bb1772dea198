"""Decoder-stack assembly with STLD-gated layers.

Layer stacks arrive in either layout (see :mod:`repro.models.stacking`):
**stacked** — one pytree with a leading ``(L, ...)`` layer axis on every
leaf, the native layout for homogeneous stacks — or **list** — one pytree
per layer, kept for heterogeneous stacks (hybrid interleaves) and legacy
callers.

``stack_apply`` runs every stack with one ``lax.scan`` over groups of
``cfg.layer_period`` layers (Jamba's mamba/attn/MoE interleave repeats with
period 8; every other family has period 1).  The body applies a group's
layers in order.  A stacked tree at period 1 is scanned as it is (zero
``jnp.stack`` inside the traced program); a list is stacked at trace time.
STLD gates each layer with a real ``cond`` (``drops``), or, in gather-STLD
(``active_idx``), the scan runs over a ``jnp.take`` of the kept layers.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import stld
from repro.models import stacking
from repro.models.layers import init_layer, init_layer_cache, layer_apply, layer_kind
from repro.nn.initializers import normal_init
from repro.nn.norms import apply_layernorm, apply_rmsnorm, init_layernorm, init_rmsnorm


def _norm_init(cfg, dim):
    return init_layernorm(dim) if cfg.activation == "gelu" else init_rmsnorm(dim)


def _norm_apply(cfg, p, x):
    return apply_layernorm(p, x, cfg.norm_eps) if "bias" in p else apply_rmsnorm(p, x, cfg.norm_eps)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_lm(key, cfg, layout: str = "auto"):
    """Decoder-only LM (also the VLM/MoE/hybrid/ssm backbone).

    ``layout`` picks the layer-stack representation: ``auto`` (default)
    emits the stacked ``(L, ...)`` layout whenever the stack is homogeneous
    and falls back to the per-layer list for heterogeneous stacks;
    ``list``/``stacked`` force a layout (see :mod:`repro.models.stacking`).
    """
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params = {
        "embed": normal_init(k_emb, (cfg.vocab_size, cfg.d_model)),
        "layers": _init_layers(layer_keys, cfg, layout),
        "final_norm": _norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(k_head, (cfg.d_model, cfg.vocab_size))
    return params


def _init_layers(layer_keys, cfg, layout: str):
    """The layer stack.  A homogeneous stack in a stacked layout is drawn
    stacked, each leaf at once over the layer keys, and its values are the
    per-layer draw's to the bit.  Drawing layer by layer and then stacking
    holds both copies at once, which at h2o-danube-1.8b's widths does not
    fit one 16 GB chip."""
    kinds = {(layer_kind(cfg, l), cfg.is_moe_layer(l)) for l in range(cfg.num_layers)}
    if layout != "list" and len(kinds) == 1:
        return jax.vmap(lambda k: init_layer(k, cfg, 0))(layer_keys)
    layers = [init_layer(layer_keys[l], cfg, l) for l in range(cfg.num_layers)]
    return stacking.maybe_stack(layers, layout)


def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, layout: str = "list"):
    """Per-layer decode caches.  ``layout='stacked'`` returns one pytree
    with a leading ``(L, ...)`` axis per leaf (homogeneous stacks only) —
    O(k) jit arguments instead of O(L·k), which is what keeps the serving
    step inside the jaxpr leaf budget."""
    caches = [init_layer_cache(cfg, l, batch, max_len, dtype) for l in range(cfg.num_layers)]
    if layout == "stacked":
        return stacking.stack_params(caches)
    if layout != "list":
        raise ValueError(f"unknown cache layout {layout!r}")
    return caches


# --------------------------------------------------------------------------
# stack execution
# --------------------------------------------------------------------------
def _layer_block(cfg, causal: bool, lora_scale: float):
    """``block(h, (params, enc_kv, positions), peft, cache=None)``: one
    layer.  Every array the block reads is an argument, so that the remat
    gate's backward closes over none."""

    def block(hh, frozen, pf, cc=None):
        p, enc_kv, pos = frozen
        return layer_apply(
            p,
            cfg,
            hh,
            positions=pos,
            causal=causal,
            cache=cc,
            enc_kv=enc_kv,
            peft=pf,
            lora_scale=lora_scale,
        )

    return block


def gated_saved_bytes(layers, cfg, h, *, positions, enc_kvs=None, peft=None) -> int:
    """Bytes one pass of a causal :func:`stack_apply` with ``drops`` and
    ``remat`` keeps across its gates: every layer's kept-branch products
    (:func:`repro.core.stld.saved_bytes`), reckoned from the shapes of
    ``h`` (the stack's input), ``positions``, the layers and their adapters;
    nothing runs.  Beyond what a checkpointed, ungated stack keeps (each
    layer's input); a dropped layer's slot is held but never written."""
    block = _layer_block(cfg, True, 1.0)
    train = lambda hh, fz, pf: block(hh, fz, pf)[:2]
    view = lambda tree, l: (
        None if tree is None else jax.eval_shape(lambda t: stacking.layer_view(t, l), tree)
    )
    period = cfg.layer_period
    per_group = sum(
        stld.saved_bytes(train, h, (view(layers, s), view(enc_kvs, s), positions), view(peft, s))
        for s in range(period)
    )
    return stacking.stack_size(layers) // period * per_group


def stack_apply(
    layers: Sequence,
    cfg,
    h,
    *,
    positions,
    causal: bool = True,
    drops=None,
    caches: Optional[Sequence] = None,
    enc_kvs: Optional[Sequence] = None,
    peft: Optional[Sequence] = None,
    lora_scale: float = 1.0,
    active_idx=None,
    remat: bool = False,
):
    """Run the layer stack.  Returns (h, aux_sum, new_caches).

    One ``lax.scan`` runs over groups of ``cfg.layer_period`` layers; its
    body applies the group's layers in order.  ``layers``/``peft``/
    ``caches``/``enc_kvs`` accept either layout: a per-layer list or a
    stacked tree with a leading layer axis.  A stacked tree at period 1 is
    scanned as it is; above period 1 it is reshaped to ``(groups, period,
    ...)`` and split into its slots, and a list is stacked per slot at trace
    time.  Caches come out in the layout they came in.

    ``drops`` gates each layer with a real ``cond``
    (:func:`repro.core.stld.gate`).  ``active_idx`` (gather-STLD, period 1
    only) scans the ``jnp.take`` of the kept layers instead; gathering *is*
    the dropout, so ``drops`` is ignored.

    ``remat`` rematerializes each layer in the backward pass.  With
    ``drops`` the gate and the block go through
    :func:`repro.core.stld.scan_remat`: a kept layer saves its input and the
    outputs of its weight products (:func:`gated_saved_bytes` reckons them),
    so the backward runs no forward matmul; a dropped layer costs no work
    either way, and no gradient reaches the layer weights.
    """
    num_layers = stacking.stack_size(layers)
    if not num_layers:
        return h, jnp.zeros((), dtype=jnp.float32), caches
    period = cfg.layer_period
    if num_layers % period:
        raise ValueError("the layer loop requires num_layers % layer_period == 0")
    n_groups = num_layers // period

    block = _layer_block(cfg, causal, lora_scale)

    def layer(p_l, peft_l, enc_kv_l, h, cache_l, drop):
        frozen = (p_l, enc_kv_l, positions)
        fn = lambda hh, cc: block(hh, frozen, peft_l, cc)
        if remat:
            fn = jax.checkpoint(fn)
        if drop is None:
            return fn(h, cache_l)
        return stld.gate(fn, drop, h, cache_l)

    def by_slot(seq):
        """A column as ``period`` trees with a leading group axis; slot
        ``s`` holds layers ``s, s + period, ...``."""
        if not stacking.is_stacked(seq):
            seq = list(seq)
            return tuple(stacking.stack_params(seq[s::period]) for s in range(period))
        if period == 1:
            return (seq,)
        grouped = jax.tree.map(lambda x: x.reshape((n_groups, period) + x.shape[1:]), seq)
        return tuple(jax.tree.map(lambda x: x[:, s], grouped) for s in range(period))

    cols = {"params": layers, "peft": peft, "caches": caches, "enc": enc_kvs, "drops": drops}
    if active_idx is not None:
        if period > 1:
            raise ValueError("gather-STLD requires a homogeneous stack (layer_period 1)")
        cols["drops"] = None  # gathering *is* the dropout
    order = [k for k, v in cols.items() if v is not None]
    if cols["drops"] is not None and remat:
        if caches is not None:
            raise ValueError("remat with STLD gates runs without caches")
        slots = lambda k: by_slot(cols[k]) if cols[k] is not None else (None,) * period
        train = lambda hh, fz, pf: block(hh, (*fz[0], fz[1]), pf)[:2]
        h, aux_sum = stld.scan_remat(
            train,
            slots("drops"),
            h,
            tuple(zip(slots("params"), slots("enc"))),
            positions,
            slots("peft"),
        )
        return h, aux_sum, None
    xs = tuple(by_slot(cols[k]) for k in order)
    if active_idx is not None:
        xs = jax.tree.map(lambda x: jnp.take(x, active_idx, axis=0), xs)

    def body(h, xs_slots):
        v = dict(zip(order, xs_slots))
        aux_sum, out_caches = None, []
        for s in range(period):
            col = lambda k: v[k][s] if k in v else None
            h, aux, cache_l = layer(
                col("params"), col("peft"), col("enc"), h, col("caches"), col("drops")
            )
            aux_sum = aux if aux_sum is None else aux_sum + aux
            out_caches.append(cache_l)
        return h, (aux_sum, tuple(out_caches) if caches is not None else None)

    h, (auxs, new_slots) = jax.lax.scan(body, h, xs)
    aux_sum = jnp.sum(auxs)
    if caches is None:
        return h, aux_sum, None
    if not stacking.is_stacked(caches):
        return h, aux_sum, [
            jax.tree.map(lambda x: x[l // period], new_slots[l % period])
            for l in range(num_layers)
        ]
    if period == 1:
        # stacked in, stacked out: the scan's (L, ...) output is the layout
        return h, aux_sum, new_slots[0]
    return h, aux_sum, jax.tree.map(
        lambda *xs: jnp.stack(xs, axis=1).reshape((num_layers,) + xs[0].shape[1:]), *new_slots
    )


# --------------------------------------------------------------------------
# LM forward
# --------------------------------------------------------------------------
def lm_apply(
    params,
    cfg,
    tokens,
    *,
    positions=None,
    prefix_embeds=None,
    drops=None,
    caches=None,
    peft=None,
    lora_scale: float = 1.0,
    active_idx=None,
    remat: bool = False,
    tail: Optional[int] = None,
):
    """Decoder-only LM forward.

    tokens: (B, S) int32.  ``prefix_embeds`` (B, P, d) is prepended (VLM stub
    frontend).  ``tail`` runs the final norm and the head on the last
    ``tail`` positions only, so logits are (B, tail, V).  Returns (logits,
    aux, new_caches).
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    h = params["embed"][tokens].astype(compute_dtype)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(compute_dtype), h], axis=1)
    if positions is None:
        positions = jnp.arange(h.shape[1])

    h, aux, new_caches = stack_apply(
        params["layers"],
        cfg,
        h,
        positions=positions,
        causal=True,
        drops=drops,
        caches=caches,
        peft=peft,
        lora_scale=lora_scale,
        active_idx=active_idx,
        remat=remat,
    )
    if tail is not None:
        h = h[:, -tail:]
    h = _norm_apply(cfg, params["final_norm"], h)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head.astype(compute_dtype)
    return logits, aux, new_caches
