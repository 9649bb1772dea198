"""Decoder-stack assembly with STLD-gated layers.

Layer stacks arrive in either layout (see :mod:`repro.models.stacking`):
**stacked** — one pytree with a leading ``(L, ...)`` layer axis on every
leaf, the native layout for homogeneous stacks — or **list** — one pytree
per layer, kept for heterogeneous stacks (hybrid interleaves) and legacy
callers.  ``scan``/``gather``/``group`` consume a stacked tree directly
(zero ``jnp.stack`` inside the traced program); a list is stacked at trace
time as before.

Stack execution modes (``stack_mode``):

* ``unroll`` — python loop over layers (per-layer slices of a stacked
  tree).  Used by the dry-run so ``cost_analysis`` counts every layer (a
  ``lax.scan`` body is costed once — measured 10x undercount, see DESIGN.md
  §8) and by heterogeneous stacks.
* ``scan``   — ``lax.scan`` over the stacked layer params (homogeneous
  stacks): fast compiles for deep models; the training default.
* ``group``  — ``lax.scan`` over groups of ``cfg.layer_period`` layers
  (Jamba's mamba/attn/MoE interleave repeats with period 8).
* ``gather`` — gather-STLD (core.stld): static active count, traced indices,
  a pure ``jnp.take`` on the stacked leaves, scan over the sub-stack.

STLD gating (``drops``) composes with ``unroll``/``scan``/``group``;
``gather`` replaces it with index sampling.
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

_EMPTY = object()  # sentinel for absent scan inputs


def _stack(trees: Sequence):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _as_stacked(trees):
    """Stacked tree for scan-family modes: pass-through when already
    stacked, trace-time stack for list-layout callers."""
    return trees if stacking.is_stacked(trees) else _stack(list(trees))


def _homogeneous(trees) -> bool:
    if stacking.is_stacked(trees):
        return True
    return stacking.is_stackable(list(trees))


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
    stack_mode: str = "unroll",
    active_idx=None,
    remat: bool = False,
):
    """Run the layer stack.  Returns (h, aux_sum, new_caches).

    ``layers``/``peft``/``enc_kvs`` accept either layout: a per-layer list
    or a stacked tree with a leading layer axis.  ``remat`` rematerializes
    each layer in the backward pass; with ``drops`` the gate and the block
    are rematerialized as one unit (:func:`repro.core.stld.gate_remat`), so
    a layer saves only its input, a dropped layer costs no backward work,
    and no gradient reaches the layer weights.  A ``cond`` around a
    checkpointed block would save the checkpoint's inputs, the layer's
    weights among them, and a layer scan would stack them into a copy of
    every layer's weights.
    """
    num_layers = stacking.stack_size(layers)

    def layer(p_l, peft_l, enc_kv_l, h, cache_l, drop):
        # every array the block reads is an argument, so that the remat
        # gate's backward closes over none
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

        frozen = (p_l, enc_kv_l, positions)
        if drop is not None and remat:
            if cache_l is not None:
                raise ValueError("remat with STLD gates runs without caches")
            train = lambda hh, fz, pf: block(hh, fz, pf)[:2]
            return (*stld.gate_remat(train, drop, h, frozen, peft_l), None)
        fn = lambda hh, cc: block(hh, frozen, peft_l, cc)
        if remat:
            fn = jax.checkpoint(fn)
        if drop is None:
            return fn(h, cache_l)
        return stld.gate(fn, drop, h, cache_l)

    # ---------------------------------------------------------- unroll
    if stack_mode == "unroll":
        aux_sum = jnp.zeros((), dtype=jnp.float32)
        caches_stacked = caches is not None and stacking.is_stacked(caches)
        new_caches = [] if caches is not None else None
        for l in range(num_layers):
            cache_l = stacking.layer_view(caches, l) if caches is not None else None
            peft_l = stacking.layer_view(peft, l) if peft is not None else None
            enc_kv_l = stacking.layer_view(enc_kvs, l) if enc_kvs is not None else None
            p_l = stacking.layer_view(layers, l)
            drop = drops[l] if drops is not None else None
            h, aux, cache_l = layer(p_l, peft_l, enc_kv_l, h, cache_l, drop)
            aux_sum = aux_sum + aux
            if new_caches is not None:
                new_caches.append(cache_l)
        if caches_stacked:
            new_caches = stacking.stack_params(new_caches)
        return h, aux_sum, new_caches

    # -------------------------------------------------- gather_unroll
    # gather-STLD with a python loop over the k gathered layers: same
    # compiled semantics as "gather", but every block appears in the HLO so
    # cost_analysis is exact (a lax.scan body is costed once — DESIGN.md §8).
    if stack_mode == "gather_unroll":
        if not _homogeneous(layers):
            raise ValueError("gather_unroll requires a homogeneous stack")
        assert active_idx is not None, "gather_unroll needs active_idx"
        stacked = _as_stacked(layers)
        peft_s = _as_stacked(peft) if peft is not None else None
        take = lambda tree, i: jax.tree.map(lambda x: x[i], tree)
        aux_sum = jnp.zeros((), dtype=jnp.float32)
        for j in range(active_idx.shape[0]):
            idx = active_idx[j]
            p_l = take(stacked, idx)
            peft_l = take(peft_s, idx) if peft_s is not None else None
            h, aux, _ = layer(p_l, peft_l, None, h, None, None)
            aux_sum = aux_sum + aux
        return h, aux_sum, None

    # ------------------------------------------------------ scan / gather
    if stack_mode in ("scan", "gather"):
        if not _homogeneous(layers):
            raise ValueError(f"stack_mode={stack_mode!r} requires a homogeneous stack")
        cols = {
            "params": _as_stacked(layers),
            "peft": _as_stacked(peft) if peft is not None else _EMPTY,
            "caches": _as_stacked(caches) if caches is not None else _EMPTY,
            "enc": _as_stacked(enc_kvs) if enc_kvs is not None else _EMPTY,
            "drops": drops if drops is not None else _EMPTY,
        }
        if stack_mode == "gather":
            assert active_idx is not None, "gather mode needs active_idx"
            cols["drops"] = _EMPTY  # gathering *is* the dropout
            for name in ("params", "peft", "caches", "enc"):
                if cols[name] is not _EMPTY:
                    cols[name] = jax.tree.map(
                        lambda x: jnp.take(x, active_idx, axis=0), cols[name]
                    )
        order = [k for k, v in cols.items() if v is not _EMPTY]
        xs = tuple(cols[k] for k in order)

        def body(h, xs_vals):
            v = dict(zip(order, xs_vals))
            h, aux, new_cache = layer(
                v["params"], v.get("peft"), v.get("enc"), h, v.get("caches"), v.get("drops")
            )
            return h, (aux, new_cache if caches is not None else jnp.zeros((0,)))

        h, (auxs, new_caches_s) = jax.lax.scan(body, h, xs)
        aux_sum = jnp.sum(auxs)
        if caches is None:
            return h, aux_sum, None
        if stacking.is_stacked(caches):
            # stacked in, stacked out: the scan's (L, ...) output IS the
            # stacked layout — no per-layer unstack in the traced program
            return h, aux_sum, new_caches_s
        new_caches = [jax.tree.map(lambda x: x[i], new_caches_s) for i in range(num_layers)]
        return h, aux_sum, new_caches

    # ------------------------------------------------------------- group
    if stack_mode == "group":
        period = cfg.layer_period
        if num_layers % period:
            raise ValueError("group mode requires num_layers % layer_period == 0")
        n_groups = num_layers // period

        def by_slot(seq):
            if stacking.is_stacked(seq):
                # stacked (L, ...) leaves: a (n_groups, period) reshape + slot
                # slice replaces the trace-time per-slot jnp.stack
                grouped = jax.tree.map(
                    lambda x: x.reshape((n_groups, period) + x.shape[1:]), seq
                )
                return tuple(
                    jax.tree.map(lambda x: x[:, s], grouped) for s in range(period)
                )
            seq = list(seq)
            return tuple(
                _stack([seq[g * period + s] for g in range(n_groups)])
                for s in range(period)
            )

        cols = {
            "params": by_slot(layers),
            "peft": by_slot(peft) if peft is not None else _EMPTY,
            "caches": by_slot(caches) if caches is not None else _EMPTY,
            "drops": drops.reshape(n_groups, period) if drops is not None else _EMPTY,
        }
        order = [k for k, v in cols.items() if v is not _EMPTY]
        xs = tuple(cols[k] for k in order)

        def gbody(h, xs_vals):
            v = dict(zip(order, xs_vals))
            aux_sum = jnp.zeros((), dtype=jnp.float32)
            out_caches = []
            for s in range(period):
                cache_l = v["caches"][s] if "caches" in v else None
                peft_l = v["peft"][s] if "peft" in v else None
                drop = v["drops"][s] if "drops" in v else None
                h, aux, cache_l = layer(v["params"][s], peft_l, None, h, cache_l, drop)
                aux_sum = aux_sum + aux
                out_caches.append(cache_l if cache_l is not None else jnp.zeros((0,)))
            return h, (aux_sum, tuple(out_caches))

        h, (auxs, new_slot_caches) = jax.lax.scan(gbody, h, xs)
        aux_sum = jnp.sum(auxs)
        if caches is None:
            return h, aux_sum, None
        new_caches = []
        for g in range(n_groups):
            for s in range(period):
                new_caches.append(jax.tree.map(lambda x: x[g], new_slot_caches[s]))
        if stacking.is_stacked(caches):
            new_caches = stacking.stack_params(new_caches)
        return h, aux_sum, new_caches

    raise ValueError(f"unknown stack_mode {stack_mode!r}")


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
    stack_mode: str = "unroll",
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
        stack_mode=stack_mode,
        active_idx=active_idx,
        remat=remat,
    )
    if tail is not None:
        h = h[:, -tail:]
    h = _norm_apply(cfg, params["final_norm"], h)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head.astype(compute_dtype)
    return logits, aux, new_caches
