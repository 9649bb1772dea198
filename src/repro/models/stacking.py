"""Stacked-native layer layout: converters and layout predicates.

Two on-host layouts exist for "a stack of L per-layer pytrees":

* **list**    — ``[tree_0, ..., tree_{L-1}]``: one pytree per layer.  The
  historical layout.  Flattening a model's parameters in this layout yields
  O(L·k) leaves, so every jit dispatch pays O(L·k) arg-flattening, and scan
  execution must ``jnp.stack`` the layers *inside* the traced program —
  materializing a second full copy of the frozen base weights per step.
* **stacked** — a single pytree whose leaves carry a leading ``(L, ...)``
  layer axis (structure-of-arrays).  O(k) leaves regardless of depth;
  ``lax.scan``/``jnp.take`` consume it directly with zero traced stacking.

Stacked is the native layout everywhere the stack is *homogeneous* (every
layer has identical structure and shapes).  Heterogeneous stacks — hybrid
attn/mamba interleaves, MoE-every-other-layer patterns — keep the list
layout, which ``stack_apply`` stacks per slot of ``cfg.layer_period`` at
trace time.  All library entry points accept either layout; these helpers are
the single place layout decisions live.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp


def is_stacked(layers) -> bool:
    """True for the stacked (single-pytree) layout, False for list layout."""
    return not isinstance(layers, (list, tuple))


def is_stackable(trees: Sequence) -> bool:
    """Can this per-layer list be stacked?  Requires identical structure and
    leaf shapes across layers (a homogeneous stack)."""
    if not trees:
        return True

    def sig(t):
        return [(jnp.shape(x), jnp.result_type(x)) for x in jax.tree.leaves(t)]

    ref_struct = jax.tree.structure(trees[0])
    ref_sig = sig(trees[0])
    for t in trees[1:]:
        # dtype is part of the signature: jnp.stack would silently promote a
        # mixed-dtype stack, breaking dtype round-trips and bit parity
        if jax.tree.structure(t) != ref_struct or sig(t) != ref_sig:
            return False
    return True


def stack_params(layers: Sequence):
    """list layout -> stacked layout (one ``jnp.stack`` per param kind).

    Raises ``ValueError`` for heterogeneous stacks, which have no stacked
    representation.
    """
    if is_stacked(layers):
        return layers
    if not is_stackable(layers):
        raise ValueError(
            "cannot stack a heterogeneous layer list (per-layer structures "
            "or shapes differ); keep the list layout for this stack"
        )
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_params(layers, num_layers: Optional[int] = None) -> list:
    """stacked layout -> list layout (per-layer slices).

    ``num_layers`` is only needed for leafless stacked trees (e.g. the empty
    PEFT tree of ``method='none'``).
    """
    if not is_stacked(layers):
        return list(layers)
    n = num_layers if num_layers is not None else stack_size(layers)
    if n is None:
        raise ValueError("cannot infer layer count of a leafless stacked tree")
    return [jax.tree.map(lambda x: x[l], layers) for l in range(n)]


def stack_size(layers) -> Optional[int]:
    """Number of layers in either layout (None for a leafless stacked tree)."""
    if not is_stacked(layers):
        return len(layers)
    leaves = jax.tree.leaves(layers)
    return int(leaves[0].shape[0]) if leaves else None


def layer_view(layers, l):
    """Layer ``l`` as a per-layer pytree (a slice view in stacked layout)."""
    if not is_stacked(layers):
        return layers[l]
    return jax.tree.map(lambda x: x[l], layers)


def maybe_stack(layers: Sequence, layout: str = "auto"):
    """Apply an init-time layout policy to a freshly built per-layer list.

    ``auto``    — stacked when homogeneous, list otherwise (the default).
    ``stacked`` — force stacked (raises for heterogeneous stacks).
    ``list``    — keep the list layout (legacy/bench baseline).
    """
    if layout == "list":
        return list(layers)
    if layout == "stacked":
        return stack_params(layers)
    if layout == "auto":
        return stack_params(layers) if is_stackable(layers) else list(layers)
    raise ValueError(f"unknown layer layout {layout!r}")


def select_layers(mask, take_tree, keep_tree, axis: int = 0):
    """Per-layer select on stacked trees: layer ``l`` comes from
    ``take_tree`` where ``mask[l]`` else from ``keep_tree``.  ``axis`` is
    the layer axis (1 for cohort-stacked ``(N, L, ...)`` leaves).  Exact
    copies (``jnp.where`` on a bool mask), so it is bit-identical to the
    list-layout per-layer python selection it replaces."""
    mask = jnp.asarray(mask)

    def pick(t, k):
        m = mask.reshape((1,) * axis + mask.shape + (1,) * (t.ndim - axis - 1))
        return jnp.where(m, t, k)

    return jax.tree.map(pick, take_tree, keep_tree)
