"""Production step functions (train / prefill / serve) used by the drivers.

``stld`` argument selects the paper semantics:
  * ``off``    — plain federated PEFT (FedLoRA/FedAdapter baseline compute)
  * ``cond``   — paper-faithful STLD: traced ``lax.cond`` gates (runtime skip)
  * ``gather`` — TPU-native gather-STLD: the compiled graph itself shrinks
                 to the static active-layer count (DESIGN.md §2)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import peft as peft_lib
from repro.core import stld
from repro.core.schedules import unit_shape
from repro.models.losses import softmax_xent
from repro.models.registry import model_apply
from repro.models import encdec
from repro.optim import adamw_update, clip_by_global_norm


def make_train_step(
    cfg,
    peft_cfg,
    train_cfg,
    *,
    stld_mode: str = "off",
    mean_rate: float = 0.5,
    distribution: str = "incremental",
    gather_bucket: int = 4,
    remat: bool = False,
    regather_specs=None,
):
    """Next-token LM fine-tuning step over the PEFT params.

    signature: (base_params, peft_params, opt_state, batch, rng)
      batch = {"tokens": (B, S+1) int32 [, "patches" | "frames"]}
    returns (peft_params, opt_state, metrics)
    """
    lora_sc = peft_lib.lora_scale(peft_cfg) if peft_cfg.method == "lora" else 1.0
    shape_vec = None
    num_active = None
    if stld_mode != "off":
        shape_vec = unit_shape(distribution, cfg.num_layers)
        if stld_mode == "gather":
            num_active = stld.static_active_count(
                mean_rate, cfg.num_layers, gather_bucket
            )

    def loss_fn(peft_params, base_params, batch, drops, active_idx):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        model_batch = dict(batch, tokens=inputs)
        logits, aux, _ = model_apply(
            base_params,
            cfg,
            model_batch,
            drops=drops,
            peft=peft_params,
            lora_scale=lora_sc,
            active_idx=active_idx,
            remat=remat,
        )
        if cfg.modality == "vision":  # strip stub-frontend prefix positions
            logits = logits[:, -inputs.shape[1] :]
        loss, metrics = softmax_xent(logits, targets)
        return loss + cfg.router_aux_coef * aux, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(base_params, peft_params, opt_state, batch, rng):
        if regather_specs is not None:
            # FSDP: base params arrive ZeRO-3-sharded over the data axes;
            # all-gather them ONCE per step back to TP-only layout so every
            # downstream einsum keeps its clean tensor-parallel sharding
            # (leaving it to GSPMD propagation replicates MoE compute).
            base_params = jax.lax.with_sharding_constraint(base_params, regather_specs)
        drops = active_idx = None
        if stld_mode == "cond":
            rates = jnp.clip(shape_vec * mean_rate, 0.0, 0.95)
            drops = stld.sample_drops(rng, rates, 1)
        elif stld_mode == "gather":
            rates = jnp.clip(shape_vec * mean_rate, 0.0, 0.95)
            active_idx = stld.sample_active_indices(rng, rates, num_active)
        (loss, metrics), grads = grad_fn(peft_params, base_params, batch, drops, active_idx)
        grads, gnorm = clip_by_global_norm(grads, train_cfg.grad_clip)
        peft_params, opt_state = adamw_update(
            grads,
            opt_state,
            peft_params,
            lr=train_cfg.learning_rate,
            beta1=train_cfg.beta1,
            beta2=train_cfg.beta2,
            eps=train_cfg.eps,
            weight_decay=train_cfg.weight_decay,
        )
        metrics = dict(metrics, grad_norm=gnorm)
        return peft_params, opt_state, metrics

    return train_step


def make_prefill_step(cfg):
    """(params, batch, caches) -> (last_logits, caches).

    batch: {"tokens": (B, S) [, "patches" | "frames"]}.
    """

    def prefill_step(params, batch, caches):
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(params, cfg, batch["frames"])
            enc_kvs = encdec.encoder_cross_kvs(params, cfg, enc_out)
            logits, _, caches = encdec.decode(
                params, cfg, batch["tokens"], enc_kvs, caches=caches
            )
            return logits[:, -1], caches, enc_kvs
        logits, _, caches = model_apply(params, cfg, batch, caches=caches)
        return logits[:, -1], caches

    return prefill_step


def make_serve_step(cfg):
    """Single-token decode against a KV cache.

    (params, token (B,1), pos (), caches [, enc_kvs]) ->
        (logits (B, V), next_token (B, 1), caches)

    ``pos`` may also be a ``(B,)`` vector — the multi-tenant serving path,
    where continuous batching runs every row at its own depth — and ``peft``
    an adapter tree (e.g. per-projection :class:`~repro.nn.linear.AdapterPool`
    nodes) applied during decode.  Pass ``peft`` as a traced argument, not a
    closure constant, so adapter hot-swaps reuse the compiled step.
    """

    def serve_step(params, token, pos, caches, enc_kvs=None, peft=None):
        positions = pos[..., None] + jnp.arange(1)  # () -> (1,); (B,) -> (B,1)
        if jnp.ndim(pos) == 0:
            positions = positions.reshape(1)
        batch = {"tokens": token}
        if cfg.is_encoder_decoder:
            logits, _, caches = encdec.decode(
                params,
                cfg,
                token,
                enc_kvs,
                positions=positions,
                caches=caches,
                peft=peft,
            )
        else:
            logits, _, caches = model_apply(
                params,
                cfg,
                batch,
                positions=positions,
                caches=caches,
                peft=peft,
            )
        logits = logits[:, -1]
        next_token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return logits, next_token, caches

    return serve_step
