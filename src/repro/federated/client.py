"""Client-side local fine-tuning with STLD (paper §3.1-3.2).

``make_client_fns`` builds the jit'd per-round programs and returns them as a
:class:`ClientFns` namedtuple:

* ``local_round``  — ``lax.scan`` over local mini-batch steps; each step
  draws fresh STLD gates (Bernoulli per layer, or gather-mode indices),
  computes PEFT-only grads, AdamW-updates the PEFT tree, and accumulates
  the Eq.-6 PTLS importance statistics.
* ``evaluate``     — full-model (no dropout) classification accuracy on the
  device's local validation split.
* ``cohort_round`` — the batched cohort engine: the local round over a
  leading device axis, the devices in turn (``lax.map``).  One jit'd call
  trains a whole cohort from stacked per-device batches, a per-device ``mean_rate``
  vector, split PRNG keys, and per-device global-step offsets.  Each device
  starts from a fresh AdamW state (exactly what the simulator does per
  round), so the optimizer state never crosses the device axis.
* ``cohort_evaluate`` — vmapped validation over the device axis.  Val shards
  have heterogeneous sizes, so batches arrive padded to a common size with a
  ``valid`` row mask; the masked mean equals the per-device plain mean.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import peft as peft_lib
from repro.core import ptls, stld
from repro.core.schedules import unit_shape
from repro.models.losses import softmax_xent
from repro.models.registry import model_apply
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, make_lr_schedule


class ClientFns(NamedTuple):
    local_round: Callable
    evaluate: Callable
    cohort_round: Callable
    cohort_evaluate: Callable
    cohort_round_eval: Callable


def _model_batch(cfg, tokens):
    batch = {"tokens": tokens}
    if cfg.modality == "vision":
        b = tokens.shape[0]
        batch["patches"] = jnp.zeros((b, cfg.frontend_seq, cfg.d_model), dtype=cfg.dtype)
    if cfg.modality == "audio":
        b = tokens.shape[0]
        batch["frames"] = jnp.zeros((b, cfg.frontend_seq, cfg.d_model), dtype=cfg.dtype)
    return batch


def _logits_for_tokens(cfg, logits, tokens):
    """Strip any stub-frontend prefix so logits align with token positions."""
    if cfg.modality == "vision":
        return logits[:, -tokens.shape[1] :]
    return logits


def make_client_fns(
    cfg,
    peft_cfg,
    stld_cfg,
    train_cfg,
    *,
    donate: Optional[bool] = None,
    loss_tail: Optional[int] = None,
) -> ClientFns:
    """Build the jit'd per-round client programs.

    PEFT/base trees arrive in either layer layout; the stacked-native layout
    shrinks the dispatch pytree from O(L·k) to O(k) leaves and removes every
    traced ``jnp.stack`` of base-layer params from the compiled programs.

    ``donate`` (default: auto — on for non-CPU backends, where XLA actually
    implements buffer donation) donates the round-scoped buffers to their
    jit'd programs so each round's PEFT/optimizer update can reuse the input
    allocation instead of holding both copies live: ``local_round`` donates
    its fresh AdamW state, ``cohort_round_eval`` its stacked cohort PEFT
    input.  ``cohort_round`` never donates — its FedAdaOPT caller truncates
    against the start stack after the call returns.

    The local-step scan runs under the named scope ``client.train`` and the
    fused validation of ``cohort_round_eval`` under ``client.validate``: the
    names reach the compiled ops' ``op_name`` metadata, where a profiler
    trace finds each phase's device time, and change nothing else.

    Every training step rematerializes its layers in the backward pass.
    With STLD enabled every program gates each layer with a real ``cond``
    (``stld.scan_remat``): a kept layer saves its input and the outputs of
    its weight products, so its backward runs no forward matmul, and a
    dropped layer costs no forward or backward.  With STLD disabled the
    layers run ungated and save only their inputs.  The cohort programs
    train their devices one after another inside the one program, since
    under ``vmap`` a ``cond`` whose predicate differs per device becomes a
    select that runs every layer.  The fused validation is vmapped over the
    trained trees.

    ``loss_tail`` (static; ``None`` keeps every position) states that the
    training loss falls on the last ``loss_tail`` positions only: the final
    norm, the head and the loss then run there alone.  Rows are independent
    through all three and a masked position adds zero to the loss, the
    accuracy and every gradient, so the step computes the same numbers; the
    caller guarantees that ``mask`` is zero before the tail.  Validation
    reads the last position and takes no tail.
    """
    if loss_tail is not None and loss_tail < 1:
        raise ValueError(f"loss_tail must be at least 1, got {loss_tail}")
    if donate is None:
        donate = jax.default_backend() != "cpu"
    lora_sc = peft_lib.lora_scale(peft_cfg) if peft_cfg.method == "lora" else 1.0
    sched = make_lr_schedule(
        train_cfg.schedule, train_cfg.learning_rate, train_cfg.warmup_steps, train_cfg.total_steps
    )
    gather_mode = stld_cfg.mode == "gather"

    def loss_fn(peft_params, base_params, tokens, targets, mask, drops, active_idx):
        logits, aux, _ = model_apply(
            base_params,
            cfg,
            _model_batch(cfg, tokens),
            drops=drops,
            peft=peft_params,
            lora_scale=lora_sc,
            active_idx=active_idx,
            remat=True,
            tail=loss_tail,
        )
        logits = _logits_for_tokens(cfg, logits, tokens)
        if loss_tail is not None:
            targets, mask = targets[:, -loss_tail:], mask[:, -loss_tail:]
        loss, metrics = softmax_xent(logits, targets, mask)
        loss = loss + cfg.router_aux_coef * aux
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _local_round(
        base_params,
        peft_params,
        opt_state,
        batches,  # dict of arrays with leading (steps,) dim
        mean_rate,  # scalar: this round's dropout-rate config (the bandit arm)
        rng,
        global_step,
        num_active: Optional[int] = None,
    ):
        shape = unit_shape(stld_cfg.distribution, cfg.num_layers)
        rates = jnp.clip(shape * mean_rate, 0.0, 0.95)
        if not stld_cfg.enabled:
            rates = jnp.zeros((cfg.num_layers,))
        imp0 = ptls.ImportanceAccumulator.init(cfg.num_layers)

        def step(carry, xs):
            peft_p, opt, imp, rng, gstep = carry
            tokens, targets, mask = xs
            rng, kd = jax.random.split(rng)
            if gather_mode and num_active is not None:
                active_idx = stld.sample_active_indices(kd, rates, num_active)
                drops = None
                drops_for_imp = jnp.ones((cfg.num_layers,)).at[active_idx].set(0.0)
            else:
                drops = stld.sample_drops(kd, rates, stld_cfg.min_active_layers)
                active_idx = None
                drops_for_imp = drops.astype(jnp.float32)
            # disabled STLD draws all-kept gates; the layers then run ungated
            gates = drops if stld_cfg.enabled else None
            (loss, metrics), grads = grad_fn(
                peft_p, base_params, tokens, targets, mask, gates, active_idx
            )
            gnorms = ptls.layer_grad_norms(grads, cfg.num_layers)
            imp = ptls.ImportanceAccumulator.update(imp, gnorms, drops_for_imp)
            grads, gn = clip_by_global_norm(grads, train_cfg.grad_clip)
            peft_p, opt = adamw_update(
                grads,
                opt,
                peft_p,
                lr=sched(gstep),
                beta1=train_cfg.beta1,
                beta2=train_cfg.beta2,
                eps=train_cfg.eps,
                weight_decay=train_cfg.weight_decay,
            )
            out_metrics = {
                "loss": metrics["loss"],
                "accuracy": metrics["accuracy"],
                "grad_norm": gn,
                "active_layers": jnp.sum(1.0 - drops_for_imp),
            }
            return (peft_p, opt, imp, rng, gstep + 1), out_metrics

        xs = (batches["tokens"], batches["targets"], batches["mask"])
        with jax.named_scope("client.train"):
            (peft_params, opt_state, imp, _, _), metrics = jax.lax.scan(
                step, (peft_params, opt_state, imp0, rng, global_step), xs
            )
        metrics = jax.tree.map(jnp.mean, metrics)
        importance = ptls.ImportanceAccumulator.importance(imp)
        return peft_params, opt_state, metrics, importance

    local_round = jax.jit(
        _local_round,
        static_argnames=("num_active",),
        donate_argnums=(2,) if donate else (),  # the per-round AdamW state
    )

    def _train_cohort(base_params, peft_stack, batch_stack, rates, rngs, global_steps, num_active):
        """The local round of every cohort member, one after another
        (``lax.map``), so each member's gates are scalars and stay real
        ``cond``s.  Returns the stacked results."""

        def one(xs):
            peft_params, batches, rate, rng, gstep = xs
            opt0 = adamw_init(peft_params)
            peft_p, _, metrics, importance = _local_round(
                base_params, peft_params, opt0, batches, rate, rng, gstep, num_active
            )
            return peft_p, metrics, importance

        return jax.lax.map(one, (peft_stack, batch_stack, rates, rngs, global_steps))

    @partial(jax.jit, static_argnames=("num_active",))
    def cohort_round(
        base_params,
        peft_stack,     # PEFT pytree with leading (N,) device axis on every leaf
        batch_stack,    # dict of (N, steps, ...) arrays
        rates,          # (N,) per-device mean dropout rates
        rngs,           # (N, 2) split PRNG keys, one per device
        global_steps,   # (N,) per-device LR-schedule offsets
        num_active: Optional[int] = None,
    ):
        """Train the whole cohort in one call: ``local_round`` per device.

        ``num_active`` is static (gather mode); a cohort with heterogeneous
        static counts must be partitioned into same-count groups by the
        caller (the simulator does this).  Returns stacked
        ``(peft_stack, metrics, importances)``.
        """
        return _train_cohort(
            base_params, peft_stack, batch_stack, rates, rngs, global_steps, num_active
        )

    def _class_logits(base_params, peft_params, tokens, num_classes_arr):
        """Label-token logits at the final position (synthetic task protocol)."""
        logits, _, _ = model_apply(
            base_params,
            cfg,
            _model_batch(cfg, tokens),
            peft=peft_params,
            lora_scale=lora_sc,
        )
        logits = _logits_for_tokens(cfg, logits, tokens)
        final = logits[:, -1].astype(jnp.float32)  # (B, V)
        return final[:, 1 : 1 + num_classes_arr.shape[0]]

    @jax.jit
    def evaluate(base_params, peft_params, tokens, labels, num_classes_arr):
        """Classification accuracy: argmax over label-token logits at the
        final position (synthetic task protocol)."""
        class_logits = _class_logits(base_params, peft_params, tokens, num_classes_arr)
        pred = jnp.argmax(class_logits, axis=-1)
        return jnp.mean((pred == labels).astype(jnp.float32))

    def _masked_accuracy(base_params, peft_params, toks, labs, v, num_classes_arr):
        class_logits = _class_logits(base_params, peft_params, toks, num_classes_arr)
        pred = jnp.argmax(class_logits, axis=-1)
        correct = (pred == labs).astype(jnp.float32) * v
        return jnp.sum(correct) / jnp.maximum(jnp.sum(v), 1.0)

    @jax.jit
    def cohort_evaluate(base_params, peft_stack, tokens, labels, valid, num_classes_arr):
        """Per-device accuracies (N,) from padded (N, B, S) val batches;
        ``valid`` is the (N, B) row mask for the padding."""

        def one(peft_params, toks, labs, v):
            return _masked_accuracy(base_params, peft_params, toks, labs, v, num_classes_arr)

        return jax.vmap(one)(peft_stack, tokens, labels, valid)

    @partial(
        jax.jit,
        static_argnames=("num_active",),
        # the stacked cohort PEFT input is rebuilt fresh every round; donate
        # it so the round's output can alias the input allocation
        donate_argnums=(1,) if donate else (),
    )
    def cohort_round_eval(
        base_params,
        peft_stack,
        batch_stack,
        rates,
        rngs,
        global_steps,
        val_tokens,
        val_labels,
        val_valid,
        num_classes_arr,
        num_active: Optional[int] = None,
    ):
        """Fused cohort train + validation: one dispatch per round so the
        per-call overhead (arg flattening of the ~100-leaf base tree, program
        launch) is paid once for the whole cohort instead of 2N times.  The
        validation of the trained trees is vmapped over the cohort."""
        peft_out, metrics, importances = _train_cohort(
            base_params, peft_stack, batch_stack, rates, rngs, global_steps, num_active
        )

        def accuracy(peft_params, toks, labs, v):
            return _masked_accuracy(base_params, peft_params, toks, labs, v, num_classes_arr)

        with jax.named_scope("client.validate"):
            accs = jax.vmap(accuracy)(peft_out, val_tokens, val_labels, val_valid)
        return peft_out, metrics, importances, accs

    return ClientFns(local_round, evaluate, cohort_round, cohort_evaluate, cohort_round_eval)
