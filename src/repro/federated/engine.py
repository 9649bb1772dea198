"""Cohort execution engine: how one round's selected devices are trained.

The engine is algorithm-agnostic — it owns the jit'd client programs, the
per-device datasets, and the batched/sequential dispatch strategy, while the
*what* of a round (cohort choice, dropout rates, aggregation rule) lives in
:mod:`repro.federated.algorithms`.

``cohort_mode`` selects the dispatch strategy:

* ``"batched"`` — per-device batches, dropout rates, PRNG keys and
  LR-schedule offsets are stacked along a leading device axis and one jit'd
  ``cohort_round`` trains the whole cohort, the devices in turn inside the
  program, so each STLD gate is a real ``cond`` and a dropped layer runs
  nothing.  Validation runs vmapped on padded val batches.  In gather-mode
  STLD the static active-layer count can differ per device, so the cohort
  is partitioned into same-count groups and each group runs as one
  batched call.
* ``"sequential"`` — the per-device python loop, one jit'd ``local_round``
  dispatch per device.  Required for FedHetLoRA's rank-heterogeneous PEFT
  trees, which cannot share one stacked vmap axis.

Both modes consume identical PRNG streams (one ``jax.random.split`` fan-out
per round, per-device global-step offsets in cohort order) and produce
numerically matching per-device PEFT trees, metrics, and PTLS importances —
see ``tests/test_cohort_parity.py``.

PEFT trees flow through the engine in the stacked-native layout (one leaf
per param kind, leading ``(L, ...)`` layer axis — see
:mod:`repro.models.stacking`) whenever the stack is homogeneous, so the
cohort stack/unstack helpers and every client dispatch handle O(k) leaves
instead of O(L·k); the per-layer list layout (hetlora, legacy callers)
keeps working through the same code paths.  How the layer stack runs
follows from the model config alone (one layer loop,
:func:`repro.models.transformer.stack_apply`); the engine chooses only how
the cohort is dispatched.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import peft as peft_lib
from repro.core import stld as stld_lib
from repro.federated import server as server_lib
from repro.federated.client import make_client_fns
from repro.models import registry, stacking
from repro.optim import adamw_init


class CohortEngine:
    """Executes cohorts of local rounds; owns jit caches and device data."""

    def __init__(
        self,
        cfg,
        peft_cfg,
        stld_cfg,
        fed_cfg,
        train_cfg,
        task,
        devices,
        base_params,
        *,
        cohort_mode: str,
        stld_enabled: bool,
    ):
        self.cfg = cfg
        self.base_params = base_params
        self.peft_cfg = peft_cfg
        self.stld_cfg = stld_cfg
        self.fed_cfg = fed_cfg
        self.train_cfg = train_cfg
        self.task = task
        self.devices = devices
        self.cohort_mode = cohort_mode
        self.stld_enabled = stld_enabled

        # the positions the task's loss can fall on: the training step runs
        # head and loss there alone (a task without the attribute keeps all)
        self.loss_tail = getattr(task, "loss_tail", None)
        self.client = make_client_fns(
            cfg, peft_cfg, stld_cfg, train_cfg, loss_tail=self.loss_tail
        )
        self.local_round, self.evaluate = self.client.local_round, self.client.evaluate
        # server aggregation is pure tree math: jit it so a round's
        # aggregation is one dispatch instead of hundreds of tiny ops
        self.fedavg = jax.jit(server_lib.fedavg)
        self.weighted_fedavg = jax.jit(server_lib.weighted_fedavg)
        self.ptls_aggregate = jax.jit(server_lib.ptls_aggregate)
        # fixed val pad size so the jit'd cohort_evaluate signature is stable
        self._val_pad = max(len(d.val_batch()["labels"]) for d in devices)
        self._val_cache: Dict[int, dict] = {}
        self._all_val_stack = None  # cohort-wide stacked val tensors (final_accuracy)
        self._stack_cache: Dict[int, object] = {}
        self._unstack_cache: Dict[int, object] = {}
        self._truncate_cache: Dict[tuple, object] = {}
        # FedHetLoRA: per-device LoRA rank + per-rank client programs
        self.device_rank: Optional[List[int]] = None
        self._het_fns: Dict[int, object] = {}
        self._saved_bytes: Optional[int] = None

    def enable_hetlora(self, device_rank: List[int]):
        """Build per-rank client programs for rank-heterogeneous cohorts."""
        self.device_rank = list(device_rank)
        for r in set(self.device_rank):
            pc = self.peft_cfg.__class__(
                **{**self.peft_cfg.__dict__, "lora_rank": r}
            )
            self._het_fns[r] = make_client_fns(
                self.cfg, pc, self.stld_cfg, self.train_cfg, loss_tail=self.loss_tail
            )

    # ------------------------------------------------------------- execution
    def run_cohort(self, key, global_step, cohort, rates, start_pefts, num_classes, adaopt_depth):
        """Train one round's cohort; returns ``(new_key, new_global_step,
        outs)`` where ``outs`` is a list (len N) of per-device
        ``(peft, metrics, importance, accuracy)`` tuples.  Both modes draw
        from identical PRNG streams: one split fan-out for the per-device
        keys, per-device global-step offsets in cohort order."""
        fed = self.fed_cfg
        n = len(cohort)
        key, *keys = jax.random.split(key, n + 1)
        gsteps = [global_step + i * fed.local_steps for i in range(n)]
        new_gstep = global_step + n * fed.local_steps

        if self.cohort_mode == "batched":
            outs = self._run_cohort_batched(
                cohort, rates, start_pefts, keys, gsteps, num_classes, adaopt_depth
            )
        else:
            outs = [
                self._run_device(
                    cohort[i], rates[i], start_pefts[i], keys[i], gsteps[i],
                    num_classes, adaopt_depth,
                )
                for i in range(n)
            ]
        return key, new_gstep, outs

    def _adaopt_truncate(self, peft_i, start_peft, adaopt_depth: int, axis: int = 0):
        """Progressive depth (FedAdaOPT): layers beyond the active depth keep
        their incoming values — their adapter updates are discarded BEFORE
        evaluation, so reported accuracy measures the retained model.

        Stacked trees use one jit'd ``jnp.where`` over the layer axis
        (``axis`` = 1 for cohort-stacked ``(N, L, ...)`` leaves); exact
        copies, bit-identical to the per-layer list selection."""
        if isinstance(peft_i, (list, tuple)):
            return [
                peft_i[l] if l < adaopt_depth else start_peft[l]
                for l in range(self.cfg.num_layers)
            ]
        fn = self._truncate_cache.get((adaopt_depth, axis))
        if fn is None:
            keep = np.arange(self.cfg.num_layers) < adaopt_depth
            fn = jax.jit(partial(stacking.select_layers, keep, axis=axis))
            self._truncate_cache[(adaopt_depth, axis)] = fn
        return fn(peft_i, start_peft)

    def _stacked_train_batches(self, dev: int):
        fed = self.fed_cfg
        batches = list(self.devices[dev].train_batches(fed.batch_size, fed.local_steps))
        stacked = {
            k: np.stack([b[k] for b in batches]) for k in ("tokens", "targets", "mask")
        }
        if self.loss_tail is not None and np.any(stacked["mask"][..., : -self.loss_tail]):
            raise ValueError(
                f"device {dev}: a loss mask is nonzero before the last "
                f"{self.loss_tail} positions, which the task's loss_tail excludes"
            )
        return stacked

    def _padded_val_batch(self, dev: int):
        """Val batch padded to the cohort-wide size with a validity mask.
        Val splits are static, so the padded batch is built once per device."""
        cached = self._val_cache.get(dev)
        if cached is None:
            val = self.devices[dev].val_batch()
            b = len(val["labels"])
            pad = self._val_pad - b
            valid = np.zeros((self._val_pad,), dtype=np.float32)
            valid[:b] = 1.0
            cached = {
                "tokens": np.pad(val["tokens"], ((0, pad), (0, 0))),
                "labels": np.pad(val["labels"], (0, pad)),
                "valid": valid,
            }
            self._val_cache[dev] = cached
        return cached

    def _static_active_counts(self, rates) -> List[Optional[int]]:
        """Gather-mode static active-layer count per device (None in cond
        mode).  Static counts partition the batched cohort into groups."""
        if self.stld_cfg.mode == "gather" and self.stld_enabled:
            return [
                stld_lib.static_active_count(
                    rate,
                    self.cfg.num_layers,
                    self.stld_cfg.gather_bucket,
                    self.stld_cfg.min_active_layers,
                )
                for rate in rates
            ]
        return [None] * len(rates)

    def layer_bodies_per_step(self, rate: float) -> Optional[int]:
        """Layer bodies one client step at ``rate`` runs, batched or
        sequential alike: the static count in gather mode; ``None`` under the
        ``cond`` gates, which run only the layers they keep (the round's
        ``active_layers``); every layer when the STLD config is off, since
        the client programs then build no gates."""
        na = self._static_active_counts([rate])[0]
        if na is not None:
            return na
        return None if self.stld_cfg.enabled else self.cfg.num_layers

    def head_positions_per_step(self) -> int:
        """Positions one client step runs the head and the loss at: ``batch
        x loss_tail``, or every position of the batch without a tail."""
        fed = self.fed_cfg
        if self.loss_tail is not None:
            return fed.batch_size * self.loss_tail
        seq = self.task.seq_len + (self.cfg.frontend_seq if self.cfg.modality == "vision" else 0)
        return fed.batch_size * seq

    def saved_activation_bytes_per_step(self) -> int:
        """Bytes one client step keeps across its STLD gates: every layer's
        kept-branch products (``stld.scan_remat``) at the step's batch,
        reckoned from shapes once; 0 where the gates do not run (gather
        mode, or the STLD config off)."""
        if self._saved_bytes is None:
            self._saved_bytes = 0
            if self.stld_cfg.enabled and self.stld_cfg.mode != "gather":
                tokens = jax.ShapeDtypeStruct(
                    (self.fed_cfg.batch_size, self.task.seq_len), jnp.int32
                )
                peft = jax.eval_shape(
                    lambda: peft_lib.init_peft(jax.random.PRNGKey(0), self.cfg, self.peft_cfg)
                )
                self._saved_bytes = registry.gated_saved_bytes(
                    self.base_params, self.cfg, tokens, peft=peft
                )
        return self._saved_bytes

    def _run_cohort_batched(
        self, cohort, rates, start_pefts, keys, gsteps, num_classes, adaopt_depth
    ):
        """One (or few, in gather mode) jit'd calls train the whole cohort.

        Host spans, inside the round's own: ``round.stage`` (stacking
        and host-to-device copies), ``round.dispatch`` (enqueueing the
        program; args ``head_rows``, :meth:`head_positions_per_step`, and
        ``saved_bytes``, :meth:`saved_activation_bytes_per_step`) and
        ``round.pull`` (the unstack and the one ``device_get`` that waits
        for it)."""
        n = len(cohort)
        adaopt = adaopt_depth < self.cfg.num_layers
        num_active = self._static_active_counts(rates)

        outs: List[Optional[tuple]] = [None] * n
        for na in dict.fromkeys(num_active):
            pos = [i for i in range(n) if num_active[i] == na]
            with obs.span("round.stage"):
                # each device samples from its own generator, so drawing
                # group by group leaves every device's batches unchanged
                batch_list = [self._stacked_train_batches(cohort[i]) for i in pos]
                val_list = [self._padded_val_batch(cohort[i]) for i in pos]
                peft_stack = self._stack_trees([start_pefts[i] for i in pos])
                batch_stack = {
                    k: jnp.asarray(np.stack([b[k] for b in batch_list]))
                    for k in ("tokens", "targets", "mask")
                }
                rate_arr = jnp.asarray(np.asarray(rates, dtype=np.float32)[pos])
                key_arr = jnp.stack([keys[i] for i in pos])
                gstep_arr = jnp.asarray([gsteps[i] for i in pos], dtype=jnp.int32)
                val_args = tuple(
                    jnp.asarray(np.stack([v[k] for v in val_list]))
                    for k in ("tokens", "labels", "valid")
                )
            with obs.span(
                "round.dispatch",
                head_rows=self.head_positions_per_step(),
                saved_bytes=self.saved_activation_bytes_per_step(),
            ):
                if adaopt:
                    # progressive depth discards deep-layer updates before
                    # eval, so train and eval cannot be fused: train,
                    # truncate the stacked tree per layer, then evaluate the
                    # retained model
                    peft_out, metrics, importances = self.client.cohort_round(
                        self.base_params, peft_stack, batch_stack,
                        rate_arr, key_arr, gstep_arr, num_active=na,
                    )
                    peft_out = self._adaopt_truncate(
                        peft_out, peft_stack, adaopt_depth,
                        axis=0 if isinstance(peft_out, (list, tuple)) else 1,
                    )
                    accs = self.client.cohort_evaluate(
                        self.base_params, peft_out, *val_args, num_classes
                    )
                else:
                    peft_out, metrics, importances, accs = self.client.cohort_round_eval(
                        self.base_params,
                        peft_stack,
                        batch_stack,
                        rate_arr,
                        key_arr,
                        gstep_arr,
                        *val_args,
                        num_classes,
                        num_active=na,
                    )
            # one jit'd unstack + one host pull: per-leaf x[j] slicing and
            # per-device float() syncs would cost hundreds of tiny dispatches
            with obs.span("round.pull"):
                peft_list = self._unstack_tree(peft_out, len(pos))
                metrics_np, imps_np, accs_np = jax.device_get((metrics, importances, accs))
            accs_list = np.asarray(accs_np).tolist()
            for j, i in enumerate(pos):
                dev_metrics = {k: v[j] for k, v in metrics_np.items()}
                outs[i] = (peft_list[j], dev_metrics, imps_np[j], accs_list[j])
        return outs

    def _stack_trees(self, trees):
        """Stack a list of identically-shaped pytrees along a new leading
        axis in ONE jit'd dispatch (cached per cohort-group size)."""
        n = len(trees)
        fn = self._stack_cache.get(n)
        if fn is None:
            fn = jax.jit(lambda *ts: jax.tree.map(lambda *xs: jnp.stack(xs), *ts))
            self._stack_cache[n] = fn
        return fn(*trees)

    def _unstack_tree(self, tree, n: int):
        """Split a leading-(n,) stacked pytree into n pytrees in ONE jit'd
        dispatch (cached per cohort-group size)."""
        fn = self._unstack_cache.get(n)
        if fn is None:
            fn = jax.jit(lambda t: tuple(jax.tree.map(lambda x: x[j], t) for j in range(n)))
            self._unstack_cache[n] = fn
        return fn(tree)

    def _run_device(
        self, dev: int, rate: float, start_peft, key, gstep: int, num_classes, adaopt_depth
    ):
        if self.device_rank is not None:
            fns = self._het_fns[self.device_rank[dev]]
            local_round, evaluate = fns.local_round, fns.evaluate
        else:
            local_round, evaluate = self.local_round, self.evaluate

        stacked = {
            k: jnp.asarray(v) for k, v in self._stacked_train_batches(dev).items()
        }
        opt_state = adamw_init(start_peft)
        num_active = self._static_active_counts([rate])[0]
        peft_i, _, metrics, importance = local_round(
            self.base_params,
            start_peft,
            opt_state,
            stacked,
            jnp.asarray(rate, dtype=jnp.float32),
            key,
            jnp.asarray(gstep, dtype=jnp.int32),
            num_active=num_active,
        )
        if adaopt_depth < self.cfg.num_layers:
            peft_i = self._adaopt_truncate(peft_i, start_peft, adaopt_depth)
        # one host pull for the round's scalars; downstream per-field float()
        # reads then touch numpy, not device buffers
        metrics, importance = jax.device_get((metrics, importance))

        val = self.devices[dev].val_batch()
        acc = float(
            evaluate(
                self.base_params,
                peft_i,
                jnp.asarray(val["tokens"]),
                jnp.asarray(val["labels"]),
                num_classes,
            )
        )
        return peft_i, metrics, importance, acc

    # ------------------------------------------------------------ evaluation
    def final_accuracy(self, global_peft, device_peft, num_classes) -> float:
        """Paper protocol: mean accuracy across ALL devices' local test sets,
        each device using its personalized model (global for non-participants)."""
        hetlora = self.device_rank is not None
        if self.cohort_mode == "batched" and not hetlora:
            devs = range(self.fed_cfg.num_devices)
            peft_stack = self._stack_trees(
                [device_peft.get(dev, global_peft) for dev in devs]
            )
            if self._all_val_stack is None:
                # val splits are static: build the cohort-wide stacked val
                # tensors once instead of re-stacking them on every call
                vals = [self._padded_val_batch(dev) for dev in devs]
                self._all_val_stack = tuple(
                    jnp.asarray(np.stack([v[k] for v in vals]))
                    for k in ("tokens", "labels", "valid")
                )
            accs = self.client.cohort_evaluate(
                self.base_params, peft_stack, *self._all_val_stack, num_classes
            )
            return float(np.mean(np.asarray(accs)))
        accs = []
        for dev in range(self.fed_cfg.num_devices):
            peft_d = device_peft.get(dev, global_peft)
            if hetlora and dev not in device_peft:
                peft_d = server_lib.truncate_lora_rank(global_peft, self.device_rank[dev])
            evaluate = (
                self._het_fns[self.device_rank[dev]].evaluate if hetlora else self.evaluate
            )
            val = self.devices[dev].val_batch()
            accs.append(
                float(
                    evaluate(
                        self.base_params,
                        peft_d,
                        jnp.asarray(val["tokens"]),
                        jnp.asarray(val["labels"]),
                        num_classes,
                    )
                )
            )
        return float(np.mean(accs))
