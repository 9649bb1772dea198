"""FROZEN pre-refactor ``FederatedSimulator`` (PR-1 state) — parity baseline.

This is a verbatim copy of ``src/repro/federated/simulator.py`` as it stood
before the hook-based algorithm API replaced it (commit 2b2028d).  It exists
ONLY so ``tests/test_method_parity.py`` can prove the new
``ExperimentRunner`` reproduces the old ``run()`` SimResult arrays
bit-for-bit for every registered method.  Do not import it from product
code, and do not "fix" it — its behavior is the contract.

(The only permitted deviation from the verbatim copy: ``init_peft`` calls
pin ``layout="list"`` — the sole layout that existed pre-refactor — so this
baseline keeps exercising the per-layer list code paths after the
stacked-native layout became the library default.  The emitted values are
unchanged; only the container layout is pinned.)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import peft as peft_lib
from repro.core import stld as stld_lib
from repro.core.configurator import OnlineConfigurator
from repro.data import DeviceDataset, dirichlet_partition, make_task
from repro.federated import server as server_lib
from repro.federated.client import make_client_fns
from repro.federated.system_model import SystemModel, sample_bandwidth, sample_device
from repro.models.registry import init_params
from repro.optim import adamw_init


@dataclass
class Strategy:
    """Which paper method/ablation to run."""

    name: str = "droppeft"
    stld: bool = True
    configurator: bool = True
    ptls: bool = True
    fixed_rate: float = 0.5          # used when configurator is off
    hetlora: bool = False            # FedHetLoRA baseline
    hetlora_ranks: tuple = (4, 8, 16)
    adaopt: bool = False             # FedAdaOPT progressive-depth baseline
    adaopt_grow_every: int = 5


METHODS: Dict[str, Strategy] = {
    "fedlora": Strategy("fedlora", stld=False, configurator=False, ptls=False),
    "fedadapter": Strategy("fedadapter", stld=False, configurator=False, ptls=False),
    "fedhetlora": Strategy(
        "fedhetlora", stld=False, configurator=False, ptls=False, hetlora=True
    ),
    "fedadaopt": Strategy(
        "fedadaopt", stld=False, configurator=False, ptls=False, adaopt=True
    ),
    "droppeft": Strategy("droppeft"),
    "droppeft_b1": Strategy("droppeft_b1", stld=False),            # w/o STLD
    "droppeft_b2": Strategy("droppeft_b2", configurator=False),    # fixed rate
    "droppeft_b3": Strategy("droppeft_b3", ptls=False),            # w/o PTLS
}


@dataclass
class SimResult:
    rounds: int
    cum_time_s: np.ndarray           # (R,)
    accuracy: np.ndarray             # (R,) mean cohort val accuracy
    loss: np.ndarray                 # (R,)
    rates: np.ndarray                # (R,) mean dropout rate used
    active_fraction: np.ndarray      # (R,) measured E[L~]/L
    traffic_mb: np.ndarray           # (R,) cohort total
    energy_j: np.ndarray             # (R,) cohort total
    memory_gb: np.ndarray            # (R,) max per-device footprint
    final_accuracy: float = 0.0

    def time_to_accuracy(self, target: float) -> Optional[float]:
        hit = np.where(self.accuracy >= target)[0]
        return float(self.cum_time_s[hit[0]]) if len(hit) else None


class FederatedSimulator:
    def __init__(
        self,
        cfg,
        peft_cfg,
        stld_cfg,
        fed_cfg,
        train_cfg,
        *,
        strategy: Strategy | str = "droppeft",
        task=None,
        cost_cfg=None,
        seed: int = 0,
        cohort_mode: str = "auto",
    ):
        self.cfg = cfg
        self.peft_cfg = peft_cfg
        self.stld_cfg = stld_cfg
        self.fed_cfg = fed_cfg
        self.train_cfg = train_cfg
        self.strategy = METHODS[strategy] if isinstance(strategy, str) else strategy
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.PRNGKey(seed)

        if cohort_mode not in ("auto", "batched", "sequential"):
            raise ValueError(f"unknown cohort_mode {cohort_mode!r}")
        if cohort_mode == "batched" and self.strategy.hetlora:
            raise ValueError(
                "cohort_mode='batched' cannot stack hetlora's rank-heterogeneous "
                "PEFT trees; use 'sequential' (or 'auto')"
            )
        if cohort_mode == "auto":
            cohort_mode = "sequential" if self.strategy.hetlora else "batched"
        self.cohort_mode = cohort_mode

        self.task = task or make_task(vocab_size=cfg.vocab_size, seed=seed)
        parts = dirichlet_partition(
            self.task.labels, fed_cfg.num_devices, fed_cfg.dirichlet_alpha, seed=seed
        )
        self.devices = [
            DeviceDataset(self.task, idx, seed=seed + i) for i, idx in enumerate(parts)
        ]
        self.device_profile = [sample_device(self.rng) for _ in range(fed_cfg.num_devices)]
        # fixed val pad size so the jit'd cohort_evaluate signature is stable
        self._val_pad = max(len(d.val_batch()["labels"]) for d in self.devices)

        self.key, k1, k2 = jax.random.split(self.key, 3)
        self.base_params = init_params(k1, cfg, layout="list")
        self.global_peft = peft_lib.init_peft(k2, cfg, peft_cfg, layout="list")
        self.device_peft: Dict[int, list] = {}
        self.client = make_client_fns(
            cfg, peft_cfg, stld_cfg, train_cfg, loss_tail=self.task.loss_tail,
        )
        self.local_round, self.evaluate = self.client.local_round, self.client.evaluate
        # server aggregation is pure tree math: jit it so a round's
        # aggregation is one dispatch instead of hundreds of tiny ops
        self._fedavg = jax.jit(server_lib.fedavg)
        self._ptls_aggregate = jax.jit(server_lib.ptls_aggregate)
        self.system = SystemModel(cost_cfg or cfg, peft_cfg)
        self.configurator = (
            OnlineConfigurator(
                rate_grid=fed_cfg.rate_grid,
                num_candidates=fed_cfg.num_candidates,
                explore_rate=fed_cfg.explore_rate,
                explore_interval=fed_cfg.explore_interval,
                window_size=fed_cfg.window_size,
                seed=seed,
            )
            if self.strategy.configurator and self.strategy.stld
            else None
        )
        self._prev_acc: Dict[int, float] = {}
        self._last_mask: Dict[int, np.ndarray] = {}
        self._unstack_cache: Dict[int, object] = {}
        self._stack_cache: Dict[int, object] = {}
        self._val_cache: Dict[int, dict] = {}
        self._global_step = 0
        if self.strategy.hetlora:
            # per-device LoRA rank from device capability tier
            tiers = {"tx2": 0, "nx": 1, "agx": 2}
            self.device_rank = [
                self.strategy.hetlora_ranks[tiers[p]] for p in self.device_profile
            ]
            self.max_rank = max(self.strategy.hetlora_ranks)
            # global tree holds the max rank
            self.global_peft = peft_lib.init_peft(
                k2, cfg,
                peft_cfg.__class__(**{**peft_cfg.__dict__, "lora_rank": self.max_rank}),
                layout="list",
            )
            self._het_fns = {}
            for r in set(self.device_rank):
                pc = peft_cfg.__class__(**{**peft_cfg.__dict__, "lora_rank": r})
                self._het_fns[r] = make_client_fns(
                    cfg, pc, stld_cfg, train_cfg, loss_tail=self.task.loss_tail,
                )

    # ------------------------------------------------------------------ run
    def run(self, rounds: Optional[int] = None, target_accuracy: Optional[float] = None) -> SimResult:
        fed = self.fed_cfg
        rounds = rounds or fed.rounds
        hist = {k: [] for k in (
            "time", "acc", "loss", "rate", "active", "traffic", "energy", "memory"
        )}
        cum_time = 0.0
        num_classes = jnp.arange(self.task.num_classes)

        for rnd in range(rounds):
            cohort = [
                int(d)
                for d in self.rng.choice(
                    fed.num_devices,
                    size=min(fed.devices_per_round, fed.num_devices),
                    replace=False,
                )
            ]
            n = len(cohort)
            if self.configurator is not None:
                rates = self.configurator.next_round(n)
            elif self.strategy.stld:
                rates = [self.strategy.fixed_rate] * n
            else:
                rates = [0.0] * n

            adaopt_depth = self.cfg.num_layers
            if self.strategy.adaopt:
                adaopt_depth = min(
                    self.cfg.num_layers,
                    2 + (rnd // self.strategy.adaopt_grow_every) * 2,
                )

            outs = self._run_cohort(cohort, rates, num_classes, adaopt_depth)
            round_accs = [acc for _, _, _, acc in outs]
            round_losses = [float(metrics["loss"]) for _, metrics, _, _ in outs]
            active_fracs = [
                float(metrics["active_layers"]) / self.cfg.num_layers
                for _, metrics, _, _ in outs
            ]

            # share masks: batched importance -> per-device mask in one call
            if self.strategy.ptls:
                k = max(1, int(fed.ptls_share_fraction * self.cfg.num_layers))
                importances = np.stack([np.asarray(imp) for _, _, imp, _ in outs])
                masks = np.asarray(server_lib.cohort_shared_masks(importances, k))
            else:
                masks = np.ones((n, self.cfg.num_layers), dtype=bool)

            client_updates = [peft_i for peft_i, _, _, _ in outs]
            client_ranks = (
                [self.device_rank[dev] for dev in cohort] if self.strategy.hetlora else []
            )
            for i, dev in enumerate(cohort):
                self.device_peft[dev] = client_updates[i]
                self._last_mask[dev] = masks[i]

            # vectorized system-model accounting over the cohort
            bandwidths = np.array([sample_bandwidth(self.rng) for _ in cohort])
            cost = self.system.cohort_round_cost(
                devices=[self.device_profile[dev] for dev in cohort],
                bandwidth_mbps=bandwidths,
                batch=fed.batch_size,
                seq=self.task.seq_len,
                local_steps=fed.local_steps,
                peft=True,
                active_fraction=(
                    np.asarray(active_fracs) if self.strategy.stld else np.ones(n)
                ),
                share_fraction=masks.mean(axis=1),
            )
            round_times = cost.total_time_s

            # ---------------------------------------------------- aggregate
            if self.strategy.hetlora:
                self.global_peft = server_lib.hetlora_aggregate(
                    client_updates, client_ranks, self.max_rank
                )
            elif self.strategy.ptls:
                self.global_peft = self._ptls_aggregate(
                    client_updates, masks, self.global_peft
                )
            else:
                self.global_peft = self._fedavg(client_updates)

            # ------------------------------------------------------- report
            round_wall = float(round_times.max())  # synchronous round
            cum_time += round_wall
            mean_acc = float(np.mean(round_accs))
            if self.configurator is not None:
                gains = []
                for i, dev in enumerate(cohort):
                    prev = self._prev_acc.get(dev, 1.0 / self.task.num_classes)
                    gains.append(max(round_accs[i] - prev, 0.0))
                self.configurator.report(rates, gains, round_times)
            for i, dev in enumerate(cohort):
                self._prev_acc[dev] = round_accs[i]

            hist["time"].append(cum_time)
            hist["acc"].append(mean_acc)
            hist["loss"].append(float(np.mean(round_losses)))
            hist["rate"].append(float(np.mean(rates)))
            hist["active"].append(float(np.mean(active_fracs)))
            hist["traffic"].append(float(cost.traffic_mb.sum()))
            hist["energy"].append(float(cost.energy_j.sum()))
            hist["memory"].append(float(cost.memory_gb.max()))

            if target_accuracy is not None and mean_acc >= target_accuracy:
                break

        result = SimResult(
            rounds=len(hist["time"]),
            cum_time_s=np.asarray(hist["time"]),
            accuracy=np.asarray(hist["acc"]),
            loss=np.asarray(hist["loss"]),
            rates=np.asarray(hist["rate"]),
            active_fraction=np.asarray(hist["active"]),
            traffic_mb=np.asarray(hist["traffic"]),
            energy_j=np.asarray(hist["energy"]),
            memory_gb=np.asarray(hist["memory"]),
        )
        result.final_accuracy = self.final_accuracy(num_classes)
        return result

    # ------------------------------------------------------------ internals
    def _device_start_peft(self, dev: int):
        """Shared layers from the global model; personalized layers local."""
        if dev not in self.device_peft or not self.strategy.ptls:
            if self.strategy.hetlora:
                return server_lib.truncate_lora_rank(self.global_peft, self.device_rank[dev])
            return self.global_peft
        own = self.device_peft[dev]
        # device keeps its own layers; refresh from global (download)
        mixed = []
        for l in range(self.cfg.num_layers):
            mixed.append(self.global_peft[l] if self._is_shared(dev, l) else own[l])
        return mixed

    def _is_shared(self, dev: int, l: int) -> bool:
        mask = self._last_mask.get(dev)
        return True if mask is None else bool(mask[l])

    def _run_cohort(self, cohort, rates, num_classes, adaopt_depth):
        """Train one round's cohort; returns a list (len N) of per-device
        ``(peft, metrics, importance, accuracy)`` tuples.  Both modes draw
        from identical PRNG streams: one split fan-out for the per-device
        keys, per-device global-step offsets in cohort order."""
        fed = self.fed_cfg
        n = len(cohort)
        start_pefts = [self._device_start_peft(dev) for dev in cohort]
        self.key, *keys = jax.random.split(self.key, n + 1)
        gsteps = [self._global_step + i * fed.local_steps for i in range(n)]
        self._global_step += n * fed.local_steps

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
        return outs

    def _adaopt_truncate(self, peft_i, start_peft, adaopt_depth: int):
        """Progressive depth (FedAdaOPT): layers beyond the active depth keep
        their incoming values — their adapter updates are discarded BEFORE
        evaluation, so reported accuracy measures the retained model."""
        return [
            peft_i[l] if l < adaopt_depth else start_peft[l]
            for l in range(self.cfg.num_layers)
        ]

    def _stacked_train_batches(self, dev: int):
        fed = self.fed_cfg
        batches = list(self.devices[dev].train_batches(fed.batch_size, fed.local_steps))
        return {
            k: np.stack([b[k] for b in batches]) for k in ("tokens", "targets", "mask")
        }

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
        if self.stld_cfg.mode == "gather" and self.strategy.stld:
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

    def _run_cohort_batched(
        self, cohort, rates, start_pefts, keys, gsteps, num_classes, adaopt_depth
    ):
        """One (or few, in gather mode) jit'd calls train the whole cohort."""
        n = len(cohort)
        adaopt = self.strategy.adaopt and adaopt_depth < self.cfg.num_layers
        batch_list = [self._stacked_train_batches(dev) for dev in cohort]
        val_list = [self._padded_val_batch(dev) for dev in cohort]
        num_active = self._static_active_counts(rates)

        outs: List[Optional[tuple]] = [None] * n
        for na in dict.fromkeys(num_active):
            pos = [i for i in range(n) if num_active[i] == na]
            peft_stack = self._stack_trees([start_pefts[i] for i in pos])
            batch_stack = {
                k: jnp.asarray(np.stack([batch_list[i][k] for i in pos]))
                for k in ("tokens", "targets", "mask")
            }
            rate_arr = jnp.asarray([float(rates[i]) for i in pos], dtype=jnp.float32)
            key_arr = jnp.stack([keys[i] for i in pos])
            gstep_arr = jnp.asarray([gsteps[i] for i in pos], dtype=jnp.int32)
            val_args = (
                jnp.asarray(np.stack([val_list[i]["tokens"] for i in pos])),
                jnp.asarray(np.stack([val_list[i]["labels"] for i in pos])),
                jnp.asarray(np.stack([val_list[i]["valid"] for i in pos])),
            )
            if adaopt:
                # progressive depth discards deep-layer updates before eval,
                # so train and eval cannot be fused: train, truncate the
                # stacked tree per layer, then evaluate the retained model
                peft_out, metrics, importances = self.client.cohort_round(
                    self.base_params, peft_stack, batch_stack,
                    rate_arr, key_arr, gstep_arr, num_active=na,
                )
                peft_out = self._adaopt_truncate(peft_out, peft_stack, adaopt_depth)
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
            peft_list = self._unstack_tree(peft_out, len(pos))
            metrics_np, imps_np, accs_np = jax.device_get((metrics, importances, accs))
            for j, i in enumerate(pos):
                dev_metrics = {k: v[j] for k, v in metrics_np.items()}
                outs[i] = (peft_list[j], dev_metrics, imps_np[j], float(accs_np[j]))
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
        fed = self.fed_cfg
        if self.strategy.hetlora:
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
        if self.strategy.adaopt and adaopt_depth < self.cfg.num_layers:
            peft_i = self._adaopt_truncate(peft_i, start_peft, adaopt_depth)

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

    def final_accuracy(self, num_classes) -> float:
        """Paper protocol: mean accuracy across ALL devices' local test sets,
        each device using its personalized model (global for non-participants)."""
        if self.cohort_mode == "batched" and not self.strategy.hetlora:
            devs = range(self.fed_cfg.num_devices)
            peft_stack = self._stack_trees(
                [self.device_peft.get(dev, self.global_peft) for dev in devs]
            )
            vals = [self._padded_val_batch(dev) for dev in devs]
            accs = self.client.cohort_evaluate(
                self.base_params,
                peft_stack,
                jnp.asarray(np.stack([v["tokens"] for v in vals])),
                jnp.asarray(np.stack([v["labels"] for v in vals])),
                jnp.asarray(np.stack([v["valid"] for v in vals])),
                num_classes,
            )
            return float(np.mean(np.asarray(accs)))
        accs = []
        for dev in range(self.fed_cfg.num_devices):
            peft_d = self.device_peft.get(dev, self.global_peft)
            if self.strategy.hetlora and dev not in self.device_peft:
                peft_d = server_lib.truncate_lora_rank(self.global_peft, self.device_rank[dev])
            evaluate = (
                self._het_fns[self.device_rank[dev]].evaluate
                if self.strategy.hetlora
                else self.evaluate
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
