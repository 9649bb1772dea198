"""Engine-agnostic experiment runner: the federated round loop.

:class:`ExperimentRunner` owns the loop that used to live inside the
``FederatedSimulator`` god-class.  It builds an :class:`ExperimentContext`
(task, device shards, hardware profiles, system model, execution engine),
binds a :class:`~repro.federated.algorithms.FederatedAlgorithm`, and drives
its lifecycle hooks round by round, threading an immutable
:class:`~repro.federated.state.RoundState` through them.

On top of the plain loop it provides what the god-class could not:

* ``target_accuracy`` early stop (unchanged semantics),
* save/resume — any round boundary can be checkpointed through
  :mod:`repro.checkpoint` and resumed bit-exactly (PRNG streams, bandit
  arms, per-device data-sampler states and metric history included),
* multi-seed replication via :func:`run_replicates`.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import ckpt as ckpt_lib
from repro.core import peft as peft_lib
from repro.data import DeviceDataset, dirichlet_partition, make_task
from repro.federated.algorithms import FederatedAlgorithm, get_algorithm
from repro.federated.compression import CompressionConfig, resolve_compression
from repro.federated.engine import CohortEngine
from repro.federated.faults import FaultInjector, resolve_fault_plan
from repro.federated.scheduler import (
    ScheduleConfig,
    VirtualClockScheduler,
    resolve_schedule,
)
from repro.federated.state import RoundState
from repro.federated.system_model import SystemModel, sample_device
from repro.models import stacking
from repro.models.registry import init_params


@dataclass
class SimResult:
    rounds: int
    cum_time_s: np.ndarray           # (R,) scheduler virtual clock at each aggregation
    accuracy: np.ndarray             # (R,) mean val accuracy of aggregated updates
    loss: np.ndarray                 # (R,)
    rates: np.ndarray                # (R,) mean dropout rate used
    active_fraction: np.ndarray      # (R,) measured E[L~]/L
    traffic_mb: np.ndarray           # (R,) cohort total
    energy_j: np.ndarray             # (R,) cohort total
    memory_gb: np.ndarray            # (R,) max per-device footprint
    final_accuracy: float = 0.0
    arrivals: Optional[np.ndarray] = None  # (R,) updates aggregated per step

    def time_to_accuracy(self, target: float, *, sustained: bool = False) -> Optional[float]:
        """Simulated time until ``accuracy >= target``.

        ``sustained=True`` requires the target to be held for every later
        round too (suffix minimum), so a single noisy round that dips back
        below the target cannot win a speedup claim.
        """
        if sustained:
            suffix_min = np.minimum.accumulate(self.accuracy[::-1])[::-1]
            hit = np.where(suffix_min >= target)[0]
        else:
            hit = np.where(self.accuracy >= target)[0]
        return float(self.cum_time_s[hit[0]]) if len(hit) else None


@dataclass
class ExperimentContext:
    """Everything an algorithm's hooks may consult; built once per seed."""

    cfg: Any
    peft_cfg: Any
    stld_cfg: Any
    fed_cfg: Any
    train_cfg: Any
    task: Any
    devices: List[DeviceDataset]
    device_profile: List[str]
    system: SystemModel
    seed: int
    peft_key: Any                  # the key init_peft consumed (hetlora re-init)
    init_global_peft: Any
    num_classes: Any               # jnp.arange(task.num_classes)
    engine: Optional[CohortEngine] = None
    schedule: Optional[ScheduleConfig] = None  # virtual-clock scheduling policy
    compression: Optional[CompressionConfig] = None  # uplink compression | None


def _build_context(
    cfg, peft_cfg, stld_cfg, fed_cfg, train_cfg, *, task=None, cost_cfg=None, seed=0,
    device_profile=None,
):
    """Replicates the legacy simulator's construction order exactly so the
    numpy/JAX RNG streams (device profiles, param init) are unchanged.

    ``device_profile`` (optional) pins the hardware mix instead of sampling
    it — benchmarks and golden tests use it to build e.g. a guaranteed
    mixed tx2/nx/agx cohort.  Pinning skips the profile RNG draws, so a
    pinned run is not stream-comparable with a sampled one.
    """
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    task = task or make_task(vocab_size=cfg.vocab_size, seed=seed)
    parts = dirichlet_partition(
        task.labels, fed_cfg.num_devices, fed_cfg.dirichlet_alpha, seed=seed
    )
    devices = [DeviceDataset(task, idx, seed=seed + i) for i, idx in enumerate(parts)]
    if device_profile is None:
        device_profile = [sample_device(rng) for _ in range(fed_cfg.num_devices)]
    else:
        device_profile = list(device_profile)
        if len(device_profile) != fed_cfg.num_devices:
            raise ValueError(
                f"device_profile has {len(device_profile)} entries for "
                f"{fed_cfg.num_devices} devices"
            )
    key, k1, k2 = jax.random.split(key, 3)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), k1)
    with obs.span("init", bytes=sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))):
        base_params = jax.block_until_ready(init_params(k1, cfg))
    global_peft = peft_lib.init_peft(k2, cfg, peft_cfg)
    ctx = ExperimentContext(
        cfg=cfg,
        peft_cfg=peft_cfg,
        stld_cfg=stld_cfg,
        fed_cfg=fed_cfg,
        train_cfg=train_cfg,
        task=task,
        devices=devices,
        device_profile=device_profile,
        system=SystemModel(cost_cfg or cfg, peft_cfg),
        seed=seed,
        peft_key=k2,
        init_global_peft=global_peft,
        num_classes=jnp.arange(task.num_classes),
    )
    return ctx, rng, key, base_params


def fresh_algorithm(algorithm):
    """Per-run copy of an algorithm prototype, configuration preserved.

    Algorithm instances are bound to one experiment context; reusing one
    across runners would rebind it and mutate the caller's object.  A
    shallow copy keeps every constructor-configured attribute (ranks,
    fixed rates, toggles) while ``bind`` recomputes all derived state.
    """
    if isinstance(algorithm, str):
        return algorithm
    algo = copy.copy(algorithm)
    algo.ctx = None
    return algo


class ExperimentRunner:
    """Round loop + state threading + checkpointing for one experiment."""

    def __init__(
        self,
        cfg,
        peft_cfg,
        stld_cfg,
        fed_cfg,
        train_cfg,
        *,
        algorithm: "FederatedAlgorithm | str" = "droppeft",
        task=None,
        cost_cfg=None,
        seed: int = 0,
        cohort_mode: str = "auto",
        schedule: "ScheduleConfig | str" = "sync",
        device_profile=None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        resume: bool = False,
        fault_plan=None,
        compression=None,
    ):
        if isinstance(algorithm, str):
            algorithm = get_algorithm(algorithm)()
        else:
            # never bind a caller-owned instance: a second runner built from
            # the same prototype would silently rebind its context
            algorithm = fresh_algorithm(algorithm)
        self.algorithm = algorithm
        self.schedule = resolve_schedule(schedule)
        self.compression = resolve_compression(compression)
        self.fault_plan = resolve_fault_plan(fault_plan)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = max(1, checkpoint_every)

        ctx, rng, key, base_params = _build_context(
            cfg, peft_cfg, stld_cfg, fed_cfg, train_cfg,
            task=task, cost_cfg=cost_cfg, seed=seed, device_profile=device_profile,
        )
        ctx.schedule = self.schedule  # visible to bind()/build_configurator
        ctx.compression = self.compression
        self.ctx = ctx
        global_peft = algorithm.bind(ctx)

        if cohort_mode not in ("auto", "batched", "sequential"):
            raise ValueError(f"unknown cohort_mode {cohort_mode!r}")
        if cohort_mode == "batched" and algorithm.requires_sequential:
            raise ValueError(
                f"cohort_mode='batched' cannot stack {algorithm.name}'s "
                "heterogeneous PEFT trees; use 'sequential' (or 'auto')"
            )
        if cohort_mode == "auto":
            cohort_mode = "sequential" if algorithm.requires_sequential else "batched"
        self.cohort_mode = cohort_mode

        ctx.engine = CohortEngine(
            cfg, peft_cfg, stld_cfg, fed_cfg, train_cfg, ctx.task, ctx.devices,
            base_params, cohort_mode=cohort_mode, stld_enabled=algorithm.stld,
        )
        if getattr(algorithm, "device_rank", None) is not None:
            ctx.engine.enable_hetlora(algorithm.device_rank)

        self.state = RoundState(
            key=key,
            global_peft=global_peft,
            rng=rng,
            configurator=algorithm.build_configurator(ctx),
        )
        self.scheduler = VirtualClockScheduler(
            self,
            self.schedule,
            faults=(
                FaultInjector(self.fault_plan)
                if self.fault_plan is not None
                else None
            ),
        )
        if resume:
            if not checkpoint_dir:
                raise ValueError("resume=True requires checkpoint_dir")
            self._restore_latest()

    # ---------------------------------------------------------------- loop
    def run(
        self, rounds: Optional[int] = None, target_accuracy: Optional[float] = None
    ) -> SimResult:
        """Drive the round loop through the virtual-clock scheduler.

        The scheduler owns the loop for every policy; ``schedule="sync"``
        calls the lifecycle hooks in the exact pre-scheduler order, so its
        results are bit-identical to the historical barrier loop
        (``tests/test_schedule_parity.py``)."""
        return self.scheduler.run(rounds=rounds, target_accuracy=target_accuracy)

    def result(self) -> SimResult:
        hist = self.state.history
        res = SimResult(
            rounds=len(hist),
            cum_time_s=np.asarray([r["time"] for r in hist]),
            accuracy=np.asarray([r["acc"] for r in hist]),
            loss=np.asarray([r["loss"] for r in hist]),
            rates=np.asarray([r["rate"] for r in hist]),
            active_fraction=np.asarray([r["active"] for r in hist]),
            traffic_mb=np.asarray([r["traffic"] for r in hist]),
            energy_j=np.asarray([r["energy"] for r in hist]),
            memory_gb=np.asarray([r["memory"] for r in hist]),
            arrivals=np.asarray([r.get("arrivals", -1) for r in hist]),
        )
        with obs.span("evaluate", round=self.state.round_index):
            res.final_accuracy = self.ctx.engine.final_accuracy(
                self.state.global_peft, self.state.device_peft, self.ctx.num_classes
            )
        return res

    # --------------------------------------------------------- checkpointing
    # Checkpoint meta versions:
    #   1 (implicit; pre-durability) — round state only, no in-flight
    #     scheduler section.  Still loads under policies that never keep
    #     updates across aggregation boundaries (sync, deadline+drop).
    #   2 — adds "scheduler" (in-flight jobs, event/fault logs, retry
    #     bookkeeping) + "fault_plan", making async-buffer and
    #     deadline+carry resumable bit-exactly.
    #   3 — adds "ef_residual" (per-device error-feedback residual trees)
    #     plus per-job uplink reconstructions/levels inside the scheduler
    #     section.  v2 snapshots still load (empty residuals, no uplink
    #     state) — they could only have been written by uncompressed runs.
    CKPT_META_VERSION = 3

    def save_checkpoint(self) -> str:
        """Persist the full round state; a resumed run is bit-identical."""
        state = self.state
        sched_jobs, sched_meta = self.scheduler.state_dict()
        arrays = {
            "key": np.asarray(state.key),
            "global_peft": state.global_peft,
            "device_peft": {str(d): t for d, t in sorted(state.device_peft.items())},
            "last_mask": {
                str(d): np.asarray(m) for d, m in sorted(state.last_mask.items())
            },
            "ef_residual": {
                str(d): t for d, t in sorted(state.ef_residual.items())
            },
            "scheduler_jobs": sched_jobs,
        }
        meta = {
            "meta_version": self.CKPT_META_VERSION,
            "scheduler": sched_meta,
            "fault_plan": (
                None if self.fault_plan is None else self.fault_plan.to_json()
            ),
            "round_index": state.round_index,
            "global_step": state.global_step,
            "cum_time": state.cum_time,
            "virtual_time": state.virtual_time,
            "server_version": state.server_version,
            "prev_acc": {str(d): v for d, v in state.prev_acc.items()},
            "rng_state": state.rng.bit_generator.state,
            "device_rng": [d._rng.bit_generator.state for d in self.ctx.devices],
            "configurator": (
                state.configurator.state_dict() if state.configurator else None
            ),
            "history": list(state.history),
        }
        return ckpt_lib.save_state(
            self.checkpoint_dir, state.round_index, arrays, meta
        )

    def _peft_native_layout(self, tree):
        """Convert a checkpointed PEFT tree to this runner's native layout.

        Pre-refactor checkpoints stored per-layer lists; the stacked-native
        runner loads them transparently (and vice versa for heterogeneous
        configs whose native layout is still the list)."""
        native_stacked = stacking.is_stacked(self.ctx.init_global_peft)
        if native_stacked and isinstance(tree, (list, tuple)):
            return stacking.stack_params(list(tree))
        if not native_stacked and stacking.is_stacked(tree):
            return stacking.unstack_params(tree, self.ctx.cfg.num_layers)
        return tree

    def _restore_latest(self):
        latest = ckpt_lib.latest_state_dir(self.checkpoint_dir)
        if latest is None:
            return  # nothing saved yet: fresh start
        arrays, meta = ckpt_lib.load_state(latest)
        state = self.state
        sched_meta = meta.get("scheduler")
        if sched_meta is None and self.schedule.keeps_in_flight_state:
            raise ValueError(
                f"checkpoint at {latest} predates durable in-flight state "
                f"(meta version {meta.get('meta_version', 1)}; this runner "
                f"writes version {self.CKPT_META_VERSION}) and cannot resume "
                f"under policy={self.schedule.policy!r}/straggler="
                f"{self.schedule.straggler!r}, which keeps updates in flight "
                "across aggregation boundaries.  Resume it under "
                "schedule='sync' or deadline+drop, or re-run from scratch to "
                "produce a current-version snapshot."
            )
        if len(meta["device_rng"]) != len(self.ctx.devices):
            raise ValueError(
                f"checkpoint at {latest} was saved with "
                f"{len(meta['device_rng'])} devices but this runner has "
                f"{len(self.ctx.devices)}; resume requires an identical config"
            )
        if (meta["configurator"] is None) != (state.configurator is None):
            raise ValueError(
                f"checkpoint at {latest} disagrees with this runner about the "
                "rate configurator; resume requires the same method/config"
            )
        state.rng.bit_generator.state = meta["rng_state"]
        for dev, rng_state in zip(self.ctx.devices, meta["device_rng"]):
            dev._rng.bit_generator.state = rng_state
        configurator = state.configurator
        if configurator is not None and meta["configurator"] is not None:
            configurator.load_state_dict(meta["configurator"])
        self.state = RoundState(
            key=jnp.asarray(arrays["key"]),
            global_peft=self._peft_native_layout(arrays["global_peft"]),
            device_peft={
                int(d): self._peft_native_layout(t)
                for d, t in arrays["device_peft"].items()
            },
            last_mask={int(d): m for d, m in arrays["last_mask"].items()},
            ef_residual={
                int(d): jax.tree.map(jnp.asarray, t)
                for d, t in arrays.get("ef_residual", {}).items()
            },
            round_index=meta["round_index"],
            global_step=meta["global_step"],
            cum_time=meta["cum_time"],
            # pre-scheduler checkpoints (no virtual clock) resume with
            # virtual_time == cum_time, which is exact for sync rounds
            virtual_time=meta.get("virtual_time", meta["cum_time"]),
            server_version=meta.get("server_version", meta["round_index"]),
            prev_acc={int(d): v for d, v in meta["prev_acc"].items()},
            rng=state.rng,
            configurator=configurator,
            history=tuple(meta["history"]),
        )
        if sched_meta is not None:
            self.scheduler.load_state_dict(
                arrays.get("scheduler_jobs", []), sched_meta
            )


def run_replicates(
    seeds: Sequence[int],
    cfg,
    peft_cfg,
    stld_cfg,
    fed_cfg,
    train_cfg,
    *,
    algorithm="droppeft",
    rounds: Optional[int] = None,
    target_accuracy: Optional[float] = None,
    **runner_kwargs,
) -> List[SimResult]:
    """Multi-seed replication: one independent runner (fresh task partition,
    device profiles, and model init) per seed."""
    results = []
    for seed in seeds:
        runner = ExperimentRunner(
            cfg, peft_cfg, stld_cfg, fed_cfg, train_cfg,
            algorithm=fresh_algorithm(algorithm), seed=seed, **runner_kwargs,
        )
        results.append(runner.run(rounds=rounds, target_accuracy=target_accuracy))
    return results
