"""Event-driven virtual-clock scheduler: straggler-aware round execution.

The paper's headline claim is a *wall-clock* one — 1.3–6.3x faster
convergence on heterogeneous Jetson cohorts — yet a barrier-synchronous
round loop lets the :class:`~repro.federated.system_model.SystemModel`'s
per-device times influence only what gets *reported*, never what gets
*trained*.  This module replaces the implicit lock-step loop with a
priority queue of device-completion events driven by
``SystemModel.cohort_round_cost``, behind one :class:`ScheduleConfig`:

* ``sync`` — today's semantics: the round closes when the slowest cohort
  member finishes.  This path calls the algorithm lifecycle hooks in
  exactly the pre-scheduler order and consumes identical RNG streams, so
  its ``SimResult`` is bit-for-bit the PR-2 runner's
  (``tests/test_schedule_parity.py``).
* ``deadline`` — the round closes at ``virtual_time + deadline_s`` (or when
  everyone finishes, whichever is earlier; never before the first
  arrival).  Stragglers are ``"drop"``-ped (their updates are discarded,
  their burned compute still billed) or ``"carry"``-ed (their updates stay
  in flight and aggregate in a later round — with a staleness discount
  when ``staleness_alpha > 0``; the default ``0`` aggregates stale and
  fresh updates at equal weight).  ``deadline_s=inf`` + ``staleness_alpha=0``
  is exactly ``sync``.
* ``async-buffer`` — FedBuff-style: no rounds at the device level.  The
  server aggregates every ``buffer_size`` arrivals with
  staleness-discounted weights ``w_i ∝ 1/(1+s_i)^alpha`` (``s_i`` = server
  versions elapsed since the update's dispatch), then immediately
  dispatches that many replacement devices.  Each aggregation is one
  ``SimResult`` row, so ``time_to_accuracy`` compares policies on the same
  virtual clock.

Event ordering is deterministic: the heap is keyed ``(finish_time,
device_id)`` — ties break by device id, never dict order — and arrival
*sets* come from the event queue while all floating-point reductions
(means, merges) run in dispatch/cohort order, keeping the sync special
case bit-exact and cross-``cohort_mode`` runs reproducible.
"""
from __future__ import annotations

import heapq
import inspect
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.federated import server as server_lib
from repro.federated.faults import FaultInjector, ServerKilled
from repro.federated.state import CohortResults, RoundPlan
from repro.federated.system_model import SystemModel

_POLICIES = ("sync", "deadline", "async-buffer")
_STRAGGLER = ("drop", "carry")


@dataclass(frozen=True)
class ScheduleConfig:
    """How the virtual-clock scheduler closes aggregation steps."""

    policy: str = "sync"             # sync | deadline | async-buffer
    deadline_s: float = math.inf     # round budget (deadline policy)
    straggler: str = "drop"          # drop | carry (deadline policy)
    buffer_size: int = 0             # K arrivals per aggregation (async; 0 -> cohort/2)
    staleness_alpha: float = 0.0     # w = 1/(1+s)^alpha; 0 = uniform (bit-exact fedavg)

    def __post_init__(self):
        if self.policy not in _POLICIES:
            raise ValueError(f"unknown schedule policy {self.policy!r}; one of {_POLICIES}")
        if self.straggler not in _STRAGGLER:
            raise ValueError(f"unknown straggler policy {self.straggler!r}; one of {_STRAGGLER}")
        if not self.deadline_s > 0:
            raise ValueError(f"deadline_s must be positive, got {self.deadline_s}")
        if self.staleness_alpha < 0:
            raise ValueError(f"staleness_alpha must be >= 0, got {self.staleness_alpha}")
        if self.buffer_size < 0:
            raise ValueError(f"buffer_size must be >= 0, got {self.buffer_size}")

    @property
    def keeps_in_flight_state(self) -> bool:
        """True when updates may live across aggregation boundaries.

        These policies checkpoint their in-flight jobs through the
        scheduler's ``state_dict`` (meta version >= 2); a pre-durability
        snapshot (meta version 1, no in-flight section) cannot resume under
        them — the runner raises an actionable error instead."""
        return self.policy == "async-buffer" or (
            self.policy == "deadline" and self.straggler == "carry"
        )


def resolve_schedule(
    schedule: Union[str, ScheduleConfig, None], **overrides
) -> ScheduleConfig:
    """Normalize a policy name / config / None into a ScheduleConfig,
    applying any non-None keyword overrides.

    With no explicit policy, the overrides *infer* one — ``deadline_s`` or
    ``straggler`` implies ``deadline``, ``buffer_size`` implies
    ``async-buffer`` — and options that would be silently dead under
    ``sync`` (every override field) raise instead, so e.g.
    ``api.experiment(..., deadline_s=30)`` can never quietly run a barrier
    experiment while the caller believes they measured deadline
    scheduling."""
    kw = {k: v for k, v in overrides.items() if v is not None}
    if schedule is None:
        if "deadline_s" in kw or "straggler" in kw:
            cfg = ScheduleConfig(policy="deadline")
        elif "buffer_size" in kw:
            cfg = ScheduleConfig(policy="async-buffer")
        elif "staleness_alpha" in kw:
            raise ValueError(
                "staleness_alpha has no effect without a straggler-tolerant "
                "policy; pass schedule='deadline' (straggler='carry') or "
                "schedule='async-buffer'"
            )
        else:
            cfg = ScheduleConfig()
    elif isinstance(schedule, ScheduleConfig):
        cfg = schedule
    elif isinstance(schedule, str):
        cfg = ScheduleConfig(policy=schedule)
    else:
        raise TypeError(f"schedule must be a name or ScheduleConfig, got {schedule!r}")
    if cfg.policy == "sync" and kw:
        raise ValueError(
            f"scheduling options {sorted(kw)} have no effect under the "
            "sync policy; pass schedule='deadline' or schedule='async-buffer'"
        )
    return replace(cfg, **kw) if kw else cfg


def feasible_rate_floor(
    system: SystemModel,
    profiles: Sequence[str],
    deadline_s: float,
    *,
    rate_grid: Sequence[float],
    batch: int,
    seq: int,
    local_steps: int,
    bandwidth_mbps: float = 40.0,
) -> float:
    """Smallest grid rate whose predicted slowest-profile round time fits
    the deadline (expected active fraction ``1 - rate``); the max grid rate
    when even that cannot make it.  Feeds
    :meth:`OnlineConfigurator.set_rate_floor` so deadline-mode exploration
    never wastes rounds on rates that guarantee a dropped straggler."""
    grid = sorted(set(float(r) for r in rate_grid))
    if not grid:
        return 0.0
    profs = sorted(set(profiles))
    for r in grid:
        cost = system.cohort_round_cost(
            devices=profs,
            bandwidth_mbps=bandwidth_mbps,
            batch=batch,
            seq=seq,
            local_steps=local_steps,
            peft=True,
            active_fraction=1.0 - r,
            share_fraction=1.0,
        )
        if float(cost.total_time_s.max()) <= deadline_s:
            return r
    return grid[-1]


@dataclass
class _Job:
    """One in-flight local update: training done eagerly at dispatch (its
    inputs depend only on dispatch-time state), completion deferred to the
    virtual clock."""

    dev: int
    rate: float
    version: int            # server_version at dispatch (staleness base)
    dispatch_round: int
    cohort_pos: int         # position within its dispatch cohort (float order)
    dispatch_time: float
    duration: float         # SystemModel total_time_s
    finish: float           # absolute virtual completion time
    peft: Any
    metrics: dict
    importance: Any
    accuracy: float
    active_frac: float
    mask: np.ndarray        # (L,) bool share-mask row
    compute_s: float
    comm_s: float
    energy_j: float
    traffic_mb: float
    memory_gb: float
    failed: bool = False    # client dropped mid-round (fault injection)
    uplink_peft: Any = None  # server-side reconstruction (compressed uplink)
    comp: str = ""          # compression level this uplink used ("" = none)

    @property
    def order_key(self) -> Tuple[int, int]:
        return (self.dispatch_round, self.cohort_pos)


def _tree_finite(tree) -> bool:
    """Host-side check that every leaf of ``tree`` is finite."""
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


# the _Job scalar fields that ride the JSON checkpoint manifest, with the
# coercion applied on both the save and load sides (state_dict /
# load_state_dict) so the two can never drift apart
_JOB_SCALARS = (
    ("dev", int), ("rate", float), ("version", int), ("dispatch_round", int),
    ("cohort_pos", int), ("dispatch_time", float), ("duration", float),
    ("finish", float), ("accuracy", float), ("active_frac", float),
    ("compute_s", float), ("comm_s", float), ("energy_j", float),
    ("traffic_mb", float), ("memory_gb", float), ("failed", bool),
    ("comp", str),
)

# fields absent from older (pre-compression, meta v2) job records load at
# these defaults instead of KeyError-ing the resume
_JOB_SCALAR_DEFAULTS = {"comp": ""}


class VirtualClockScheduler:
    """Drives one :class:`~repro.federated.runner.ExperimentRunner`'s round
    loop through the configured scheduling policy.

    One ``SimResult`` row per aggregation step for every policy, so time
    axes (``cum_time_s`` = the virtual clock) are directly comparable.
    ``event_log`` records every arrival as ``(round_index, device,
    finish_time)`` in event order — the determinism suite asserts it is
    identical across runs and across batched/sequential cohort modes.
    """

    def __init__(
        self,
        runner,
        cfg: Optional[ScheduleConfig] = None,
        faults: Optional[FaultInjector] = None,
    ):
        self.runner = runner
        self.cfg = cfg or getattr(runner, "schedule", None) or ScheduleConfig()
        self.faults = faults
        self.event_log: List[Tuple[int, int, float]] = []
        self.fault_log: List[dict] = []            # rejected updates + billing
        self._heap: List[Tuple[float, int]] = []   # (finish_time, dev)
        self._jobs: Dict[int, _Job] = {}
        self._backoff: Dict[int, float] = {}       # dev -> earliest re-dispatch t
        self._fail_count: Dict[int, int] = {}      # dev -> consecutive failures

    # ------------------------------------------------------------ public api
    @property
    def in_flight(self) -> frozenset:
        return frozenset(self._jobs)

    def run(self, rounds: Optional[int] = None, target_accuracy: Optional[float] = None):
        runner = self.runner
        total = rounds or runner.ctx.fed_cfg.rounds
        step = {
            "sync": self._sync_round,
            "deadline": self._deadline_round,
            "async-buffer": self._async_step,
        }[self.cfg.policy]
        if self.faults is not None and self.cfg.policy == "sync":
            # the barrier path has no dispatch/arrival machinery to inject
            # into; an infinite-deadline drop round is bit-identical to sync
            # (test_schedule_parity) and routes every completion through the
            # fault-aware event loop
            step = self._deadline_round
        while runner.state.round_index < total:
            with obs.span("round", round=runner.state.round_index):
                row = step(total, target_accuracy)
            hit_target = (
                target_accuracy is not None and row["acc"] >= target_accuracy
            )
            if runner.checkpoint_dir and (
                runner.state.round_index % runner.checkpoint_every == 0
                or runner.state.round_index == total
                or hit_target
            ):
                runner.save_checkpoint()
            if self.faults is not None and self.faults.kills_after(
                runner.state.round_index
            ):
                # the crash-restart drill: the checkpoint (if configured)
                # is already durably renamed into place
                raise ServerKilled(
                    f"fault plan kills the server after round "
                    f"{runner.state.round_index}; rebuild the runner with "
                    "resume=True to continue from the newest checkpoint"
                )
            if hit_target:
                break
        return runner.result()

    # ------------------------------------------------------------- sync path
    def _sync_round(self, total: int, target: Optional[float] = None) -> dict:
        """Today's barrier round, hook for hook — the bit-parity anchor."""
        runner, algo = self.runner, self.runner.algorithm
        state = runner.state
        with obs.span("round.configure"):
            plan = algo.configure_round(state)
            plan.start_pefts = [algo.client_init(state, dev) for dev in plan.cohort]
        state, results = algo.cohort_step(state, plan)
        state, results = algo.compress_uplink(state, results)
        with obs.span("round.aggregate"):
            state = algo.aggregate(state, results)
        with obs.span("round.report"):
            state, row = algo.report(state, results)
            t0 = runner.state.cum_time
            state = replace(
                state,
                round_index=state.round_index + 1,
                history=state.history + (row,),
                virtual_time=state.cum_time,
                server_version=state.server_version + 1,
            )
            runner.state = state
            # log arrivals in event order for the determinism suite
            times = np.asarray(results.cost.total_time_s).tolist()
            for t, dev in sorted(
                zip(times, plan.cohort), key=lambda p: (p[0], p[1])
            ):
                self.event_log.append((plan.round_index, dev, t0 + t))
        return row

    # ------------------------------------------------------------- dispatch
    def _configure_round(self, algo, state, size: Optional[int]) -> RoundPlan:
        """Call ``configure_round`` with the scheduling kwargs when the
        algorithm accepts them; a pre-scheduler subclass that overrides the
        hook with the old one-argument signature still works whenever no
        kwarg is actually needed (sync and deadline-drop), and gets an
        actionable error instead of a bare TypeError otherwise."""
        excl = self._dispatch_exclusions()
        params = inspect.signature(algo.configure_round).parameters
        accepts_kwargs = any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
        ) or ("size" in params and "exclude" in params)
        if accepts_kwargs:
            return algo.configure_round(state, size=size, exclude=excl)
        if size is None and not excl:
            return algo.configure_round(state)
        raise TypeError(
            f"{type(algo).__name__}.configure_round(state) must accept "
            f"size=/exclude= keyword arguments to run under the "
            f"{self.cfg.policy!r} policy with in-flight updates — see "
            "FederatedAlgorithm.configure_round"
        )

    def _dispatch_exclusions(self) -> frozenset:
        """Devices that cannot be dispatched at the current virtual time:
        in flight, backing off after a fault, or churned out of the
        population.  Expired backoffs are purged here, so a recovered
        device re-enters the pool exactly at its retry instant."""
        if self.faults is None:
            return self.in_flight
        t = self.runner.state.virtual_time
        for dev in [d for d, ready in self._backoff.items() if ready <= t]:
            del self._backoff[dev]
        excl = set(self._jobs) | set(self._backoff)
        for dev in range(self.runner.ctx.fed_cfg.num_devices):
            if self.faults.unavailable(dev, t):
                excl.add(dev)
        return frozenset(excl)

    def _next_available_time(self, t: float) -> Optional[float]:
        """Earliest virtual instant strictly after ``t`` when a currently
        excluded device becomes dispatchable (backoff expiry or churn
        rejoin), or None when no such instant exists.  The deadline-aware
        fallback idle-advances the clock here instead of stalling when a
        faulted cohort leaves nothing dispatchable and nothing in flight."""
        times = [ready for ready in self._backoff.values() if ready > t]
        if self.faults is not None:
            for dev in range(self.runner.ctx.fed_cfg.num_devices):
                if dev in self._jobs:
                    continue
                rejoin = self.faults.next_rejoin(dev, t)
                if rejoin is not None and rejoin > t:
                    times.append(rejoin)
        return min(times) if times else None

    def _inject_dispatch_faults(self, job: _Job) -> None:
        """Mutate a freshly-dispatched job per the fault plan: stretch its
        uplink (bandwidth collapse), truncate it at the dropout instant
        (partial work billed, update lost), or corrupt its update to NaN.
        Only the virtual-clock trajectory and billing change — the
        training RNG streams are untouched, so devices unaffected by any
        fault compute bit-identical updates."""
        inj = self.faults
        r, dev = job.dispatch_round, job.dev
        bw = inj.bandwidth_factor_at(r, dev)
        if bw > 1.0:
            extra = job.comm_s * (bw - 1.0)
            job.comm_s *= bw
            job.duration += extra
            self.fault_log.append(
                {
                    "round": r,
                    "dev": dev,
                    "reason": "bandwidth-collapse",
                    "time": job.dispatch_time,
                    "slowdown": bw,
                }
            )
        frac = inj.dropout_at(r, dev)
        if frac is not None:
            # the client vanishes after completing `frac` of its round: all
            # billed quantities scale down, the update never arrives intact
            job.failed = True
            job.duration *= frac
            job.compute_s *= frac
            job.comm_s *= frac
            job.energy_j *= frac
            job.traffic_mb *= frac
        if inj.corrupts(r, dev):
            job.peft = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), job.peft)
            if job.uplink_peft is not None:
                job.uplink_peft = jax.tree.map(
                    lambda x: jnp.full_like(x, jnp.nan), job.uplink_peft
                )
        job.finish = job.dispatch_time + job.duration

    def _dispatch(self, size: Optional[int] = None) -> Tuple[Optional[RoundPlan], List[_Job]]:
        """Sample + train a cohort at the current virtual time and push its
        completion events.  Cost accounting goes through the algorithm's
        ``round_cost`` — the same method the synchronous ``report`` uses —
        so deadline with an infinite budget stays bit-identical to sync."""
        runner, algo = self.runner, self.runner.algorithm
        state = runner.state
        plan = self._configure_round(algo, state, size)
        if not plan.cohort:
            return None, []
        plan.start_pefts = [algo.client_init(state, dev) for dev in plan.cohort]
        state, results = algo.cohort_step(state, plan)
        state, results = algo.compress_uplink(state, results)
        results.masks = algo.compute_masks(state, results)
        cost, active_fracs = algo.round_cost(state, results)
        t0 = state.virtual_time
        # pull each cost vector to python floats once; per-field float(x[i])
        # reads inside the job loop would cost one conversion per element
        rates = [float(r) for r in plan.rates]
        total_s = np.asarray(cost.total_time_s).tolist()
        compute_s = np.asarray(cost.compute_time_s).tolist()
        comm_s = np.asarray(cost.comm_time_s).tolist()
        energy_j = np.asarray(cost.energy_j).tolist()
        traffic_mb = np.asarray(cost.traffic_mb).tolist()
        memory_gb = np.asarray(cost.memory_gb).tolist()
        jobs = []
        for i, dev in enumerate(plan.cohort):
            job = _Job(
                dev=dev,
                rate=rates[i],
                version=state.server_version,
                dispatch_round=plan.round_index,
                cohort_pos=i,
                dispatch_time=t0,
                duration=total_s[i],
                finish=t0 + total_s[i],
                peft=results.pefts[i],
                metrics=results.metrics[i],
                importance=results.importances[i],
                accuracy=results.accuracies[i],
                active_frac=active_fracs[i],
                mask=np.asarray(results.masks[i]),
                compute_s=compute_s[i],
                comm_s=comm_s[i],
                energy_j=energy_j[i],
                traffic_mb=traffic_mb[i],
                memory_gb=memory_gb[i],
                uplink_peft=(
                    results.uplink_pefts[i]
                    if results.uplink_pefts is not None
                    else None
                ),
                comp=plan.compression[i] if plan.compression else "",
            )
            if self.faults is not None:
                self._inject_dispatch_faults(job)
            jobs.append(job)
            self._jobs[dev] = job
            heapq.heappush(self._heap, (job.finish, dev))
        runner.state = state  # key/global_step advanced by cohort_step
        return plan, jobs

    def _pop_arrivals_until(self, close_t: float, round_index: int) -> List[_Job]:
        """Pop every event with ``finish <= close_t`` in (finish, dev) order."""
        arrived = []
        while self._heap and self._heap[0][0] <= close_t:
            finish, dev = heapq.heappop(self._heap)
            job = self._jobs.pop(dev)
            arrived.append(job)
            self.event_log.append((round_index, dev, finish))
        return arrived

    def _pop_k_arrivals(self, k: int, round_index: int) -> List[_Job]:
        arrived = []
        for _ in range(min(k, len(self._heap))):
            finish, dev = heapq.heappop(self._heap)
            job = self._jobs.pop(dev)
            arrived.append(job)
            self.event_log.append((round_index, dev, finish))
        return arrived

    def _screen(self, arrived: List[_Job], round_index: int) -> List[_Job]:
        """Graceful-degradation gate between arrival and aggregation.

        Partitions arrivals into accepted and rejected: a dropped client
        never delivered its update, and a delivered-but-non-finite update
        is screened out before it can poison the global PEFT.  Rejected
        work stays billed (the compute was burned — ``_row`` bills every
        dispatched job), the rejection is recorded in ``fault_log``, and a
        dropped device re-enters the dispatch pool only after an
        exponential virtual-time backoff.  With no injector attached this
        is the identity, and with a zero-fault plan no job is ever
        rejected — both bit-transparent."""
        if self.faults is None:
            return arrived
        ok = []
        for job in sorted(arrived, key=lambda j: j.order_key):
            if job.failed:
                reason = "dropout"
            elif not _tree_finite(
                job.peft if job.uplink_peft is None else job.uplink_peft
            ):
                reason = "non-finite-update"
            else:
                self._fail_count.pop(job.dev, None)
                ok.append(job)
                continue
            entry = {
                "round": round_index,
                "dev": job.dev,
                "reason": reason,
                "time": job.finish,
                "burned_compute_s": job.compute_s,
                "burned_energy_j": job.energy_j,
            }
            if reason == "dropout":
                n = self._fail_count.get(job.dev, 0) + 1
                self._fail_count[job.dev] = n
                retry_at = job.finish + self.faults.backoff_s(n)
                self._backoff[job.dev] = retry_at
                entry["retry_after"] = retry_at
            self.fault_log.append(entry)
        return ok

    # ----------------------------------------------------------- aggregation
    def _aggregate_arrivals(self, arrived: List[_Job], adaopt_depth: int):
        """Apply the algorithm's aggregation to an arrival set, in dispatch
        order (floating-point reductions must not depend on event order),
        with staleness-discounted weights when configured."""
        runner, algo = self.runner, self.runner.algorithm
        state = runner.state
        if not arrived:
            return state, None
        arrived = sorted(arrived, key=lambda j: j.order_key)
        results = CohortResults(
            plan=RoundPlan(
                round_index=state.round_index,
                cohort=[j.dev for j in arrived],
                rates=[j.rate for j in arrived],
                adaopt_depth=adaopt_depth,
                compression=(
                    [j.comp or "none" for j in arrived]
                    if any(j.comp for j in arrived)
                    else None
                ),
            ),
            pefts=[j.peft for j in arrived],
            metrics=[j.metrics for j in arrived],
            importances=[j.importance for j in arrived],
            accuracies=[j.accuracy for j in arrived],
            masks=np.stack([j.mask for j in arrived]),
        )
        if any(j.uplink_peft is not None for j in arrived):
            results.uplink_pefts = [
                j.uplink_peft if j.uplink_peft is not None else j.peft
                for j in arrived
            ]
        staleness = np.array(
            [state.server_version - j.version for j in arrived], dtype=np.int64
        )
        results.staleness = staleness
        if self.cfg.staleness_alpha > 0:
            results.weights = server_lib.staleness_weights(
                staleness, self.cfg.staleness_alpha
            )
        return algo.aggregate(state, results), results

    def _feedback_and_prev_acc(self, state, fb_results, realized, arrived):
        """Reward the configurator with *realized* virtual-clock times and
        advance prev_acc for incorporated updates only."""
        algo = self.runner.algorithm
        algo.feedback(state, fb_results, realized)
        prev_acc = dict(state.prev_acc)
        for job in arrived:
            prev_acc[job.dev] = job.accuracy
        return prev_acc

    # --------------------------------------------------------- deadline path
    def _deadline_round(self, total: int, target: Optional[float] = None) -> dict:
        runner, algo, ctx = self.runner, self.runner.algorithm, self.runner.ctx
        cfg = self.cfg
        t0 = runner.state.virtual_time
        round_index = runner.state.round_index
        plan, jobs = self._dispatch()

        while not self._jobs:
            # deadline-aware fallback: every device is backing off or
            # churned out and nothing is in flight — idle-advance the
            # virtual clock to the next availability instant instead of
            # stalling the queue
            nxt = self._next_available_time(runner.state.virtual_time)
            if nxt is None:
                raise RuntimeError(
                    "deadline scheduler has no dispatchable devices and nothing "
                    "in flight — num_devices is too small for the carry backlog"
                )
            runner.state = replace(runner.state, virtual_time=nxt)
            t0 = nxt
            plan, jobs = self._dispatch()
        state = runner.state
        # close the window: min(deadline, everyone-done), never before the
        # first arrival (a too-tight deadline must still make progress)
        max_fin = max(j.finish for j in self._jobs.values())
        close_t = max_fin
        if math.isfinite(cfg.deadline_s):
            close_t = min(close_t, t0 + cfg.deadline_s)
        min_fin = min(j.finish for j in self._jobs.values())
        close_t = max(close_t, min_fin)
        arrived = self._pop_arrivals_until(close_t, round_index)
        if cfg.straggler == "drop":
            # cut-off updates are discarded; their devices free up next round
            self._heap.clear()
            self._jobs.clear()
        ok = self._screen(arrived, round_index)

        arrived_devs = {j.dev for j in ok}
        state, agg_results = self._aggregate_arrivals(
            ok, plan.adaopt_depth if plan else ctx.cfg.num_layers
        )

        if cfg.straggler == "carry":
            # carried updates are never lost, so bandit feedback waits for
            # the landing: every accepted arrival (on-time or late) reports
            # its full realized duration and trained accuracy — a slow
            # low-dropout arm whose carried updates drive gains is
            # credited, not zeroed.  agg_results already holds the
            # arrivals in dispatch order (its plan cohort/rates match the
            # durations below).  Rejected arrivals carry no usable update
            # and no trained accuracy, so they give the bandit nothing.
            if agg_results is not None:
                ordered = sorted(ok, key=lambda j: j.order_key)
                prev_acc = self._feedback_and_prev_acc(
                    state,
                    agg_results,
                    np.asarray([j.duration for j in ordered], dtype=np.float64),
                    ok,
                )
            else:  # every arrival this window was screened out
                prev_acc = state.prev_acc
        else:
            # drop frees every device each round, so a dispatch plan always
            # exists; feedback covers this round's *dispatched* cohort —
            # arrivals report their realized duration; cut-off stragglers
            # report the deadline they burned and a zero accuracy gain
            # (their update went nowhere)
            assert plan is not None
            chance = 1.0 / ctx.task.num_classes
            fb_accs, realized = [], []
            for job in jobs:
                if job.dev in arrived_devs and job.dispatch_round == round_index:
                    fb_accs.append(job.accuracy)
                    realized.append(job.duration)
                else:
                    fb_accs.append(state.prev_acc.get(job.dev, chance))
                    realized.append(min(job.duration, cfg.deadline_s))
            fb_results = CohortResults(
                plan=plan,
                pefts=[j.peft for j in jobs],
                metrics=[j.metrics for j in jobs],
                importances=[j.importance for j in jobs],
                accuracies=fb_accs,
                masks=np.stack([j.mask for j in jobs]),
            )
            prev_acc = self._feedback_and_prev_acc(
                state, fb_results, np.asarray(realized, dtype=np.float64), ok
            )

        row = self._row(
            close_t,
            arrived=sorted(ok, key=lambda j: j.order_key),
            dispatched=jobs,
        )
        state = replace(
            state,
            cum_time=close_t,
            virtual_time=close_t,
            server_version=state.server_version + 1,
            prev_acc=prev_acc,
            round_index=state.round_index + 1,
            history=state.history + (row,),
        )
        runner.state = state
        return row

    # ------------------------------------------------------------ async path
    def _async_step(self, total: int, target: Optional[float] = None) -> dict:
        runner, ctx = self.runner, self.runner.ctx
        fed = ctx.fed_cfg
        if not self._jobs:
            # prime the pipeline: fill concurrency = devices_per_round
            self._dispatch(size=fed.devices_per_round)
        while not self._jobs:
            # deadline-aware fallback, async flavor: the whole population
            # is backing off or churned out — idle-advance the virtual
            # clock to the next availability instant and re-prime
            nxt = self._next_available_time(runner.state.virtual_time)
            if nxt is None:
                raise RuntimeError("async scheduler drained its event queue")
            runner.state = replace(runner.state, virtual_time=nxt)
            self._dispatch(size=fed.devices_per_round)
        k = self.cfg.buffer_size or max(1, fed.devices_per_round // 2)
        round_index = runner.state.round_index
        arrived = self._pop_k_arrivals(k, round_index)
        if not arrived:
            raise RuntimeError("async scheduler drained its event queue")
        close_t = max(j.finish for j in arrived)  # heap pops are monotone
        ok = self._screen(arrived, round_index)

        state, agg_results = self._aggregate_arrivals(ok, ctx.cfg.num_layers)
        ordered = sorted(ok, key=lambda j: j.order_key)
        if agg_results is not None:
            realized = np.asarray([j.duration for j in ordered], dtype=np.float64)
            prev_acc = self._feedback_and_prev_acc(state, agg_results, realized, ok)
        else:  # the whole buffer was screened out — aggregate nothing
            prev_acc = state.prev_acc
        row = self._row(
            close_t,
            arrived=ordered,
            dispatched=sorted(arrived, key=lambda j: j.order_key),
        )
        if agg_results is not None:
            row["staleness"] = float(np.mean(agg_results.staleness))
        state = replace(
            state,
            cum_time=close_t,
            virtual_time=close_t,
            server_version=state.server_version + 1,
            prev_acc=prev_acc,
            round_index=state.round_index + 1,
            history=state.history + (row,),
        )
        runner.state = state
        # refill the pipeline with as many devices as just arrived (skip
        # once the aggregation budget is spent or the target accuracy was
        # just reached — no point training a cohort whose updates can never
        # land)
        if state.round_index < total and not (
            target is not None and row["acc"] >= target
        ):
            self._dispatch(size=len(arrived))
        return row

    # --------------------------------------------------------- durable state
    def state_dict(self) -> Tuple[list, dict]:
        """Serializable snapshot of every piece of in-flight state.

        Returns ``(jobs_arrays, meta)``: one array tree per in-flight job
        (PEFT update, metrics, importance, share-mask) aligned with the
        ``meta["jobs"]`` scalar records, plus the event/fault logs and the
        retry bookkeeping.  Scalars ride the JSON manifest (Python's float
        repr round-trips exactly); arrays ride the checkpoint npz path
        with dtypes preserved.  :meth:`load_state_dict` rebuilds a
        scheduler that continues bit-identically: the heap is keyed
        ``(finish, dev)``, so re-``heapify``-ing the rebuilt entries pops
        in exactly the original order regardless of internal arrangement.
        """
        jobs = [self._jobs[dev] for dev in sorted(self._jobs)]
        jobs_arrays, job_meta = [], []
        for j in jobs:
            jobs_arrays.append(
                {
                    "peft": j.peft,
                    "metrics": j.metrics,
                    "importance": j.importance if j.importance is not None else [],
                    "mask": j.mask,
                    "uplink_peft": j.uplink_peft if j.uplink_peft is not None else [],
                }
            )
            record = {
                name: cast(getattr(j, name)) for name, cast in _JOB_SCALARS
            }
            record["has_importance"] = j.importance is not None
            record["has_uplink"] = j.uplink_peft is not None
            job_meta.append(record)
        meta = {
            "jobs": job_meta,
            "event_log": [[int(r), int(d), float(t)] for r, d, t in self.event_log],
            "fault_log": list(self.fault_log),
            "backoff": {str(k): float(v) for k, v in self._backoff.items()},
            "fail_count": {str(k): int(v) for k, v in self._fail_count.items()},
        }
        return jobs_arrays, meta

    def load_state_dict(self, jobs_arrays: list, meta: dict) -> None:
        """Rebuild in-flight state saved by :meth:`state_dict`."""
        self._jobs.clear()
        self._heap = []
        for arrs, jm in zip(jobs_arrays, meta["jobs"]):
            # jm holds JSON scalars (never device arrays); the shared field
            # table keeps save/load coercions from drifting apart
            scalars = {
                name: cast(jm[name]) if name in jm else _JOB_SCALAR_DEFAULTS[name]
                for name, cast in _JOB_SCALARS
            }
            job = _Job(
                peft=jax.tree.map(jnp.asarray, arrs["peft"]),
                metrics=arrs["metrics"],
                importance=arrs["importance"] if jm["has_importance"] else None,
                mask=np.asarray(arrs["mask"]),
                uplink_peft=(
                    jax.tree.map(jnp.asarray, arrs["uplink_peft"])
                    if jm.get("has_uplink", False)
                    else None
                ),
                **scalars,
            )
            self._jobs[job.dev] = job
            self._heap.append((job.finish, job.dev))
        heapq.heapify(self._heap)
        self.event_log = [
            (int(r), int(d), float(t)) for r, d, t in meta.get("event_log", [])
        ]
        self.fault_log = list(meta.get("fault_log", []))
        self._backoff = {
            int(k): float(v) for k, v in meta.get("backoff", {}).items()
        }
        self._fail_count = {
            int(k): int(v) for k, v in meta.get("fail_count", {}).items()
        }

    # ------------------------------------------------------------------ rows
    def _row(self, close_t, *, arrived: List[_Job], dispatched: List[_Job]) -> dict:
        """One SimResult history row.

        Accuracy/loss describe what the server aggregated (arrivals);
        rate/active/traffic/energy/memory bill the work dispatched this
        step.  A deadline-*drop* straggler burned only the window, not its
        full round: its energy/traffic are billed pro-rata to the time it
        actually spent before the cut (matching the deadline-capped time
        the bandit sees).  Carried stragglers complete later, so their
        dispatch row bills the full job.  In the sync special case both
        sets coincide, every job finishes inside the window (pro-rata
        factor exactly 1.0), and every reduction runs in cohort order,
        reproducing the barrier row bit-for-bit.
        """
        cut = self.cfg.policy == "deadline" and self.cfg.straggler == "drop"

        def _frac(j: _Job) -> float:
            if not cut or j.finish <= close_t:
                return 1.0
            return max(close_t - j.dispatch_time, 0.0) / j.duration

        if arrived:
            acc = float(np.mean([j.accuracy for j in arrived]))
            loss = float(
                np.mean(
                    np.asarray(
                        jax.device_get([j.metrics["loss"] for j in arrived]),
                        dtype=np.float64,
                    )
                )
            )
        else:  # nothing incorporated: carry the previous row's curve values
            hist = self.runner.state.history
            acc = float(hist[-1]["acc"]) if hist else 0.0
            loss = float(hist[-1]["loss"]) if hist else 0.0
        # only dispatch-time work is billed; a carry round that dispatched
        # nothing (all devices in flight) bills zero — its arrivals were
        # already billed in full at their own dispatch rounds
        billed = dispatched
        return {
            "time": close_t,
            "acc": acc,
            "loss": loss,
            "rate": float(np.mean([j.rate for j in billed])) if billed else 0.0,
            "active": float(np.mean([j.active_frac for j in billed])) if billed else 0.0,
            "traffic": float(np.sum([j.traffic_mb * _frac(j) for j in billed])) if billed else 0.0,
            "energy": float(np.sum([j.energy_j * _frac(j) for j in billed])) if billed else 0.0,
            "memory": float(np.max([j.memory_gb for j in billed])) if billed else 0.0,
            "arrivals": len(arrived),
        }
