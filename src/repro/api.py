"""Single entry point for federated fine-tuning experiments.

Every consumer — benchmarks, examples, the launch driver — builds its
experiment here instead of hand-wiring configs into a simulator::

    from repro import api

    result = api.experiment(method="droppeft", rounds=10, seed=0)
    print(result.final_accuracy, result.time_to_accuracy(0.6, sustained=True))

``experiment`` is the one-shot path; ``build`` returns the underlying
:class:`~repro.federated.runner.ExperimentRunner` when the caller needs the
trained state afterwards (checkpointing, inspection, resuming)::

    runner = api.build(method="droppeft", checkpoint_dir="ckpts")
    result = runner.run(rounds=20, target_accuracy=0.8)
    peft = runner.state.global_peft

``method`` accepts a registered name (``api.list_methods()``), a
:class:`~repro.federated.algorithms.FederatedAlgorithm` instance (e.g. a
custom plugin subclass), or a legacy ``Strategy``.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

from repro.configs import (
    FederatedConfig,
    PEFTConfig,
    STLDConfig,
    TrainConfig,
    get_config,
)
from repro.federated.algorithms import (
    FederatedAlgorithm,
    get_algorithm,
    registered_methods,
)
from repro.federated.compression import CompressionConfig, resolve_compression
from repro.federated.runner import ExperimentRunner, SimResult, fresh_algorithm
from repro.federated.scheduler import ScheduleConfig, resolve_schedule

__all__ = [
    "build",
    "experiment",
    "replicate",
    "serve",
    "list_methods",
    "ScheduleConfig",
    "CompressionConfig",
]


def list_methods() -> List[str]:
    """Names accepted by ``method=`` (the algorithm registry)."""
    return registered_methods()


def _resolve_algorithm(method, fixed_rate: Optional[float]) -> FederatedAlgorithm:
    if isinstance(method, str):
        algorithm: FederatedAlgorithm = get_algorithm(method)()
    elif isinstance(method, FederatedAlgorithm):
        algorithm = method
    else:  # legacy Strategy flag table
        from repro.federated.simulator import algorithm_from_strategy

        algorithm = algorithm_from_strategy(method)
    if fixed_rate is not None:
        # an explicit fixed rate overrides the bandit (0.0 is a valid sweep
        # point: "unset" is spelled None, never falsiness); copy first so a
        # caller-owned instance is never mutated
        algorithm = fresh_algorithm(algorithm)
        algorithm.use_configurator = False
        algorithm.fixed_rate = float(fixed_rate)
    return algorithm


def build(
    method: Union[str, FederatedAlgorithm, object] = "droppeft",
    model: str = "qwen3-1.7b",
    *,
    smoke: bool = True,
    cfg=None,
    model_overrides: Optional[dict] = None,
    # PEFT
    peft: str = "lora",
    lora_rank: Optional[int] = None,
    adapter_dim: Optional[int] = None,
    peft_cfg: Optional[PEFTConfig] = None,
    # STLD
    stld_mode: str = "cond",
    mean_rate: Optional[float] = None,
    distribution: str = "incremental",
    stld_cfg: Optional[STLDConfig] = None,
    # federated round structure
    fed_cfg: Optional[FederatedConfig] = None,
    train_cfg: Optional[TrainConfig] = None,
    # method policy
    fixed_rate: Optional[float] = None,
    # virtual-clock scheduling: a policy name ("sync" | "deadline" |
    # "async-buffer") or a full ScheduleConfig; the scalar kwargs override
    # individual fields of whichever config `schedule` resolves to
    schedule: Union[str, ScheduleConfig, None] = None,
    deadline_s: Optional[float] = None,
    straggler: Optional[str] = None,
    buffer_size: Optional[int] = None,
    staleness_alpha: Optional[float] = None,
    # uplink compression: a level name ("none" | "int8" | "topk" |
    # "int8+topk"), "auto" (joint bandit over levels), a dict of
    # CompressionConfig fields, or a CompressionConfig; None (default) skips
    # the compression machinery entirely — bit-identical to pre-compression
    # rounds
    compression: Union[str, dict, CompressionConfig, None] = None,
    topk_fraction: Optional[float] = None,
    # pinned hardware mix (one profile name per device); None -> sampled
    device_profile: Optional[Sequence[str]] = None,
    # system-model cost scale: None -> the training cfg; an arch name or a
    # ModelConfig -> cost accounting at that (e.g. full 1.7B) scale
    cost_model=None,
    task=None,
    seed: int = 0,
    cohort_mode: str = "auto",
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    # fault injection: a FaultPlan, a kwargs dict, or a JSON-file path
    # (see repro.federated.faults); None runs fault-free
    fault_plan=None,
) -> ExperimentRunner:
    """Construct a fully-wired :class:`ExperimentRunner` (does not run it)."""
    if cfg is None:
        cfg = get_config(model, smoke=smoke)
    if model_overrides:
        cfg = cfg.replace(**model_overrides)
    if peft_cfg is None:
        kw = {"method": peft}
        if lora_rank is not None:
            kw["lora_rank"] = lora_rank
        if adapter_dim is not None:
            kw["adapter_dim"] = adapter_dim
        peft_cfg = PEFTConfig(**kw)
    if stld_cfg is None:
        if mean_rate is None:
            mean_rate = 0.5 if fixed_rate is None else fixed_rate
        stld_cfg = STLDConfig(
            mode=stld_mode, mean_rate=mean_rate, distribution=distribution
        )
    if fed_cfg is None:
        fed_cfg = FederatedConfig()
    if train_cfg is None:
        train_cfg = TrainConfig()
    if isinstance(cost_model, str):
        cost_model = get_config(cost_model)
    return ExperimentRunner(
        cfg,
        peft_cfg,
        stld_cfg,
        fed_cfg,
        train_cfg,
        algorithm=_resolve_algorithm(method, fixed_rate),
        task=task,
        cost_cfg=cost_model,
        seed=seed,
        cohort_mode=cohort_mode,
        schedule=resolve_schedule(
            schedule,
            deadline_s=deadline_s,
            straggler=straggler,
            buffer_size=buffer_size,
            staleness_alpha=staleness_alpha,
        ),
        device_profile=device_profile,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        resume=resume,
        fault_plan=fault_plan,
        compression=resolve_compression(compression, topk_fraction=topk_fraction),
    )


def serve(
    model: str = "qwen3-1.7b",
    *,
    smoke: bool = True,
    cfg=None,
    model_overrides: Optional[dict] = None,
    params=None,
    # adapter sources: a federated checkpoint dir and/or named trees
    checkpoint_dir: Optional[str] = None,
    adapters: Optional[dict] = None,
    lora_alpha: float = 16.0,
    # serving shape
    batch: int = 4,
    max_len: int = 256,
    n_slots: Optional[int] = None,
    cache_dtype: str = "bfloat16",
    seed: int = 0,
):
    """Multi-tenant adapter serving: a ready :class:`ContinuousBatcher`.

    Adapters come from a federated ``save_state`` checkpoint
    (``checkpoint_dir`` — every client's adapter registers as
    ``client<id>``) and/or an explicit ``{name: peft_tree}`` dict.  Submit
    :class:`~repro.serving.batcher.Request`s against adapter names and call
    ``run()``; heterogeneous ranks, prompts, and stop conditions share one
    compiled decode step.
    """
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import make_serve_step
    from repro.models import init_params
    from repro.serving.adapters import AdapterPoolCache, AdapterRegistry
    from repro.serving.batcher import ContinuousBatcher

    if cfg is None:
        cfg = get_config(model, smoke=smoke)
    if model_overrides:
        cfg = cfg.replace(**model_overrides)
    if params is None:
        params = init_params(jax.random.PRNGKey(seed), cfg)
    registry = AdapterRegistry()
    if checkpoint_dir is not None:
        registry.load_checkpoint(checkpoint_dir, alpha=lora_alpha)
    for name, tree in (adapters or {}).items():
        registry.register(name, tree, alpha=lora_alpha)
    if len(registry) == 0:
        raise ValueError("no adapters: pass checkpoint_dir and/or adapters")
    pool = AdapterPoolCache(
        registry, n_slots=n_slots if n_slots is not None else max(batch, len(registry))
    )
    serve_step = make_serve_step(cfg)
    return ContinuousBatcher(
        serve_step,
        params,
        cfg,
        pool,
        batch=batch,
        max_len=max_len,
        cache_dtype=jnp.dtype(cache_dtype),
    )


def experiment(
    method: Union[str, FederatedAlgorithm, object] = "droppeft",
    model: str = "qwen3-1.7b",
    *,
    rounds: Optional[int] = None,
    target_accuracy: Optional[float] = None,
    **kwargs,
) -> SimResult:
    """Build and run one federated experiment; returns its SimResult."""
    runner = build(method, model, **kwargs)
    return runner.run(rounds=rounds, target_accuracy=target_accuracy)


def replicate(
    method: Union[str, FederatedAlgorithm] = "droppeft",
    model: str = "qwen3-1.7b",
    *,
    seeds: Sequence[int] = (0, 1, 2),
    rounds: Optional[int] = None,
    target_accuracy: Optional[float] = None,
    **kwargs,
) -> List[SimResult]:
    """Multi-seed replication: one independent experiment per seed."""
    results = []
    for seed in seeds:
        kw = dict(kwargs)
        kw["seed"] = seed
        # each seed gets a fresh, configuration-preserving algorithm copy so
        # replicates are independent and the caller's instance stays unbound
        runner = build(fresh_algorithm(method), model, **kw)
        results.append(runner.run(rounds=rounds, target_accuracy=target_accuracy))
    return results
