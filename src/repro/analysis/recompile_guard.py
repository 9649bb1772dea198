"""Recompilation guard: count XLA compilations and enforce per-run budgets.

A silent recompile per round — a static argument churning, a shape leaking
into a cache key — multiplies by the round count and, at population scale,
by the client count.  :class:`CompilationCounter` hooks the
``jax.monitoring`` event stream (every XLA backend compile fires one
``/jax/core/compile/backend_compile_duration`` event) so a test or the CLI
can assert a steady-state experiment compiles nothing new:

    with recompile_guard(max_compiles=0, label="droppeft rounds 3-6"):
        runner.run(rounds=6)          # rounds 0-3 already warmed the caches

:func:`check_experiment_recompiles` packages the standard check the CLI
runs: warm a smoke-scale experiment for a few rounds under a schedule
policy, then extend it and require at most the policy's budget of new
programs (0 for sync/deadline — every shape is known after round one;
async-buffer refills dispatch varying cohort sizes, so it gets a small
bounded allowance).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

from repro.analysis.report import Violation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# steady-state budget for NEW programs after a warmed-up multi-round run
DEFAULT_BUDGETS: Dict[str, int] = {
    "sync": 0,
    "deadline": 0,
    # async refills dispatch as many devices as just arrived, so late rounds
    # can still meet a cohort size (and its stack/unstack helpers) the
    # warmup never saw; bounded by the buffer-size grid, not by the rounds
    "async-buffer": 8,
}


class RecompileBudgetExceeded(RuntimeError):
    """A guarded block compiled more XLA programs than its budget."""


class CompilationCounter:
    """Context manager counting XLA backend compilations via jax.monitoring.

    ``seconds`` sums the backend compile durations of those compilations."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompilationCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> bool:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._listen)
        return False


@contextlib.contextmanager
def recompile_guard(max_compiles: int, *, label: str = ""):
    """Assert the with-block compiles at most ``max_compiles`` XLA programs.

    Yields the live :class:`CompilationCounter` (``counter.count`` is
    readable mid-block); raises :class:`RecompileBudgetExceeded` on exit if
    the budget was blown.  Exceptions from the block propagate unchanged."""
    with CompilationCounter() as counter:
        yield counter
    if counter.count > max_compiles:
        raise RecompileBudgetExceeded(
            f"{label or 'guarded block'}: {counter.count} XLA compilation(s), "
            f"budget {max_compiles}"
        )


# ------------------------------------------------------- experiment check
def _quickstart_runner(method: str, policy: str, *, seed: int = 0):
    """A smoke-scale experiment runner matching the test-suite configs."""
    from repro import api
    from repro.configs import FederatedConfig, TrainConfig, get_config
    from repro.data import make_task

    cfg = get_config("qwen3-1.7b", smoke=True).replace(
        num_layers=4, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
        vocab_size=128, dtype="float32",
    )
    return api.build(
        method,
        cfg=cfg,
        fed_cfg=FederatedConfig(
            num_devices=5, devices_per_round=3, local_steps=2, batch_size=8
        ),
        train_cfg=TrainConfig(
            learning_rate=5e-3, total_steps=100, warmup_steps=2
        ),
        task=make_task(num_examples=256, vocab_size=128, seed=0),
        schedule=policy,
        seed=seed,
    )


def check_experiment_recompiles(
    method: str = "droppeft",
    policies: Sequence[str] = ("sync",),
    *,
    warmup_rounds: int = 3,
    extra_rounds: int = 3,
    budgets: Optional[Dict[str, int]] = None,
    progress=None,
) -> List[Violation]:
    """Warm a multi-round experiment per policy, extend it, and require at
    most the policy's budget of newly compiled programs."""
    budgets = dict(DEFAULT_BUDGETS, **(budgets or {}))
    violations: List[Violation] = []
    for policy in policies:
        if progress:
            progress(f"{method}/{policy}")
        runner = _quickstart_runner(method, policy)
        runner.run(rounds=warmup_rounds)  # compiles every steady-state program
        with CompilationCounter() as counter:
            runner.run(rounds=warmup_rounds + extra_rounds)
        if counter.count > budgets[policy]:
            violations.append(
                Violation(
                    "recompile",
                    f"{method}/{policy}",
                    f"{counter.count} XLA compilation(s) in rounds "
                    f"{warmup_rounds}..{warmup_rounds + extra_rounds} "
                    f"(budget {budgets[policy]}) — a shape or static arg is "
                    "churning per round",
                    "make the varying value a traced argument, or bucket it "
                    "so the set of compiled programs is bounded",
                )
            )
    return violations
