"""Negative fixtures: one deliberately-violating toy program per rule.

Each fixture runs the real analyzer machinery (never a stub) over a program
built to violate exactly one rule and returns the violations found, so

* ``python -m repro.analysis --fixture RULE`` exits nonzero — proof the
  analyzer catches that class of bug, and
* ``python -m repro.analysis --self-test`` asserts every fixture is caught —
  proof a refactor of the analyzer didn't silently blind a rule.

The fixtures are the analyzer's own regression suite; the pytest coverage in
``tests/test_analysis_*.py`` drives them through this module.
"""
from __future__ import annotations

import textwrap
from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_contracts as contracts
from repro.analysis import lint_jax
from repro.analysis.recompile_guard import CompilationCounter
from repro.analysis.report import Violation

FIXTURES: Dict[str, Callable[[], List[Violation]]] = {}


def _fixture(rule_id: str):
    def deco(fn):
        FIXTURES[rule_id] = fn
        return fn

    return deco


def _lint(source: str) -> List[Violation]:
    return lint_jax.lint_source(textwrap.dedent(source), "fixture.py")


# ------------------------------------------------------------- lint fixtures
@_fixture("JXH001")
def key_reuse() -> List[Violation]:
    return _lint(
        """
        import jax

        def two_draws(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """
    )


@_fixture("JXH002")
def host_sync_loop() -> List[Violation]:
    return _lint(
        """
        def pull(rates, pos):
            return [float(rates[i]) for i in pos]
        """
    )


@_fixture("JXH003")
def stale_static_argnames() -> List[Violation]:
    return _lint(
        """
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("mode",))
        def f(x):
            return x * 2
        """
    )


@_fixture("JXH004")
def mutable_default() -> List[Violation]:
    return _lint(
        """
        def accumulate(x, acc=[]):
            acc.append(x)
            return acc
        """
    )


@_fixture("JXH005")
def env_query_in_jit() -> List[Violation]:
    return _lint(
        """
        import jax

        @jax.jit
        def f(x):
            if jax.devices()[0].platform == "cpu":
                return x
            return x * 2
        """
    )


@_fixture("PYL001")
def unused_import() -> List[Violation]:
    return _lint(
        """
        import os

        def f():
            return 1
        """
    )


@_fixture("PYL002")
def shadowed_builtin() -> List[Violation]:
    return _lint(
        """
        def head(list):
            return list[0]
        """
    )


# --------------------------------------------------------- contract fixtures
@_fixture("restack")
def traced_restack() -> List[Violation]:
    """A per-layer list stacked INSIDE the traced program — the layout bug
    the stacked-native refactor removed."""
    num_layers, d = 4, 8
    layers = [jnp.zeros((d,)) for _ in range(num_layers)]

    def f(ls):
        stacked = jnp.stack(ls)  # (L, d) rebuilt at trace time
        return jnp.sum(stacked * 2.0)

    closed = jax.make_jaxpr(f)(layers)
    trace = contracts.make_trace("fixture/restack", closed, {(num_layers, d)})
    return contracts.check_trace_rules(trace)


@_fixture("dtype64")
def silent_f64() -> List[Violation]:
    """An f32 input promoted to f64 mid-program (x64 mode makes the
    promotion representable, exactly as a production x64 run would)."""
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: jnp.sum(x.astype(jnp.float64) * 2.0)
        )(jnp.zeros((4,), jnp.float32))
    trace = contracts.make_trace("fixture/dtype64", closed)
    return contracts.check_trace_rules(trace)


@_fixture("callback")
def host_callback_in_body() -> List[Violation]:
    """A pure_callback smuggled into a traced body — one host round-trip per
    execution."""
    import numpy as np

    def f(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            x,
        )
        return jnp.sum(y)

    closed = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
    trace = contracts.make_trace("fixture/callback", closed)
    return contracts.check_trace_rules(trace)


@_fixture("leaf-budget")
def per_layer_signature() -> List[Violation]:
    """A client signature that takes one argument per layer — the O(L·k)
    dispatch shape the stacked layout retired."""

    def trace(num_layers):
        layers = [jnp.zeros((8,)) for _ in range(num_layers)]
        closed = jax.make_jaxpr(lambda ls: sum(ls) * 2.0)(layers)
        return contracts.make_trace("fixture/leaf-budget", closed)

    return contracts.check_leaf_budget(trace(4), trace(8))


def _flat_cost_curve() -> contracts.ScalingCurve:
    """A fake gather-mode program that runs dense over ALL layers and only
    pretends to honor the static active count — its cost curve is flat."""
    num_layers, d = 4, 16
    weights = jnp.ones((num_layers, d, d), jnp.float32)
    x = jnp.ones((d,), jnp.float32)

    @partial(jax.jit, static_argnames=("k",))
    def f(x, weights, k: int):
        def body(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, x, weights)  # k never gathers anything
        return h

    flops, nbytes = [], []
    for frac in contracts.FRACTIONS:
        k = max(1, round(frac * num_layers))
        closed = jax.make_jaxpr(lambda x, w: f(x, w, k=k))(x, weights)
        flops.append(contracts.estimate_flops(closed))
        cost = f.lower(x, weights, k=k).cost_analysis()
        # repro-lint: disable=JXH002 — cost_analysis() is a host-side dict
        nbytes.append(float(cost["bytes accessed"]))
    return contracts.ScalingCurve(
        "fixture/flat-cost", contracts.FRACTIONS, tuple(flops), tuple(nbytes)
    )


@_fixture("flops-linear")
def flat_flops() -> List[Violation]:
    return [
        v for v in contracts.check_curve(_flat_cost_curve())
        if v.rule == "flops-linear"
    ]


@_fixture("bytes-linear")
def flat_bytes() -> List[Violation]:
    return [
        v for v in contracts.check_curve(_flat_cost_curve())
        if v.rule == "bytes-linear"
    ]


@_fixture("finite-guard")
def unguarded_aggregation() -> List[Violation]:
    """An aggregation body with the screening guard deleted — a NaN client
    update would average straight into the global PEFT."""
    n, d = 3, 8
    clients = [{"a": jnp.ones((d,)), "b": jnp.ones((d,))} for _ in range(n)]

    def naive_fedavg(trees):
        return jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees)

    closed = jax.make_jaxpr(naive_fedavg)(clients)
    trace = contracts.make_trace("fixture/finite-guard", closed)
    return contracts.check_finite_guard(trace)


@_fixture("uplink-callback")
def host_roundtrip_in_uplink() -> List[Violation]:
    """A dequantize→aggregate pipeline with a pure_callback wedged between
    the two — the silent device_get the uplink contract forbids."""
    import numpy as np

    from repro.federated import compression as comp_lib

    n, d = 3, 8
    clients = [{"a": jnp.ones((d,)), "b": jnp.ones((d,))} for _ in range(n)]
    wire = [comp_lib.quantize_int8(c) for c in clients]
    vals = [v for v, _ in wire]
    scales = [s for _, s in wire]

    def fn(vals, scales):
        dense = [comp_lib.dequantize_int8(v, s) for v, s in zip(vals, scales)]
        # the host round-trip: every reconstructed tree bounces off numpy
        dense = [
            jax.tree.map(
                lambda x: jax.pure_callback(
                    lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x
                ),
                t,
            )
            for t in dense
        ]
        return jax.tree.map(lambda *xs: sum(xs) / len(xs), *dense)

    closed = jax.make_jaxpr(fn)(vals, scales)
    trace = contracts.make_trace("fixture/uplink-callback", closed)
    return contracts.check_uplink(trace)


# -------------------------------------------------------- recompile fixture
@_fixture("recompile")
def static_arg_churn() -> List[Violation]:
    """A static argument fed a fresh value per call: one XLA compile each."""
    f = jax.jit(lambda x, s: x + s, static_argnums=(1,))
    with CompilationCounter() as counter:
        for s in range(5):
            f(jnp.float32(1.0), 100 + s)  # offset: never collides with cache
    if counter.count > 1:
        return [
            Violation(
                "recompile",
                "fixture/static-arg-churn",
                f"{counter.count} XLA compilation(s) for 5 calls varying one "
                "static arg (budget 1)",
                "make the varying value a traced argument, or bucket it so "
                "the set of compiled programs is bounded",
            )
        ]
    return []


def run_fixture(rule_id: str) -> List[Violation]:
    """Run one fixture; raises KeyError for an unknown rule id."""
    return FIXTURES[rule_id]()


def self_test() -> Dict[str, bool]:
    """rule id -> was the deliberately-bad program caught by that rule?"""
    return {
        rule_id: any(v.rule == rule_id for v in fn())
        for rule_id, fn in FIXTURES.items()
    }
