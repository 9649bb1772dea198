"""Declarative jaxpr contracts over the registered algorithms' traced programs.

Every registered :class:`~repro.federated.algorithms.base.FederatedAlgorithm`
gets its client step, aggregation body, and (once, shared) the serving decode
loop traced at smoke scale, and the traces are checked against structural and
cost-scaling contracts:

``restack``       no ``concatenate`` whose output shape matches a stacked
                  base-layer leaf — trace-time re-stacking of the
                  stacked-native layout (the PR-3 acceptance contract,
                  generalized from ``tests/test_stacked_layout.py``).
``dtype64``       no float64 intermediate anywhere in the traced program —
                  a silent f32→f64 promotion doubles memory and flops.
``callback``      no host-callback / infeed primitive inside a traced round
                  body — one host round-trip per round multiplies by the
                  population size.
``leaf-budget``   the client call signature has the same number of program
                  inputs at L layers and 2L layers (the O(k), L-independent
                  dispatch contract).
``flops-linear``  estimated program FLOPs scale linearly (positive slope)
                  with the STLD active fraction in gather mode.
``bytes-linear``  XLA ``cost_analysis()`` bytes-accessed scales linearly
                  with the active fraction.
``finite-guard``  every traced aggregation program must contain the
                  ``is_finite`` screening guard (``server.screen_finite``)
                  — the in-graph defense that keeps a corrupted client
                  update from poisoning the global PEFT.

FLOPs come from :func:`estimate_flops`, a scan-length-aware jaxpr walker —
XLA's own HLO cost analysis counts a ``scan`` body once regardless of trip
count, so it cannot see active-fraction scaling; bytes-accessed does scale
in the HLO accounting, so that side uses ``lower().cost_analysis()``.

Adding a rule: write a ``check_*`` function that takes a
:class:`ProgramTrace` (or :class:`ScalingCurve`) and returns
:class:`~repro.analysis.report.Violation` rows, register its id in
``CONTRACT_RULES``, and call it from :func:`check_algorithms`.  Exempting a
specific program from a rule is an ``ALLOWLIST`` entry — keyed
``"<algorithm>/<program>"`` with a justification string, never a bare pass.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.report import Violation

FRACTIONS = (0.25, 0.5, 1.0)

# Smoke-scale trace config: tiny dims so one trace is ~a second; num_layers
# stays overridable for the leaf-budget L-doubling check.
_SMOKE_ARCH = "qwen3-1.7b"
_SMOKE_DIMS = dict(
    d_model=32, d_ff=64, num_heads=2, num_kv_heads=2, vocab_size=128,
    dtype="float32",
)


@dataclass(frozen=True)
class ContractRule:
    """One contract: id + human description + fix hint (for reports/docs)."""

    rule_id: str
    description: str
    hint: str


CONTRACT_RULES: Dict[str, ContractRule] = {
    r.rule_id: r
    for r in (
        ContractRule(
            "restack",
            "no traced concatenate may rebuild a stacked base-layer leaf",
            "keep params in the stacked-native layout end to end; stack once "
            "outside jit (see models/stacking.py), never inside a traced body",
        ),
        ContractRule(
            "dtype64",
            "no float64 intermediate in a traced program",
            "an op promoted to f64 (python float arithmetic on tracers, "
            "np.float64 constants); cast the operand to the compute dtype",
        ),
        ContractRule(
            "callback",
            "no host callback / infeed primitive inside a traced round body",
            "move the host-side work outside jit, or precompute it and pass "
            "the result in as an argument",
        ),
        ContractRule(
            "leaf-budget",
            "client dispatch signature must not scale with the layer count",
            "a per-layer list leaked into the call signature; pass the "
            "stacked (L, ...) tree instead",
        ),
        ContractRule(
            "flops-linear",
            "program FLOPs must scale linearly with the STLD active fraction",
            "a dense-over-L computation ignores the gather-mode active set; "
            "route layer work through the gathered (k, ...) stack",
        ),
        ContractRule(
            "bytes-linear",
            "bytes-accessed must scale linearly with the STLD active fraction",
            "per-layer params are touched even for dropped layers; gather "
            "the k active layers before the scan instead of masking after",
        ),
        ContractRule(
            "finite-guard",
            "traced aggregation must contain the non-finite screening guard",
            "route the aggregated tree through server.screen_finite (or an "
            "equivalent jnp.isfinite select) as the last step of the traced "
            "aggregation body",
        ),
        ContractRule(
            "uplink-callback",
            "the traced uplink path (dequantize → densify → aggregate) must "
            "not round-trip through the host",
            "a silent device_get / callback between dequantization and the "
            "reduce serializes every cohort member through host memory; keep "
            "the dequantize-and-merge pipeline inside one traced program",
        ),
    )
}

# rule id -> {"<algorithm>/<program>": justification}.  An entry exempts one
# traced program from one rule; the justification is printed with --list.
ALLOWLIST: Dict[str, Dict[str, str]] = {
    "restack": {},
    "dtype64": {},
    "callback": {},
    "finite-guard": {},
    "uplink-callback": {},
}


def allowlisted(rule_id: str, where: str) -> bool:
    return where in ALLOWLIST.get(rule_id, {})


# --------------------------------------------------------------- jaxpr walks
def walk_eqns(jaxpr) -> Iterable:
    """Yield every eqn in ``jaxpr`` (an open ``Jaxpr`` or ``ClosedJaxpr``),
    descending into pjit / scan / cond / custom-call sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _subjaxprs(eqn):
            yield from walk_eqns(inner)


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def stacking_concats(jaxpr, target_shapes) -> List:
    """Concatenate eqns whose output shape matches a stacked layer-leaf shape
    — i.e. trace-time re-stacking of the stacked-native layout."""
    targets = {tuple(s) for s in target_shapes}
    return [
        eqn
        for eqn in walk_eqns(jaxpr)
        if eqn.primitive.name == "concatenate"
        and any(tuple(ov.aval.shape) in targets for ov in eqn.outvars)
    ]


def stacked_leaf_shapes(tree) -> frozenset:
    """Shapes of the stacked layer leaves of ``tree`` (stacking it first if
    it still is a per-layer list)."""
    from repro.models import stacking

    if not stacking.is_stacked(tree):
        tree = stacking.stack_params(tree)
    return frozenset(tuple(x.shape) for x in jax.tree.leaves(tree))


# ------------------------------------------------------------ FLOP estimator
def _size(aval) -> float:
    return float(math.prod(aval.shape)) if aval.shape else 1.0


def estimate_flops(jaxpr) -> float:
    """Scan-length-aware FLOP estimate of a jaxpr.

    XLA's HLO ``cost_analysis()`` counts a ``scan`` body once regardless of
    trip count, so it cannot see gather-mode active-fraction scaling; this
    walker multiplies a scan body by its ``length``, takes the max over
    ``cond`` branches, and counts ``dot_general`` exactly
    (2 · |out| · contraction).  Elementwise / data-movement ops count one
    unit per output element — coarse, but exact enough for *linearity*
    contracts (the estimate is a fixed polynomial in the trip counts)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lhs_contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            contraction = 1.0
            for d in lhs_contract:
                contraction *= lhs.shape[d]
            total += 2.0 * _size(eqn.outvars[0].aval) * contraction
        elif name == "scan":
            length = eqn.params["length"]  # host-side eqn metadata, not a tracer
            total += float(length) * estimate_flops(eqn.params["jaxpr"])
        elif name == "while":
            # unknown trip count: count one iteration (a lower bound; still
            # monotone in the body cost)
            total += estimate_flops(eqn.params["body_jaxpr"])
            total += estimate_flops(eqn.params["cond_jaxpr"])
        elif name == "cond":
            total += max(
                estimate_flops(b) for b in eqn.params["branches"]
            )
        else:
            nested = list(_subjaxprs(eqn))
            if nested:
                total += sum(estimate_flops(j) for j in nested)
            else:
                total += sum(_size(ov.aval) for ov in eqn.outvars)
    return total


# ------------------------------------------------------------- trace records
@dataclass(frozen=True)
class ProgramTrace:
    """One traced program plus the metadata the structural rules need."""

    where: str                    # "<algorithm>/<program>" report key
    jaxpr: object                 # ClosedJaxpr
    stacked_shapes: frozenset     # restack targets; empty set disables
    num_inputs: int               # len(jaxpr.jaxpr.invars)


@dataclass(frozen=True)
class ScalingCurve:
    """Cost measurements of one program family across active fractions."""

    where: str
    fractions: Tuple[float, ...]
    flops: Tuple[float, ...]
    bytes_accessed: Tuple[float, ...]


def make_trace(where: str, jaxpr, stacked_shapes=frozenset()) -> ProgramTrace:
    return ProgramTrace(
        where=where,
        jaxpr=jaxpr,
        stacked_shapes=frozenset(tuple(s) for s in stacked_shapes),
        num_inputs=len(jaxpr.jaxpr.invars),
    )


# -------------------------------------------------------------- rule checks
_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "host_callback_call",
    "outside_call", "infeed", "outfeed",
}


def check_trace_rules(trace: ProgramTrace) -> List[Violation]:
    """Run the structural rules (restack / dtype64 / callback) on one trace."""
    out: List[Violation] = []

    if trace.stacked_shapes and not allowlisted("restack", trace.where):
        concats = stacking_concats(trace.jaxpr, trace.stacked_shapes)
        if concats:
            shapes = sorted(
                {tuple(ov.aval.shape) for e in concats for ov in e.outvars}
            )
            out.append(
                Violation(
                    "restack", trace.where,
                    f"{len(concats)} traced concatenate(s) rebuild stacked "
                    f"layer leaves (shapes {shapes})",
                    CONTRACT_RULES["restack"].hint,
                )
            )

    if not allowlisted("dtype64", trace.where):
        f64 = np.dtype("float64")
        bad = sorted(
            {
                eqn.primitive.name
                for eqn in walk_eqns(trace.jaxpr)
                for ov in eqn.outvars
                if getattr(ov.aval, "dtype", None) == f64
            }
        )
        if bad:
            out.append(
                Violation(
                    "dtype64", trace.where,
                    f"float64 intermediates produced by: {', '.join(bad)}",
                    CONTRACT_RULES["dtype64"].hint,
                )
            )

    if not allowlisted("callback", trace.where):
        cbs = sorted(
            {
                eqn.primitive.name
                for eqn in walk_eqns(trace.jaxpr)
                if eqn.primitive.name in _CALLBACK_PRIMS
                or "callback" in eqn.primitive.name
            }
        )
        if cbs:
            out.append(
                Violation(
                    "callback", trace.where,
                    f"host callback primitive(s) in traced body: {', '.join(cbs)}",
                    CONTRACT_RULES["callback"].hint,
                )
            )
    return out


def check_finite_guard(trace: ProgramTrace) -> List[Violation]:
    """finite-guard: unlike the structural *absence* rules, this one
    requires a primitive to be *present* — at least one ``is_finite`` eqn
    (the lowering of ``jnp.isfinite`` inside ``server.screen_finite``)
    anywhere in the traced aggregation program."""
    if allowlisted("finite-guard", trace.where):
        return []
    for eqn in walk_eqns(trace.jaxpr):
        if eqn.primitive.name == "is_finite":
            return []
    return [
        Violation(
            "finite-guard", trace.where,
            "no is_finite primitive anywhere in the traced aggregation "
            "program: a non-finite client update would flow straight into "
            "the global PEFT",
            CONTRACT_RULES["finite-guard"].hint,
        )
    ]


def check_uplink(trace: ProgramTrace) -> List[Violation]:
    """uplink-callback: the dequantize→densify→aggregate program must stay
    on device end to end — any callback/infeed primitive means a host
    round-trip inside the compressed-uplink hot path."""
    if allowlisted("uplink-callback", trace.where):
        return []
    cbs = sorted(
        {
            eqn.primitive.name
            for eqn in walk_eqns(trace.jaxpr)
            if eqn.primitive.name in _CALLBACK_PRIMS
            or "callback" in eqn.primitive.name
        }
    )
    if cbs:
        return [
            Violation(
                "uplink-callback", trace.where,
                f"host round-trip between dequantize and reduce: {', '.join(cbs)}",
                CONTRACT_RULES["uplink-callback"].hint,
            )
        ]
    return []


def check_leaf_budget(trace: ProgramTrace, trace_2l: ProgramTrace) -> List[Violation]:
    """O(k) dispatch: the program input count may not grow with L."""
    if trace.num_inputs != trace_2l.num_inputs:
        return [
            Violation(
                "leaf-budget", trace.where,
                f"program inputs grow with the layer count: "
                f"{trace.num_inputs} at L vs {trace_2l.num_inputs} at 2L",
                CONTRACT_RULES["leaf-budget"].hint,
            )
        ]
    return []


def _linearity(xs: Sequence[float], ys: Sequence[float]):
    """Least-squares line through (xs, ys): (slope, max relative residual)."""
    n = len(xs)
    xm, ym = sum(xs) / n, sum(ys) / n
    sxx = sum((x - xm) ** 2 for x in xs)
    sxy = sum((x - xm) * (y - ym) for x, y in zip(xs, ys))
    slope = sxy / sxx
    intercept = ym - slope * xm
    scale = max(abs(ym), 1e-30)
    resid = max(abs(intercept + slope * x - y) for x, y in zip(xs, ys)) / scale
    return slope, resid


def check_curve(curve: ScalingCurve, *, tol: float = 0.02) -> List[Violation]:
    """flops-linear + bytes-linear: both cost measures must fit a positive-
    slope line over the active fractions within ``tol`` relative residual."""
    out: List[Violation] = []
    for rule_id, ys in (
        ("flops-linear", curve.flops),
        ("bytes-linear", curve.bytes_accessed),
    ):
        if allowlisted(rule_id, curve.where):
            continue
        slope, resid = _linearity(curve.fractions, ys)
        if slope <= 0:
            out.append(
                Violation(
                    rule_id, curve.where,
                    f"cost does not grow with the active fraction "
                    f"(slope {slope:.3g}; points {list(zip(curve.fractions, ys))})",
                    CONTRACT_RULES[rule_id].hint,
                )
            )
        elif resid > tol:
            out.append(
                Violation(
                    rule_id, curve.where,
                    f"cost is not linear in the active fraction "
                    f"(relative residual {resid:.3g} > {tol}; "
                    f"points {list(zip(curve.fractions, ys))})",
                    CONTRACT_RULES[rule_id].hint,
                )
            )
    return out


# ------------------------------------------------------- program construction
_TRAIN = None  # lazy: repro.configs import kept out of module import time
_trace_cache: Dict[tuple, object] = {}


def _train_cfg():
    global _TRAIN
    if _TRAIN is None:
        from repro.configs import TrainConfig

        _TRAIN = TrainConfig(learning_rate=5e-3, total_steps=100, warmup_steps=2)
    return _TRAIN


def _smoke_cfg(num_layers: int = 4):
    from repro.configs import get_config

    return get_config(_SMOKE_ARCH, smoke=True).replace(
        num_layers=num_layers, **_SMOKE_DIMS
    )


def _client_setup(num_layers, peft_method, lora_rank, stld_cfg):
    """Client fns + stacked args at smoke scale (mirrors the layout tests)."""
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib
    from repro.federated.client import make_client_fns
    from repro.models.registry import init_params
    from repro.optim import adamw_init

    cfg = _smoke_cfg(num_layers)
    pcfg = PEFTConfig(method=peft_method, lora_rank=lora_rank, adapter_dim=4)
    fns = make_client_fns(cfg, pcfg, stld_cfg, _train_cfg(), donate=False)
    key = jax.random.PRNGKey(0)
    base = init_params(key, cfg)
    peft = peft_lib.init_peft(key, cfg, pcfg)
    batches = {
        "tokens": jnp.zeros((2, 4, 8), dtype=jnp.int32),
        "targets": jnp.zeros((2, 4, 8), dtype=jnp.int32),
        "mask": jnp.ones((2, 4, 8), dtype=jnp.float32),
    }
    args = (
        base, peft, adamw_init(peft), batches,
        jnp.asarray(0.5, jnp.float32), key, jnp.asarray(0, jnp.int32),
    )
    return fns, base, args


def _peft_family(name: str) -> Tuple[str, int]:
    """(peft method, lora rank) the algorithm's client programs run with."""
    if name in ("fedadapter", "fedadaopt"):
        return "adapter", 2
    if name == "fedhetlora":
        return "lora", 16  # the max-rank tier's client program
    return "lora", 2


def _merge_family(name: str) -> str:
    if name == "fedhetlora":
        return "hetlora"
    if name.startswith("droppeft") and name != "droppeft_b3":
        return "ptls"
    return "fedavg"


def client_trace(peft_method, lora_rank, stld_enabled, *, num_layers=4,
                 where="client_step") -> ProgramTrace:
    """Structural trace of the jit'd local round in its configured mode."""
    from repro.configs import STLDConfig

    key = ("client", peft_method, lora_rank, stld_enabled, num_layers)
    cached = _trace_cache.get(key)
    if cached is None:
        scfg = STLDConfig(mode="cond", mean_rate=0.5, enabled=stld_enabled)
        fns, base, args = _client_setup(num_layers, peft_method, lora_rank, scfg)
        closed = jax.make_jaxpr(lambda *a: fns.local_round(*a))(*args)
        cached = (closed, stacked_leaf_shapes(base["layers"]))
        _trace_cache[key] = cached
    closed, shapes = cached
    return make_trace(where, closed, shapes)


def client_scaling_curve(peft_method, lora_rank, *, fractions=FRACTIONS,
                         num_layers=4, where="client_step") -> ScalingCurve:
    """Gather-mode cost curve: trace the local round at each static active
    count k = round(fraction · L) and measure FLOPs (jaxpr estimate) and
    bytes accessed (XLA cost analysis)."""
    from repro.configs import STLDConfig

    key = ("curve", peft_method, lora_rank, tuple(fractions), num_layers)
    cached = _trace_cache.get(key)
    if cached is None:
        scfg = STLDConfig(mode="gather", mean_rate=0.5, gather_bucket=1)
        fns, _, args = _client_setup(num_layers, peft_method, lora_rank, scfg)
        flops, nbytes = [], []
        for frac in fractions:
            k = max(1, round(frac * num_layers))
            closed = jax.make_jaxpr(
                lambda *a: fns.local_round(*a, num_active=k)
            )(*args)
            flops.append(estimate_flops(closed))
            cost = fns.local_round.lower(*args, num_active=k).cost_analysis()
            # repro-lint: disable=JXH002 — cost_analysis() is a host-side dict
            nbytes.append(float(cost["bytes accessed"]))
        cached = (tuple(flops), tuple(nbytes))
        _trace_cache[key] = cached
    flops, nbytes = cached
    return ScalingCurve(where, tuple(fractions), flops, nbytes)


def aggregation_trace(family: str, *, where="aggregate") -> ProgramTrace:
    """Trace the merge family's aggregation body over a 3-client cohort."""
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib
    from repro.federated import server as server_lib

    key = ("agg", family)
    cached = _trace_cache.get(key)
    if cached is None:
        cfg = _smoke_cfg(4)
        prng = jax.random.PRNGKey(0)
        n = 3
        if family == "hetlora":
            ranks = (2, 4)
            clients = [
                peft_lib.init_peft(
                    prng, cfg, PEFTConfig(method="lora", lora_rank=r)
                )
                for r in ranks
            ]
            closed = jax.make_jaxpr(
                lambda *cs: server_lib.hetlora_aggregate(
                    list(cs), list(ranks), max(ranks)
                )
            )(*clients)
            shapes = stacked_leaf_shapes(clients[-1])
        else:
            pcfg = PEFTConfig(method="lora", lora_rank=2)
            gpeft = peft_lib.init_peft(prng, cfg, pcfg)
            if family == "ptls":
                cohort = jax.tree.map(
                    lambda x: jnp.stack([x] * n), gpeft
                )
                masks = np.ones((n, cfg.num_layers), dtype=bool)
                closed = jax.make_jaxpr(
                    lambda cp, gp: server_lib.ptls_aggregate(cp, masks, gp)
                )(cohort, gpeft)
            else:  # fedavg
                closed = jax.make_jaxpr(
                    lambda ts: server_lib.fedavg(ts)
                )([gpeft] * n)
            shapes = stacked_leaf_shapes(gpeft)
        cached = (closed, shapes)
        _trace_cache[key] = cached
    closed, shapes = cached
    return make_trace(where, closed, shapes)


def uplink_trace(family: str, *, where="uplink") -> ProgramTrace:
    """Trace the compressed-uplink server path for one merge family: int8
    payloads (from top-k'd deltas) in, dequantize, densify, aggregate — all
    inside one ``make_jaxpr`` so :func:`check_uplink` can prove the pipeline
    never leaves the device."""
    from repro.configs import PEFTConfig
    from repro.core import peft as peft_lib
    from repro.federated import compression as comp_lib
    from repro.federated import server as server_lib

    key = ("uplink", family)
    cached = _trace_cache.get(key)
    if cached is None:
        cfg = _smoke_cfg(4)
        prng = jax.random.PRNGKey(0)
        n = 3
        if family == "hetlora":
            ranks = (2, 4, 4)
            clients = [
                peft_lib.init_peft(
                    prng, cfg, PEFTConfig(method="lora", lora_rank=r)
                )
                for r in ranks
            ]
        else:
            gpeft = peft_lib.init_peft(
                prng, cfg, PEFTConfig(method="lora", lora_rank=2)
            )
            clients = [gpeft] * n
        wire = [
            comp_lib.quantize_int8(comp_lib.topk_sparsify(c, 0.25))
            for c in clients
        ]
        vals = [v for v, _ in wire]
        scales = [s for _, s in wire]
        if family == "hetlora":

            def fn(vals, scales):
                dense = [
                    comp_lib.dequantize_int8(v, s) for v, s in zip(vals, scales)
                ]
                return server_lib.hetlora_aggregate(dense, list(ranks), max(ranks))

            closed = jax.make_jaxpr(fn)(vals, scales)
            shapes = stacked_leaf_shapes(clients[-1])
        elif family == "ptls":
            masks = np.ones((n, cfg.num_layers), dtype=bool)

            def fn(vals, scales, gp):
                dense = [
                    comp_lib.dequantize_int8(v, s) for v, s in zip(vals, scales)
                ]
                cohort = jax.tree.map(lambda *xs: jnp.stack(xs), *dense)
                return server_lib.ptls_aggregate(cohort, masks, gp)

            closed = jax.make_jaxpr(fn)(vals, scales, clients[0])
            shapes = stacked_leaf_shapes(clients[0])
        else:  # fedavg

            def fn(vals, scales):
                dense = [
                    comp_lib.dequantize_int8(v, s) for v, s in zip(vals, scales)
                ]
                return server_lib.fedavg(dense)

            closed = jax.make_jaxpr(fn)(vals, scales)
            shapes = stacked_leaf_shapes(clients[0])
        cached = (closed, shapes)
        _trace_cache[key] = cached
    closed, shapes = cached
    return make_trace(where, closed, shapes)


def decode_trace(*, where="serving/decode", num_tokens=4) -> ProgramTrace:
    """Trace the greedy KV-cache decode loop at smoke scale (shared across
    algorithms — serving is method-independent)."""
    key = ("decode", num_tokens)
    cached = _trace_cache.get(key)
    if cached is None:
        from repro.launch.steps import make_serve_step
        from repro.models.registry import init_params
        from repro.models.transformer import init_caches
        from repro.serving.decode import generate

        cfg = _smoke_cfg(4)
        params = init_params(jax.random.PRNGKey(0), cfg)
        serve = make_serve_step(cfg)
        caches = init_caches(cfg, 2, 16, dtype=jnp.dtype(cfg.dtype))
        first = jnp.zeros((2, 1), dtype=jnp.int32)
        closed = jax.make_jaxpr(
            lambda p, c, t: generate(serve, p, c, t, 8, num_tokens)[0]
        )(params, caches, first)
        cached = (closed, stacked_leaf_shapes(params["layers"]))
        _trace_cache[key] = cached
    closed, shapes = cached
    return make_trace(where, closed, shapes)


def batched_decode_trace(
    *, where="serving/batched_decode", num_layers=4, num_tokens=4
) -> ProgramTrace:
    """Trace one multi-tenant batched decode step: pooled mixed-rank
    adapters (segmented gather kernel), stacked batched KV caches, per-row
    positions.  The pooled peft and the stacked caches are both O(k)-leaf
    trees, so this program must satisfy the same leaf budget as training.
    """
    key = ("batched_decode", num_layers, num_tokens)
    cached = _trace_cache.get(key)
    if cached is None:
        from repro.configs import PEFTConfig
        from repro.core import peft as peft_lib
        from repro.launch.steps import make_serve_step
        from repro.models.registry import init_params
        from repro.serving.adapters import AdapterPoolCache, AdapterRegistry
        from repro.serving.batcher import batched_caches

        cfg = _smoke_cfg(num_layers)
        prng = jax.random.PRNGKey(0)
        params = init_params(prng, cfg)
        registry = AdapterRegistry()
        for i, rank in enumerate((2, 4)):  # hetlora mixed ranks in one pool
            registry.register(
                f"client{i}",
                peft_lib.init_peft(
                    prng, cfg,
                    PEFTConfig(method="lora", lora_rank=rank, lora_targets=("q", "v")),
                ),
            )
        pool = AdapterPoolCache(registry, n_slots=2)
        peft = pool.pooled_peft(jnp.asarray([0, 1], jnp.int32))
        serve = make_serve_step(cfg)
        caches = batched_caches(cfg, 2, 16, dtype=jnp.dtype(cfg.dtype))
        token = jnp.zeros((2, 1), dtype=jnp.int32)
        pos = jnp.zeros((2,), dtype=jnp.int32)
        closed = jax.make_jaxpr(
            lambda p, pf, t, ps, c: serve(p, t, ps, c, peft=pf)[0]
        )(params, peft, token, pos, caches)
        cached = (closed, stacked_leaf_shapes(params["layers"]))
        _trace_cache[key] = cached
    closed, shapes = cached
    return make_trace(where, closed, shapes)


# ----------------------------------------------------------------- top level
def check_algorithms(
    algorithms: Optional[Sequence[str]] = None,
    *,
    fractions: Sequence[float] = FRACTIONS,
    include_decode: bool = True,
    progress=None,
) -> List[Violation]:
    """Run every contract over every (or the named) registered algorithms.

    Traces are cached per program family (droppeft and its ablations share
    one client program), so the full registry costs a handful of traces."""
    from repro.federated import algorithms as alg_pkg

    names = list(algorithms) if algorithms else alg_pkg.registered_methods()
    violations: List[Violation] = []
    for name in names:
        if progress:
            progress(name)
        cls = alg_pkg.get_algorithm(name)
        method, rank = _peft_family(name)

        tr = client_trace(
            method, rank, cls.stld, where=f"{name}/client_step"
        )
        tr_2l = client_trace(
            method, rank, cls.stld, num_layers=8, where=f"{name}/client_step"
        )
        violations += check_trace_rules(tr)
        violations += check_leaf_budget(tr, tr_2l)
        violations += check_curve(
            client_scaling_curve(
                method, rank, fractions=tuple(fractions),
                where=f"{name}/client_step",
            )
        )
        agg_tr = aggregation_trace(_merge_family(name), where=f"{name}/aggregate")
        violations += check_trace_rules(agg_tr)
        violations += check_finite_guard(agg_tr)
        violations += check_uplink(
            uplink_trace(_merge_family(name), where=f"{name}/uplink")
        )
    if include_decode:
        if progress:
            progress("serving/decode")
        violations += check_trace_rules(decode_trace())
        if progress:
            progress("serving/batched_decode")
        btr = batched_decode_trace()
        btr_2l = batched_decode_trace(num_layers=8)
        violations += check_trace_rules(btr)
        violations += check_leaf_budget(btr, btr_2l)
    return violations
