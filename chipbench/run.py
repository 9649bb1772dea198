"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json`` (see ``chipbench/harness.py``).  The run builds
the system under test from the seed, warms every program the cell uses,
measures for ``--seconds``, reads the peak device memory, frees the
program's state and compares what the timed path produced with the plain
reference.  With ``--trace 1`` the window is traced and the per-layer
metrics are reported instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, then ``window_compiles`` (programs compiled inside the
window, which should be none), and last ``checks``: each number compared
with its limit.
The same numbers are the last lines of standard error.  Without an
accelerator, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness  # noqa: E402

OUT = ROOT / ".chipbench"  # traces; listed in .gitignore


class Context:
    """What a traffic module gets: the cell's files, the seed, and the window."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.config = cell["config_file"]
        self.traffic = cell["traffic_file"]
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.trace_dir = OUT / f"trace-{cell['name']}"
        self.setup_s = None
        self.window_compiles = None
        self.check_inputs = None  # what the traffic module's check() compares
        from repro.configs.base import ModelConfig

        self.model_config = ModelConfig(**self.config["model"])

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def open_window(self):
        import jax
        from repro.analysis.recompile_guard import CompilationCounter

        self.setup_s = time.perf_counter() - PROCESS_START
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
        self._compiles = CompilationCounter().__enter__()
        self._window_span = self.span("window")
        self._window_span.__enter__()

    def close_window(self):
        import jax

        self._window_span.__exit__(None, None, None)
        self._compiles.__exit__(None, None, None)
        self.window_compiles = self._compiles.count
        if self.trace:
            jax.profiler.stop_trace()


def _device_or_exit(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform not in ("tpu", "gpu") or len(devices) < chips:
        print(
            f"chipbench: the cell needs {chips} accelerator chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s). There is no CPU fallback.",
            file=sys.stderr,
        )
        sys.exit(3)
    return devices


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = _device_or_exit(cell["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                     harness.peaks(devices[0].device_kind))
    print(json.dumps(result), flush=True)
    return 0


def judge(pairs, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all are within."""
    checks = {name: {"value": value, "limit": limits[name]} for name, value in pairs}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return checks, correct


def execute(cell: dict, seed: int, seconds: float, trace: bool, devices, peak: dict) -> dict:
    """One run of ``cell`` on ``devices``: the result line's object.  The
    numbers compared, and the traffic module's counters, go to standard error."""
    ctx = Context(cell, seed, seconds, trace)
    drv = harness.driver(cell["traffic_file"]["driver"])
    record, attempted, failed = drv.run(ctx)

    with ctx.span("metrics_pull"):
        stats = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    gc.collect()
    record.update(setup_s=ctx.setup_s, memory_peak_bytes=memory_peak, peak=peak,
                  window_compiles=ctx.window_compiles, trace=None)
    if ctx.trace:
        from chipbench import trace as trace_lib

        record["trace"] = trace_lib.reduce(trace_lib.read(str(ctx.trace_dir)))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    checks, correct = judge(drv.check(ctx), cell["workload_file"]["limits"])

    metrics = {}
    for m in cell["per_layer"] if ctx.trace else cell["end_to_end"]:
        value = harness.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": cell["chips"],
        "memory_peak_bytes": memory_peak,
    }
    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if ctx.trace:
        device.update(busy_s=record["trace"]["busy_s"], window_s=record["trace"]["window_s"])
        result["breakdown"] = record["trace"]["breakdown"]
    result["window_compiles"] = ctx.window_compiles  # programs compiled inside the window: 0
    result["checks"] = checks

    notes = {k: v for k, v in record.items() if k in ("round", "serve")}
    if ctx.trace:
        top = lambda d: sorted(((k, v[0], v[1]) for k, v in d.items()), key=lambda x: -x[1])[:12]
        notes["trace"] = {"modules": top(record["trace"]["modules"]), "ops": top(record["trace"]["ops"]),
                          "gap_by_span": record["trace"]["gap_by_span"]}
    print(f"window_compiles={ctx.window_compiles} setup_s={ctx.setup_s!r}", file=sys.stderr)
    print(json.dumps(_summary(notes)), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} <= {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    return result


def _summary(notes: dict) -> dict:
    """The traffic module's counters, with long per-step lists reduced to counts."""
    out = {}
    for section, values in notes.items():
        out[section] = {k: (len(v) if isinstance(v, list) and len(v) > 16 else v) for k, v in values.items()}
    return out


if __name__ == "__main__":
    sys.exit(main())
