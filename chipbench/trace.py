"""Reduction of a profiler trace to device busy time, op and program times,
and idle gaps named by what the host was doing.

A trace is read into plain data, ``[{"name": plane, "lines": [{"name": line,
"events": [(name, start_ns, duration_ns), ...]}]}]``, so the reduction can
be checked on a small trace written by hand.

* The window is the host span ``chipbench.window``; everything is clipped
  to it.
* Busy time of a device is the union of its ``XLA Ops`` intervals; the idle
  share is one less busy over the window.  Several devices are averaged.
* Op and program time sum a device's ``XLA Ops`` and ``XLA Modules``
  events by name, with their counts; the ``(n)`` suffix the runtime adds to
  a program's name is taken off.
* Each idle gap is named by the ``chipbench.*`` host span (other than the
  window) that overlaps it most, or ``other``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
NAME_CHARS = 160  # an op's name is its HLO text, thousands of characters for a loop
_SUFFIX = re.compile(r"\(\d+\)$")


def read(trace_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(events, lo, hi):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def _host_span(spans, starts, a, b):
    """The span among ``spans`` (sorted by start) overlapping [a, b] most."""
    best, most = "other", 0.0
    i = bisect.bisect_left(starts, b) - 1
    while i >= 0:
        name, s0, s1 = spans[i]
        ov = _overlap(a, b, s0, s1)
        if ov > most:
            best, most = name, ov
        if s1 < a and (i == 0 or spans[i - 1][2] < a):
            break
        i -= 1
    return best


def reduce(planes: list, top: int = 10) -> dict:
    host_spans = [
        (name, start, start + dur)
        for plane in planes if plane["name"].startswith("/host")
        for line in plane["lines"]
        for name, start, dur in line["events"]
        if name.startswith(SPAN_PREFIX)
    ]
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = windows[0]
    spans = sorted((s for s in host_spans if s[0] != WINDOW), key=lambda s: s[1])
    starts = [s[1] for s in spans]
    devices = [p for p in planes if p["name"].startswith("/device:") and any(
        line["name"] == "XLA Ops" for line in p["lines"])]
    if not devices:
        seen = {p["name"]: [line["name"] for line in p["lines"]] for p in planes}
        raise ValueError(f"no device plane with an 'XLA Ops' line in the trace: {seen}")

    busy, ops, modules, gaps = [], defaultdict(lambda: [0.0, 0]), defaultdict(lambda: [0.0, 0]), []
    for plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        intervals = []
        for name, a, b in _clip(lines.get("XLA Ops", []), w0, w1):
            intervals.append((a, b))
            ops[name][0] += (b - a) / len(devices)
            ops[name][1] += 1 / len(devices)
        merged = _union(intervals)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in _clip(lines.get("XLA Modules", []), w0, w1):
            entry = modules[_SUFFIX.sub("", name)]
            entry[0] += (b - a) / len(devices)
            entry[1] += 1 / len(devices)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_host_span(spans, starts, a, b), (b - a) / len(devices)))

    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    gap_total = defaultdict(float)
    for name, ns in gaps:
        gap_total[name] += ns * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "ops": {k: (v[0] * 1e-9, v[1]) for k, v in ops.items()},
        "modules": {k: (v[0] * 1e-9, v[1]) for k, v in modules.items()},
        "gap_by_span": dict(gap_total),
        "breakdown": {
            "device_ops": [[k[:NAME_CHARS], v[0] * 1e-9] for k, v in sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in sorted(gaps, key=lambda g: -g[1])[:top]],
        },
    }


def time_of(reduced: dict, kind: str, *needles: str) -> tuple[float, float]:
    """(seconds, count) of the ``kind`` (``"modules"`` or ``"ops"``) events
    whose name holds any of ``needles``."""
    secs = count = 0.0
    for name, (s, n) in reduced[kind].items():
        if any(x in name for x in needles):
            secs += s
            count += n
    return secs, count
