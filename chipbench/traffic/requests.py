"""Open-loop request traffic from a mix's parameters and a seed.

The mix fixes the multiset of sizes: prompt and output lengths are the
quantiles of their clipped lognormals, and the gaps between arrivals the
quantiles of an exponential at the mix's rate (a Poisson process).  The seed
shuffles them, picks each request's adapter from a Zipf popularity and draws
its token ids.  So every seed asks for the same work in another order, and
runs on different seeds differ only by how the work falls in time.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Arrival:
    uid: int
    due_s: float          # relative to the window's opening; negative = warm-up
    prompt: tuple
    max_new_tokens: int
    adapter: int


def _lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Arrivals due from ``-mix['warm_s']`` until the window closes at
    ``seconds``, in order of due time."""
    rate = mix["rate_per_s"]
    span = mix["warm_s"] + seconds
    n = int(math.ceil(rate * span)) + 1
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    prompts = _lognormal_quantiles(n, mix["prompt"])
    outputs = _lognormal_quantiles(n, mix["output"])
    rng = np.random.default_rng(seed)
    gaps, prompts, outputs = (rng.permutation(x) for x in (gaps, prompts, outputs))
    adapters = rng.choice(mix["adapters"], size=n, p=zipf_weights(mix["adapters"], mix["zipf_s"]))
    due = np.cumsum(gaps) - gaps[0] - mix["warm_s"]
    out = []
    for i in range(n):
        if due[i] >= seconds:
            break
        tokens = rng.integers(0, vocab, size=int(prompts[i]))
        out.append(Arrival(i, float(due[i]), tuple(int(t) for t in tokens), int(outputs[i]), int(adapters[i])))
    return out
