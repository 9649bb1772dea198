"""Work the algorithm requires, computed from shapes alone.

"Required" is what the algorithm needs, whatever implements it, so a change
that removes waste can only raise a share of the peak towards 100 %, never
past it:

* A federated round counts, for the active layers only, the forward matmuls
  and the input-gradient matmuls of the backward pass (one forward's worth
  for every projection, two for the attention core), the LoRA factors'
  forward, input gradient and weight gradient, and the LM head at the
  positions the loss mask keeps.  Recomputation, dropped layers and the head
  at masked-out positions are left out.
* A decode step and a kernel call read each weight once, at the bfloat16
  compute dtype, plus the KV cache of the live positions, the adapters in
  use and the activations they consume and produce.

A multiply-add counts as two operations.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    window: int | None = None

    @classmethod
    def of(cls, model: dict) -> "Shape":
        d, h = model["d_model"], model["num_heads"]
        return cls(
            layers=model["num_layers"],
            d=d,
            heads=h,
            kv_heads=model["num_kv_heads"],
            head_dim=model.get("head_dim") or d // h,
            ff=model["d_ff"],
            vocab=model["vocab_size"],
            window=model.get("sliding_window"),
        )

    @property
    def q_out(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_out(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def layer_weights(self) -> int:
        """Matmul weights of one layer: q, k, v, o and the SwiGLU triple."""
        return 2 * self.d * self.q_out + 2 * self.d * self.kv_out + 3 * self.d * self.ff

    def lora_width(self, rank: int, targets=("q", "v")) -> int:
        """Sum over LoRA targets of (d_in + d_out) * rank, per layer."""
        out = {"q": self.q_out, "k": self.kv_out, "v": self.kv_out, "o": self.d}
        return sum((self.d + out[t]) * rank for t in targets)


def attended_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs a causal sequence of ``seq`` tokens attends."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def round_step_flops(m: Shape, *, batch: int, seq: int, active_layers: float,
                     rank: int, targets=("q", "v"), masked_positions: int = 1) -> float:
    """Required operations of one client step on ``batch`` sequences."""
    tokens = batch * seq
    linear = 2 * m.layer_weights * tokens
    attn = 4 * m.heads * m.head_dim * attended_pairs(seq, m.window) * batch
    lora = 2 * m.lora_width(rank, targets) * tokens
    per_layer = (linear + attn + lora) + (linear + 2 * attn + 2 * lora)
    head = 2 * (2 * m.d * m.vocab * masked_positions * batch)
    return active_layers * per_layer + head


def decode_step(m: Shape, *, contexts, adapters_in_use: int, rank: int,
                targets=("q", "v")) -> tuple[float, float]:
    """(operations, bytes) one decode step requires for live rows whose
    context lengths (this step's token included) are ``contexts``."""
    rows = len(contexts)
    ctx = [c if m.window is None else min(c, m.window) for c in contexts]
    flops = rows * m.layers * (2 * m.layer_weights + 2 * m.lora_width(rank, targets))
    flops += m.layers * 4 * m.heads * m.head_dim * sum(ctx)
    flops += rows * 2 * m.d * m.vocab
    weights = m.layers * m.layer_weights + m.d * m.vocab
    kv_read = m.layers * 2 * m.kv_out * sum(ctx)
    kv_write = m.layers * 2 * m.kv_out * rows
    lora = adapters_in_use * m.layers * m.lora_width(rank, targets)
    acts = rows * (2 * m.d + m.vocab)  # embedding rows in, logits out
    return flops, BF16 * (weights + kv_read + kv_write + lora + acts)


def segmented_lora_call(*, rows: int, k: int, n: int, rank: int,
                        adapters_in_use: int) -> tuple[float, float]:
    """(operations, bytes) of one segmented LoRA projection: ``rows`` rows
    of width ``k`` through a ``k x n`` weight plus each row's rank-``rank``
    adapter."""
    flops = 2 * rows * k * n + 2 * rows * rank * (k + n)
    nbytes = BF16 * (k * n + rows * (k + n) + adapters_in_use * rank * (k + n))
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
