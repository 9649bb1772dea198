"""Plain float32 reference of the dense decoders the benchmark runs.

Written from the published descriptions of Qwen3 and H2O-Danube (pre-norm
RMSNorm, grouped-query attention with rotary positions, optional qk-norm and
sliding window, SwiGLU feed-forward, tied or untied head), with LoRA on the
attention projections.  It imports nothing of the program under test.

Weights are drawn from the seed with the same ``jax.random`` calls as the
program's initialiser, so the reference regenerates the weights the program
was given instead of taking them from it.

``mode`` sets the arithmetic of every matrix product:

* ``"highest"``: float32 operands at ``Precision.HIGHEST``, the reference.
* ``"fp8"``: each operand rounded to float8 e4m3 with one scale per tensor
  (its largest magnitude mapped to 448), then multiplied in float32; in the
  backward pass the incoming gradient is rounded the same way.  This is the
  control: the precision just below the bfloat16 compute the configurations
  state.

Everything else (norms, softmax, rotary, the loss) is float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
_F8_MAX = 448.0


def sizes(model: dict) -> dict:
    """The sizes the reference needs, from a configuration's ``model`` block."""
    d, h = model["d_model"], model["num_heads"]
    hd = model.get("head_dim") or d // h
    return {
        "L": model["num_layers"],
        "d": d,
        "H": h,
        "KV": model["num_kv_heads"],
        "hd": hd,
        "ff": model["d_ff"],
        "V": model["vocab_size"],
        "eps": model.get("norm_eps", 1e-5),
        "theta": model.get("rope_theta", 10_000.0),
        "qk_norm": bool(model.get("qk_norm", False)),
        "window": model.get("sliding_window"),
        "tied": bool(model.get("tie_embeddings", False)),
    }


# ---------------------------------------------------------------- weights
def _lecun(key, shape):
    std = (1.0 / max(1, shape[0])) ** 0.5
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype=jnp.float32)


def _init_layer(key, s):
    k1, k2, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(k1, 4)
    kg, ku, kd = jax.random.split(k2, 3)
    d, hd = s["d"], s["hd"]
    p = {
        "norm1": jnp.ones((d,), jnp.float32),
        "norm2": jnp.ones((d,), jnp.float32),
        "wq": _lecun(kq, (d, s["H"] * hd)),
        "wk": _lecun(kk, (d, s["KV"] * hd)),
        "wv": _lecun(kv, (d, s["KV"] * hd)),
        "wo": _lecun(ko, (s["H"] * hd, d)),
        "gate": _lecun(kg, (d, s["ff"])),
        "up": _lecun(ku, (d, s["ff"])),
        "down": _lecun(kd, (s["ff"], d)),
    }
    if s["qk_norm"]:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def init_base(key, s):
    """Base weights, layer leaves stacked on a leading layer axis."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, s["L"])
    params = {
        "embed": 0.02 * jax.random.normal(k_emb, (s["V"], s["d"]), jnp.float32),
        "layers": jax.vmap(lambda k: _init_layer(k, s))(layer_keys),
        "final_norm": jnp.ones((s["d"],), jnp.float32),
    }
    if not s["tied"]:
        params["lm_head"] = 0.02 * jax.random.normal(k_head, (s["d"], s["V"]), jnp.float32)
    return params


def init_lora(key, s, rank: int, targets=("q", "v")):
    """LoRA factors as the program initialises them: ``a`` LeCun, ``b`` zero."""
    out_dim = {"q": s["H"] * s["hd"], "k": s["KV"] * s["hd"], "v": s["KV"] * s["hd"]}

    def one(k):
        keys = jax.random.split(k, 16)
        return {
            t: {
                "a": _lecun(keys[i], (s["d"], rank)),
                "b": jnp.zeros((rank, out_dim[t]), jnp.float32),
            }
            for i, t in enumerate(targets)
        }

    return jax.vmap(one)(jax.random.split(key, s["L"]))


# ------------------------------------------------------------- arithmetic
def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    """Rounds an operand to float8; its gradient passes straight through."""
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity forward; rounds the gradient flowing back into a product, so
    the backward products take float8 operands too."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_fp8(g),))


def einsum(mode: str, spec: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    if mode == "highest":
        return jnp.einsum(spec, a, b, precision=hi)
    if mode == "fp8":
        return _fp8_cotangent(jnp.einsum(spec, _fp8_operand(a), _fp8_operand(b), precision=hi))
    raise ValueError(f"unknown reference mode {mode!r}")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _proj(mode, x, w, lora, scale):
    y = einsum(mode, "bsd,de->bse", x, w)
    if lora is not None:
        y = y + scale * einsum(mode, "bsr,re->bse", einsum(mode, "bsd,dr->bsr", x, lora["a"]), lora["b"])
    return y


def block(p, lora, h, s, mode, lora_scale):
    """One pre-norm decoder layer on ``h`` (B, S, d)."""
    b, t, _ = h.shape
    lora = lora or {}
    x = rmsnorm(h, p["norm1"], s["eps"])
    q = _proj(mode, x, p["wq"], lora.get("q"), lora_scale).reshape(b, t, s["H"], s["hd"])
    k = _proj(mode, x, p["wk"], lora.get("k"), lora_scale).reshape(b, t, s["KV"], s["hd"])
    v = _proj(mode, x, p["wv"], lora.get("v"), lora_scale).reshape(b, t, s["KV"], s["hd"])
    if s["qk_norm"]:
        q = rmsnorm(q, p["q_norm"], s["eps"])
        k = rmsnorm(k, p["k_norm"], s["eps"])
    pos = jnp.arange(t)
    q, k = rotary(q, pos, s["theta"]), rotary(k, pos, s["theta"])
    rep = s["H"] // s["KV"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)  # head h reads kv head h // rep
    scores = einsum(mode, "bqhd,bkhd->bhqk", q, k) * s["hd"] ** -0.5
    ok = pos[None, :] <= pos[:, None]
    if s["window"] is not None:
        ok = ok & (pos[None, :] > pos[:, None] - s["window"])
    probs = jax.nn.softmax(jnp.where(ok, scores, NEG_INF), axis=-1)
    attn = einsum(mode, "bhqk,bkhd->bqhd", probs, v).reshape(b, t, s["H"] * s["hd"])
    h = h + einsum(mode, "bse,ed->bsd", attn, p["wo"])
    x = rmsnorm(h, p["norm2"], s["eps"])
    g = einsum(mode, "bsd,df->bsf", x, p["gate"])
    u = einsum(mode, "bsd,df->bsf", x, p["up"])
    return h + einsum(mode, "bsf,fd->bsd", jax.nn.silu(g) * u, p["down"])


def hidden(params, s, tokens, *, lora=None, lora_scale=1.0, drops=None, mode="highest"):
    """Final-normed hidden states (B, S, d).  ``drops`` (L,) bool skips the
    dropped layers outright, each behind a ``lax.cond``."""
    h = params["embed"][tokens]
    xs = {"p": params["layers"]}
    if lora is not None:
        xs["lora"] = lora
    if drops is not None:
        xs["drop"] = drops

    def body(h, x):
        run = lambda hh: block(x["p"], x.get("lora"), hh, s, mode, lora_scale)
        if "drop" in x:
            return jax.lax.cond(x["drop"], lambda hh: hh, run, h), None
        return run(h), None

    h, _ = jax.lax.scan(body, h, xs)
    return rmsnorm(h, params["final_norm"], s["eps"])


def head(params, s):
    return params["embed"].T if s["tied"] else params["lm_head"]


def logits(params, s, tokens, *, mode="highest", **kw):
    h = hidden(params, s, tokens, mode=mode, **kw)
    return einsum(mode, "bsd,dv->bsv", h, head(params, s))


def loss_and_grad(params, s, tokens, targets, mask, *, lora, lora_scale, drops, mode="highest"):
    """The masked mean next-token loss over ``tokens`` (B, S) and its
    gradient with respect to ``lora``, by backpropagation one layer at a time.

    The forward pass keeps each layer's input; the backward pass takes one
    layer's vector-Jacobian product at a time from that input, and a dropped
    layer (``drops`` (L,) bool) passes the gradient through unchanged.  So
    no layer's activations, and no copy of its weights, outlive its own
    step, and the whole model's gradient fits beside its weights."""
    xs = {"p": params["layers"], "lora": lora, "drop": drops}
    run = lambda p, lo, h: block(p, lo, h, s, mode, lora_scale)

    def forward(h, x):
        return jax.lax.cond(x["drop"], lambda hh: hh, lambda hh: run(x["p"], x["lora"], hh), h), h

    h_last, h_in = jax.lax.scan(forward, params["embed"][tokens], xs)

    def head_loss(h):
        lg = einsum(mode, "bsd,dv->bsv", rmsnorm(h, params["final_norm"], s["eps"]), head(params, s))
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    loss, d_h = jax.value_and_grad(head_loss)(h_last)

    def backward(d_h, x):
        def through(d):
            _, vjp = jax.vjp(lambda hh, lo: run(x["p"], lo, hh), x["h"], x["lora"])
            return vjp(d)

        skip = lambda d: (d, jax.tree.map(jnp.zeros_like, x["lora"]))
        return jax.lax.cond(x["drop"], skip, through, d_h)

    _, d_lora = jax.lax.scan(backward, d_h, dict(xs, h=h_in), reverse=True)
    return loss, d_lora
