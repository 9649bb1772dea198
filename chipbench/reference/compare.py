"""The numbers that decide ``correct``: the timed path's outputs against the
plain reference, recomputed from the seed.

Round cells compare the first round, which set-up drives through the
window's own call (``ExperimentRunner.run`` at the cell's cohort, batch and
sequence length).  The reference regenerates the base weights and the
initial LoRA tree from the seed, takes the round's token batches, dropout
rates, PRNG keys and step offsets as the program received them, and trains
each client with ``chipbench.reference.round``.  A "leaf" below is one layer's
slice of one LoRA factor (``q/a``, ``q/b``, ``v/a``, ``v/b``).

* ``loss_gap``: over clients, the largest relative gap between the mean
  loss of the client's local steps and the reference's.
* ``grad_gap``: over clients and layers, the gap between the program's
  Eq.-6 importance (the mean gradient norm over the steps the layer ran)
  and the reference's, against the larger of the reference's value and
  that client's median layer.
* ``update_gap``: over clients and leaves, the gap between the norm
  of the program's change to the leaf and the reference's, against the
  larger of the reference's norm and the median leaf's.
* ``global_gap``: the same for the aggregated global adapter, the
  reference aggregating its own client updates over the program's share
  masks.  (Which layers a client shares is a ranking of near-equal
  importances; neither the control nor a planted fault moved it, so the
  masks are taken as the program chose them and not compared.)

A leaf moves when the largest gradient norm the reference saw for it is at
least a thousandth (``MOVING``) of the median over leaves with any gradient;
the others move by weight decay and round-off alone.  The two gaps of norms
count only the leaves whose gradient is at least a tenth (``STEADY``) of that
median.  In the first round these leave out the ``a`` factors, whose
gradient is small because ``b`` starts at zero: summed over many tokens it
cancels, bfloat16 rounding moves it by several per cent, and AdamW turns
that into their change, while a float32 run of the program matches the
reference on them to 1e-6.

Serve cells compare a sample of the finished requests drawn from the seed,
the longest among them: ``logit_gap`` is the widest gap, in logits, by which
a served token lies below the reference's best token at its position, the
reference running base plus the request's adapter over prompt and served
tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import model as ref
from chipbench.reference import round as round_ref

MOVING = 1e-3
STEADY = 1e-1
SHARES = (MOVING, 1e-2, STEADY, 0.3)  # reported in ``detail``: the worst gap at each


def _layer_leaves(tree) -> dict:
    """{(path, layer): float64 array} for a LoRA tree with leaves (L, ...)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        leaf = np.asarray(leaf, np.float64)
        for layer in range(leaf.shape[0]):
            out[(name, layer)] = leaf[layer]
    return out


def _norm_gaps(delta_p: dict, delta_r: dict, leaves: set) -> dict:
    """Per leaf, the gap between the norms of the program's and the
    reference's change, against the larger of the reference's norm and the
    median leaf's."""
    if not leaves:
        return {}
    norm_p = {k: float(np.linalg.norm(delta_p[k])) for k in leaves}
    norm_r = {k: float(np.linalg.norm(delta_r[k])) for k in leaves}
    med = float(np.median(list(norm_r.values())))
    return {k: abs(norm_p[k] - norm_r[k]) / max(norm_r[k], med, 1e-30) for k in leaves}


def _minus(a: dict, b: dict) -> dict:
    return {k: a[k] - b[k] for k in a}


def reference_cfg(ctx) -> dict:
    peft = ctx.config["peft"]
    mix = ctx.traffic
    return {
        "train": mix["train"],
        "stld": {
            "enabled": True,
            "distribution": mix["distribution"],
            "min_active_layers": mix["min_active_layers"],
        },
        "lora_scale": peft["lora_alpha"] / peft["lora_rank"],
    }


def round_reference(ctx, first):
    """Reference client rounds for the captured first round: per client
    (LoRA tree, mean loss, importance, per-leaf largest gradient norm),
    and the initial LoRA tree."""
    s = ref.sizes(ctx.config["model"])
    peft = ctx.config["peft"]
    _, k_base, k_peft = jax.random.split(jax.random.PRNGKey(ctx.seed), 3)
    base = jax.jit(lambda k: ref.init_base(k, s))(k_base)
    lora0 = jax.jit(lambda k: ref.init_lora(k, s, peft["lora_rank"], tuple(peft["lora_targets"])))(k_peft)
    _, batch, rates, keys, gsteps = first["inputs"]
    client = round_ref.make_client_round(s, reference_cfg(ctx))
    clients = []
    for i in range(len(rates)):
        out = client(base, lora0, batch["tokens"][i], batch["targets"][i], batch["mask"][i],
                     rates[i], keys[i], gsteps[i])
        clients.append(jax.device_get(out))
    del base
    return clients, jax.device_get(lora0)


def round_numbers(ctx, first, reference=None, detail=None) -> list:
    """(name, value) pairs for a captured first round; ``reference`` is
    ``round_reference``'s result where the caller already has it.  A dict
    passed as ``detail`` receives, per gap of norms, the worst leaf with its
    gradient's share of the median leaf's, and the worst gap among the
    leaves at each of ``SHARES``."""
    clients, lora0 = reference if reference is not None else round_reference(ctx, first)
    starts, _, _, _, _ = first["inputs"]
    p_out, metrics, imps = first["outputs"]
    masks = first["masks"]
    n = len(clients)
    program = lambda tree, i: jax.tree.map(lambda x: x[i], tree["attn"])

    loss_gap = max(abs(float(metrics["loss"][i]) - float(c[1])) / abs(float(c[1])) for i, c in enumerate(clients))

    grad_gap = 0.0
    for i, c in enumerate(clients):
        imp_r, imp_p = np.asarray(c[2], np.float64), np.asarray(imps[i], np.float64)
        ran = imp_r > 0
        med = float(np.median(imp_r[ran])) if ran.any() else 0.0
        keep = ran & (imp_r >= MOVING * med)
        if keep.any():
            grad_gap = max(grad_gap, float(np.max(np.abs(imp_p[keep] - imp_r[keep]) / np.maximum(imp_r[keep], med))))

    init = _layer_leaves(lora0)
    gmax = [_layer_leaves(c[3]) for c in clients]
    positive = [v for g in gmax for v in g.values() if v > 0]
    median = float(np.median(positive)) if positive else np.inf
    share = [{k: v / median for k, v in g.items()} for g in gmax]  # of the median leaf's gradient
    moving = lambda i, floor: {k for k, v in share[i].items() if v >= floor}

    def worst_of(pairs):
        """The largest gap among leaves at each share of the median gradient."""
        return {f"{f:g}": max((g for r, g in pairs if r >= f), default=0.0) for f in SHARES}

    update_gap, worst, pairs = 0.0, {}, []
    for i, c in enumerate(clients):
        d_p = _minus(_layer_leaves(program(p_out, i)), _layer_leaves(program(starts, i)))
        d_r = _minus(_layer_leaves(c[0]), init)
        gaps = _norm_gaps(d_p, d_r, moving(i, MOVING))
        pairs += [(share[i][k], g) for k, g in gaps.items()]
        steady = {k: g for k, g in gaps.items() if share[i][k] >= STEADY}
        if steady and max(steady.values()) >= update_gap:
            leaf = max(steady, key=steady.get)
            update_gap, worst = steady[leaf], {"leaf": leaf, "client": i, "grad_share": share[i][leaf]}

    ref_global = round_ref.aggregate([c[0] for c in clients], masks, lora0)
    d_p = _minus(_layer_leaves(first["global"]["attn"]), _layer_leaves(program(starts, 0)))
    d_r = _minus(_layer_leaves(ref_global), init)
    # a shared leaf counts at the largest share of the median any sharing client gave it
    g_share = {}
    for i in range(n):
        for k, v in share[i].items():
            if masks[i][k[1]]:
                g_share[k] = max(g_share.get(k, 0.0), v)
    gaps = _norm_gaps(d_p, d_r, {k for k, v in g_share.items() if v >= MOVING})
    steady = {k: g for k, g in gaps.items() if g_share[k] >= STEADY}
    global_gap = max(steady.values(), default=0.0)
    if detail is not None:
        detail["update_gap"] = dict(worst, at_grad_share=worst_of(pairs))
        leaf = max(steady, key=steady.get, default=None)
        detail["global_gap"] = {"leaf": leaf, "grad_share": g_share.get(leaf),
                                "at_grad_share": worst_of([(g_share[k], g) for k, g in gaps.items()])}
    return [
        ("loss_gap", loss_gap),
        ("grad_gap", grad_gap),
        ("update_gap", update_gap),
        ("global_gap", global_gap),
    ]


def serve_gaps(ctx, requests, adapters, modes=("highest",)) -> dict:
    """Per mode, the widest logit gap over the sampled requests.  For
    ``"highest"`` the gap is that of the served token; for a control mode it
    is that of the token the control ranks first, read against the
    ``"highest"`` logits at the same positions."""
    s = ref.sizes(ctx.config["model"])
    peft = ctx.config["peft"]
    base = jax.jit(lambda k: ref.init_base(k, s))(jax.random.PRNGKey(ctx.seed))
    scale = peft["lora_alpha"] / peft["lora_rank"]
    length = ctx.traffic["max_len"]

    def make(mode):
        @jax.jit
        def logits_at(params, tokens, lora, positions):
            h = ref.hidden(params, s, tokens[None], lora=lora, lora_scale=scale, mode=mode)[0]
            return ref.einsum(mode, "pd,dv->pv", h[positions], ref.head(params, s))
        return logits_at

    fns = {m: make(m) for m in dict.fromkeys(("highest",) + tuple(modes))}
    gaps = {m: 0.0 for m in modes}
    for r in requests:
        seq, served, adapter = tuple(r["prompt"]) + tuple(r["tokens"]), len(r["tokens"]), r["adapter"]
        tokens = np.zeros((length,), np.int32)
        tokens[: len(seq) - 1] = seq[:-1]
        positions = np.zeros((ctx.traffic["output"]["max"],), np.int32)
        first_pos = len(seq) - served - 1
        positions[:served] = np.arange(first_pos, first_pos + served)
        lora = jax.tree.map(jnp.asarray, adapters[adapter])
        exact = np.asarray(fns["highest"](base, tokens, lora, positions), np.float64)[:served]
        target = np.asarray(seq[-served:])
        for m in modes:
            if m == "highest":
                chosen = target
            else:
                chosen = np.argmax(np.asarray(fns[m](base, tokens, lora, positions))[:served], axis=-1)
            gap = exact.max(axis=-1) - exact[np.arange(served), chosen]
            gaps[m] = max(gaps[m], float(gap.max()))
    return gaps
