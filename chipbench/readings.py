"""Readings that set a cell's limits: the program, the control and planted faults.

    python3 chipbench/readings.py --workload <cell> --seeds 11 12 13 \\
        [--modes program fp8 half_batch token] [--seconds 20] [--out FILE]

For every seed the program runs as in a benchmark run (a serve cell with a
short window of ``--seconds`` at the cell's own load), and each mode is
compared with the float32 reference by the cell's own numbers, and judged
by the cell's limits (``correct``):

* ``program``: the timed path itself, the lower reading;
* ``fp8``: the control, the reference in the program's place with every
  matrix product's operands rounded to float8 e4m3;
* ``half_batch`` (round cells): the reference in the program's place, each
  step's loss the mean over the first half of the batch only;
* ``token``: an answer altered where it is produced, the label at the
  first row's last position (round cells), or the first served token of
  every sampled request (serve cells).

A step that returns its state unchanged reads 1 on ``update_gap`` by
definition and needs no run.  One JSON line per seed goes to ``--out`` and
to standard output.  This is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness  # noqa: E402


def in_programs_place(ctx, first, mode: str) -> dict:
    """The reference run in the program's place under ``mode``, shaped like
    the program's captured first round."""
    import jax
    import numpy as np

    from chipbench.reference import compare
    from chipbench.reference import model as ref
    from chipbench.reference import round as round_ref

    starts, batch, rates, keys, gsteps = first["inputs"]
    batch = {k: np.array(v) for k, v in batch.items()}
    arith = "fp8" if mode == "fp8" else "highest"
    if mode == "half_batch":
        half = batch["mask"].shape[2] // 2
        batch["mask"][:, :, half:] = 0.0
    if mode == "token":
        batch["targets"][:, :, 0, -1] = (batch["targets"][:, :, 0, -1] + 1) % ctx.config["model"]["vocab_size"]
    s = ref.sizes(ctx.config["model"])
    peft = ctx.config["peft"]
    _, k_base, k_peft = jax.random.split(jax.random.PRNGKey(ctx.seed), 3)
    base = jax.jit(lambda k: ref.init_base(k, s))(k_base)
    lora0 = jax.device_get(jax.jit(lambda k: ref.init_lora(k, s, peft["lora_rank"], tuple(peft["lora_targets"])))(k_peft))
    client = round_ref.make_client_round(s, compare.reference_cfg(ctx), arith)
    outs = [jax.device_get(client(base, lora0, batch["tokens"][i], batch["targets"][i], batch["mask"][i],
                                  rates[i], keys[i], gsteps[i])) for i in range(len(rates))]
    del base
    imps = np.stack([o[2] for o in outs])
    share = max(1, int(ctx.traffic["ptls_share_fraction"] * s["L"]))
    masks = round_ref.shared_masks(imps, share)
    stack = lambda trees: jax.tree.map(lambda *x: np.stack(x), *trees)
    glob = round_ref.aggregate([o[0] for o in outs], masks, lora0)
    return {
        "inputs": ({"attn": stack([lora0] * len(outs))}, None, rates, keys, gsteps),
        "outputs": ({"attn": stack([o[0] for o in outs])}, {"loss": np.array([o[1] for o in outs])}, imps),
        "masks": masks,
        "global": {"attn": jax.tree.map(lambda x: np.asarray(x, np.float32), glob)},
    }


def round_readings(ctx, modes) -> dict:
    from chipbench.drivers import round as drv
    from chipbench.reference import compare

    runner = drv.setup(ctx)
    del runner
    gc.collect()
    first = ctx.check_inputs
    reference = compare.round_reference(ctx, first)
    out = {}
    for mode in modes:
        data = first if mode == "program" else in_programs_place(ctx, first, mode)
        detail = {}
        out[mode] = _judged(ctx, compare.round_numbers(ctx, data, reference, detail))
        out[mode]["worst"] = {k: dict(v, leaf="/".join(map(str, v.get("leaf") or ()))) for k, v in detail.items()}
    return out


def _judged(ctx, pairs) -> dict:
    """The numbers of ``pairs``, and ``correct`` as the cell's limits judge them."""
    from chipbench.run import judge

    pairs = list(pairs)
    _, correct = judge(pairs, ctx.cell["workload_file"]["limits"])
    return dict(pairs, correct=correct)


def serve_readings(ctx, modes) -> dict:
    import numpy as np

    from chipbench.drivers import serve as drv
    from chipbench.reference import compare

    record, _, _ = drv.run(ctx)
    gc.collect()
    sample, adapters = ctx.check_inputs
    gaps = compare.serve_gaps(ctx, sample, adapters, modes=tuple(m for m in modes if m in ("fp8",)) + ("highest",))
    out = {"program": _judged(ctx, [("logit_gap", gaps["highest"])]),
           "served_tokens": sum(len(r["tokens"]) for r in sample), "finished": record["serve"]["finished"]}
    if "fp8" in modes:
        out["fp8"] = _judged(ctx, [("logit_gap", gaps["fp8"])])
    if "token" in modes:
        vocab = ctx.config["model"]["vocab_size"]
        altered = [dict(r, tokens=[(r["tokens"][0] + 1) % vocab] + list(r["tokens"][1:])) for r in sample]
        out["token"] = _judged(ctx, [("logit_gap", compare.serve_gaps(ctx, altered, adapters)["highest"])])
    out["ttft_p90_ms"] = float(np.percentile(record["serve"]["ttft_ms"], 90))
    out["itl_p95_ms"] = float(np.percentile(record["serve"]["itl_ms"], 95))
    out["queue"] = record["serve"]["queue"]
    out["rate_per_s"] = ctx.traffic["rate_per_s"]
    return out


def main() -> int:
    import jax

    from chipbench.run import Context
    from repro.launch.compile_cache import enable_compile_cache

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--modes", nargs="+", default=["program", "fp8", "half_batch", "token"])
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--rate", type=float, help="offered load for a sweep, in place of the mix's")
    p.add_argument("--out")
    args = p.parse_args()
    cell = harness.load_cell(args.workload)
    if args.rate is not None:
        cell["traffic_file"]["rate_per_s"] = args.rate
    if jax.devices()[0].platform == "cpu":
        print("readings: needs the accelerator", file=sys.stderr)
        return 3
    enable_compile_cache()
    for seed in args.seeds:
        ctx = Context(cell, seed, args.seconds, False)
        if cell["traffic_file"]["driver"] == "round":
            line = round_readings(ctx, args.modes)
        else:
            line = serve_readings(ctx, [m for m in args.modes if m != "half_batch"])
        line = {"cell": args.workload, "seed": seed, **line}
        if ctx.setup_s is not None:
            line["setup_s"] = ctx.setup_s
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
