"""CPU tests of the benchmark's yardstick: loading by name, the trace
reduction, required-work counts, traffic and the peak table."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import flops, harness, trace
from chipbench.traffic import requests

ROOT = Path(__file__).resolve().parents[2]


# ------------------------------------------------------------------ loader
def test_every_cell_loads_by_name():
    bench = harness.load_benchmark()
    for c in bench["workloads"]:
        cell = harness.load_cell(c["name"])
        assert cell["config_file"]["name"] == c["config"]
        assert cell["traffic_file"]["driver"] in ("round", "serve")
        harness.driver(cell["traffic_file"]["driver"])
        assert cell["end_to_end"] and cell["per_layer"]
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))
        assert set(cell["workload_file"]["limits"]) >= {"logit_gap"} or set(
            cell["workload_file"]["limits"]) >= {"loss_gap", "grad_gap", "update_gap"}


def test_every_config_file_is_named_in_the_benchmark():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


@pytest.mark.parametrize("bad", ["", "a b", "a/b", "-x", "x" * 65, "µs"])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        harness.check_name(bad)


@pytest.mark.parametrize("bad", ["", "tokens per second", "x" * 17, "µs"])
def test_bad_units_are_refused(bad):
    with pytest.raises(ValueError):
        harness.check_unit(bad)


@pytest.mark.parametrize("good", ["tokens/s", "%", "GiB", "ms"])
def test_good_units_pass(good):
    assert harness.check_unit(good) == good


def test_unknown_cell_and_reader_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric")


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")


# ------------------------------------------------------------------- trace
def _trace():
    """Window 0-100 ns; device ops at 10-30, 20-40 (overlapping), 60-70 and
    95-115; host spans: step 0-38, submit 75-100."""
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("chipbench.window", 0.0, 100.0),
        ("chipbench.step", 0.0, 38.0),
        ("chipbench.submit", 75.0, 25.0),
        ("unrelated", 0.0, 100.0),
    ]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [("fusion.1", 10.0, 20.0), ("_segmented_kernel", 20.0, 20.0),
                                        ("fusion.1", 60.0, 10.0), ("late", 95.0, 20.0)]},
        {"name": "XLA Modules", "events": [("jit_step_fn(3)", 10.0, 30.0), ("jit_step_fn(3)", 60.0, 10.0)]},
    ]}
    return [host, dev]


def test_trace_busy_is_the_union_of_op_intervals():
    r = trace.reduce(_trace())
    # 10-40 (union), 60-70, 95-100 (clipped at the window)
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.55)


def test_trace_op_and_module_times_by_name():
    r = trace.reduce(_trace())
    assert r["ops"]["fusion.1"] == pytest.approx((30e-9, 2))
    assert trace.time_of(r, "ops", "segmented") == pytest.approx((20e-9, 1))
    assert trace.time_of(r, "modules", "jit_step_fn") == pytest.approx((40e-9, 2))


def test_trace_gaps_are_named_by_host_span():
    r = trace.reduce(_trace())
    # idle 0-10 under "step"; 40-60 overlaps no span; 70-95 mostly under "submit"
    assert r["gap_by_span"] == pytest.approx(
        {"chipbench.step": 10e-9, "other": 20e-9, "chipbench.submit": 25e-9})
    assert r["breakdown"]["idle_gaps"][0] == ["chipbench.submit", pytest.approx(25e-9)]
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_trace_without_window_is_an_error():
    planes = _trace()
    planes[0]["lines"][0]["events"] = planes[0]["lines"][0]["events"][1:]
    with pytest.raises(ValueError):
        trace.reduce(planes)


# ------------------------------------------------------------------- flops
QWEN = flops.Shape(layers=28, d=2048, heads=16, kv_heads=8, head_dim=128, ff=6144, vocab=151936)
DANUBE = flops.Shape(layers=24, d=2560, heads=32, kv_heads=8, head_dim=80, ff=6912, vocab=32000,
                     window=4096)


def test_layer_weights_by_hand():
    # q 2048x2048, k and v 2048x1024, o 2048x2048, gate/up/down 2048x6144
    assert QWEN.layer_weights == 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    # q 2560x2560, k and v 2560x640, o 2560x2560, gate/up/down 2560x6912
    assert DANUBE.layer_weights == 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912


@pytest.mark.parametrize("shape", [QWEN, DANUBE], ids=["qwen3-1.7b", "h2o-danube-1.8b"])
def test_round_step_counts_active_layers_and_masked_head(shape):
    b, s, r = 8, 128, 8
    lora = (shape.d + shape.q_out + shape.d + shape.kv_out) * r
    attn = 4 * shape.heads * shape.head_dim * (s * (s + 1) // 2) * b
    per_layer = 2 * (2 * shape.layer_weights * b * s) + 3 * attn + 3 * 2 * lora * b * s
    head = 4 * shape.d * shape.vocab * b  # forward and input gradient, one masked position per row
    got = flops.round_step_flops(shape, batch=b, seq=s, active_layers=14, rank=r)
    assert got == pytest.approx(14 * per_layer + head)
    # a dropped layer costs nothing; the head does not scale with depth
    none = flops.round_step_flops(shape, batch=b, seq=s, active_layers=0, rank=r)
    assert none == pytest.approx(head)
    assert flops.round_step_flops(shape, batch=b, seq=s, active_layers=28, rank=r) - none == pytest.approx(
        2 * (got - none))


def test_round_step_qwen_per_token_by_hand():
    # about 2 x 100.7 MFLOP per token per active layer: forward plus input gradient
    per_token = flops.round_step_flops(QWEN, batch=1, seq=1, active_layers=1, rank=0, masked_positions=0)
    assert per_token == pytest.approx(4 * 50_331_648 + 3 * 4 * 16 * 128)


def test_sliding_window_caps_attended_pairs():
    assert flops.attended_pairs(8, None) == 36
    assert flops.attended_pairs(8, 3) == 6 + 5 * 3
    assert flops.attended_pairs(8, 8) == 36


def test_decode_step_reads_each_weight_once():
    f1, b1 = flops.decode_step(QWEN, contexts=[100], adapters_in_use=1, rank=8)
    f2, b2 = flops.decode_step(QWEN, contexts=[100, 100], adapters_in_use=1, rank=8)
    weights = 2 * (28 * QWEN.layer_weights + 2048 * 151936)
    assert b1 > weights and b2 - b1 < 0.01 * weights  # a second row adds its KV and activations only
    kv_row = 2 * 28 * 2 * 1024 * 100
    assert b2 - b1 == pytest.approx(kv_row + 2 * 28 * 2 * 1024 + 2 * (2 * 2048 + 151936))
    assert f2 == pytest.approx(2 * f1)


def test_segmented_lora_call_by_hand():
    f, b = flops.segmented_lora_call(rows=32, k=2048, n=2048, rank=8, adapters_in_use=3)
    assert f == 2 * 32 * 2048 * 2048 + 2 * 32 * 8 * 4096
    assert b == 2 * (2048 * 2048 + 32 * 4096 + 3 * 8 * 4096)
    t, bound = flops.roofline_seconds(f, b, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "bytes" and t == pytest.approx(b / 819e9)


# ----------------------------------------------------------------- traffic
MIX = json.loads((ROOT / "chipbench" / "traffic" / "chat.json").read_text())


def test_traffic_is_deterministic_per_seed():
    a = requests.schedule(MIX, 2**31 + 11, 20.0, 151936)
    b = requests.schedule(MIX, 2**31 + 11, 20.0, 151936)
    c = requests.schedule(MIX, 2**31 + 12, 20.0, 151936)
    assert a == b
    assert a != c


def test_traffic_seeds_share_their_sizes():
    a = requests.schedule(MIX, 1, 20.0, 151936)
    b = requests.schedule(MIX, 2, 20.0, 151936)
    n = min(len(a), len(b))
    # the same multiset of sizes, shuffled; arrival times may cut the tail
    assert abs(len(a) - len(b)) <= 3
    la = sorted(len(x.prompt) for x in a[:n])
    lb = sorted(len(x.prompt) for x in b[:n])
    assert abs(sum(la) - sum(lb)) / sum(la) < 0.15
    for x in a:
        assert MIX["prompt"]["min"] <= len(x.prompt) <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= x.max_new_tokens <= MIX["output"]["max"]
        assert 0 <= x.adapter < MIX["adapters"]
        assert all(0 <= t < 151936 for t in x.prompt)
    assert a[0].due_s == pytest.approx(-MIX["warm_s"])
    assert all(x.due_s < 20.0 for x in a)


def test_zipf_popularity():
    w = requests.zipf_weights(8, 1.0)
    assert w.sum() == pytest.approx(1.0) and w[0] == pytest.approx(2 * w[1])


# -------------------------------------------------------------- entry point
def test_run_off_the_chip_exits_nonzero_without_a_result():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: v for k, v in os.environ.items() if k in ("HOME", "TMPDIR", "PYTHONPATH", "VIRTUAL_ENV")})
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload", "qwen3-1.7b.round.stld50",
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr
