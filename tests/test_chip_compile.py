"""Compiles for a described TPU v5e chip: what the chip's compiler refuses
fails here, without a chip.

Nothing runs: these tests lower and compile the main path's kernel and its
largest training program at the published widths of the benchmark's
configurations, and read what the compiler reports.  The topology is
described inside a module fixture, never at import, so that under
pytest-xdist only the worker given this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import PEFTConfig, STLDConfig, TrainConfig, get_config
from repro.core import peft as peft_lib
from repro.data.synthetic import SyntheticTask
from repro.federated.client import make_client_fns
from repro.kernels.segmented_lora import segmented_lora_pallas
from repro.models.registry import gated_saved_bytes, init_params
from repro.models import transformer

GiB = 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no described chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cohort_programs(one_chip):
    """``compile(arch, batch, seq)``: the full-width cohort-4 train+eval
    program in cond-mode STLD, built as the engine builds it for the
    synthetic task (head and loss at its ``loss_tail``), compiled once per
    shape; returns ``(compiled, argument bytes)``."""
    cache = {}

    def compile_(arch, batch, seq):
        if (arch, batch, seq) not in cache:
            cache[arch, batch, seq] = _compile_cohort_round_eval(one_chip, arch, batch, seq)
        return cache[arch, batch, seq]

    return compile_


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _nbytes(tree) -> int:
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree)
    )


@pytest.mark.parametrize("n", [2048, 1024], ids=["q", "v"])
def test_segmented_lora_compiles_at_qwen3_width(one_chip, n):
    """The serving kernel at qwen3-1.7b's q (2048) and v (1024) widths,
    batch 4, rank pool 8: Mosaic accepts its block shapes."""
    m, k, n_adapters, r_max = 4, 2048, 4, 8
    fn = jax.jit(
        lambda x, w, a, b, idx, ranks: segmented_lora_pallas(
            x, w, a, b, idx, ranks, interpret=False
        )
    )
    compiled = fn.lower(
        _spec(one_chip, (m, k), jnp.bfloat16),
        _spec(one_chip, (k, n), jnp.bfloat16),
        _spec(one_chip, (n_adapters, k, r_max), jnp.bfloat16),
        _spec(one_chip, (n_adapters, r_max, n), jnp.bfloat16),
        _spec(one_chip, (m,), jnp.int32),
        _spec(one_chip, (n_adapters,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "arch,batch,seq,before_gib",
    [
        pytest.param("qwen3-1.7b", 16, 32, None, id="b16s32"),
        # the round cell's shape: the cohort in turn compiled at 10.53 GiB
        # there with head and loss at the last position and each gate
        # saving only its layer's input (the select-gated program took
        # 14.71); the kept products add 1.15 GiB of residuals
        pytest.param("qwen3-1.7b", 8, 128, 10.53, id="cell"),
        # the danube round cell's shape, head_dim 80 and an untied 32k head:
        # 11.22 GiB (arguments 6.85, temp 4.38) before the products were
        # kept; they add 2.15 GiB of residuals
        pytest.param("h2o-danube-1.8b", 16, 128, 11.22, id="danube-cell"),
    ],
)
def test_cohort_round_eval_fits_one_chip(cohort_programs, arch, batch, seq, before_gib):
    """The full-width cohort-4 train+eval program in cond-mode STLD: the
    frozen base has one copy whatever the cohort, so arguments plus the
    compiler's temp space stay under the limit of the chip's 16 GB.

    At the round cells' shapes the limit is derived: the total before the
    gates kept their layers' products (``before_gib``), plus 1.25 x the
    residual stack one client step keeps across its gates, as the engine
    reckons it, plus 0.25 GiB, and never above 14 GiB of the chip's 15.75.
    A per-layer copy of the weights (2.6 GiB for qwen's in bfloat16, 3.1
    for danube's) would pass it."""
    compiled, arg_bytes = cohort_programs(arch, batch, seq)
    temp = compiled.memory_analysis().temp_size_in_bytes
    total = arg_bytes + temp
    if before_gib is None:
        limit = 15.0 * GiB
    else:
        saved = _saved_bytes_per_step(arch, batch, seq)
        limit = min(before_gib * GiB + 1.25 * saved + 0.25 * GiB, 14.0 * GiB)
    assert total < limit, (
        f"args + temp = {total / GiB:.2f} GiB against {limit / GiB:.2f} GiB"
    )


@pytest.mark.parametrize(
    "arch,batch,seq",
    [
        pytest.param("qwen3-1.7b", 8, 128, id="cell"),
        pytest.param("h2o-danube-1.8b", 16, 128, id="danube-cell"),
    ],
)
def test_training_head_runs_at_the_loss_tail_only(cohort_programs, arch, batch, seq):
    """With the synthetic task's tail of 1 no op of the round cells' program
    makes a ``[batch, seq, vocab]`` result: the training head and its loss
    run at the last position."""
    compiled, _ = cohort_programs(arch, batch, seq)
    vocab = get_config(arch).vocab_size
    full = re.findall(rf"\w+\[(?:\d+,)*{batch},{seq},{vocab}\]", compiled.as_text())
    assert not full, f"head products over every position: {sorted(set(full))}"


def test_gated_remat_temp_is_the_reckoned_residual_stack(one_chip):
    """What the gated, rematerialized stack's gradient keeps beyond a
    checkpointed, ungated stack (which saves only each layer's input) is the
    residual stack that ``gated_saved_bytes`` reckons, within 25 %: the
    products and nothing else, no weight slice.  At the qwen cell's shape,
    on the chip's compiler: the CPU compiler copies a carried buffer that a
    ``cond`` updates, so its temp holds the stack twice."""
    cfg = get_config("qwen3-1.7b")
    key = jax.random.PRNGKey(0)
    spec = lambda tree: jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    layers = spec(jax.eval_shape(lambda: init_params(key, cfg)["layers"]))
    peft = spec(jax.eval_shape(lambda: peft_lib.init_peft(key, cfg, PEFTConfig())))
    h = _spec(one_chip, (8, 128, cfg.d_model), jnp.dtype(cfg.dtype))
    positions = jnp.arange(128)

    def temp(gated):
        def loss(peft, layers, h):
            drops = jnp.arange(cfg.num_layers) % 2 == 1 if gated else None
            out, _, _ = transformer.stack_apply(
                layers, cfg, h, positions=positions, drops=drops, peft=peft, remat=True
            )
            return jnp.sum(out.astype(jnp.float32) ** 2)

        compiled = jax.jit(jax.grad(loss)).lower(peft, layers, h).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    kept = temp(True) - temp(False)
    reckoned = transformer.gated_saved_bytes(layers, cfg, h, positions=positions, peft=peft)
    assert reckoned > 0
    assert abs(kept - reckoned) <= 0.25 * reckoned, (kept, reckoned)


def _saved_bytes_per_step(arch, batch, seq) -> int:
    """``CohortEngine.saved_activation_bytes_per_step`` at a shape: the
    residual stack one client step keeps across its gates."""
    cfg = get_config(arch)
    base = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    peft = jax.eval_shape(lambda: peft_lib.init_peft(jax.random.PRNGKey(1), cfg, PEFTConfig()))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return gated_saved_bytes(base, cfg, tokens, peft=peft)


def _compile_cohort_round_eval(one_chip, arch, batch, seq):
    cfg = get_config(arch)
    pcfg = PEFTConfig()
    n, steps, val_pad = 4, 4, 64
    spec = lambda tree, lead=(): jax.tree.map(
        lambda x: _spec(one_chip, lead + x.shape, x.dtype), tree
    )
    base = spec(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    peft = spec(
        jax.eval_shape(lambda: peft_lib.init_peft(jax.random.PRNGKey(1), cfg, pcfg)), (n,)
    )
    i32 = lambda *s: _spec(one_chip, s, jnp.int32)
    f32 = lambda *s: _spec(one_chip, s, jnp.float32)
    args = (
        base,
        peft,
        {
            "tokens": i32(n, steps, batch, seq),
            "targets": i32(n, steps, batch, seq),
            "mask": f32(n, steps, batch, seq),
        },
        f32(n),
        _spec(one_chip, (n, 2), jnp.uint32),
        i32(n),
        i32(n, val_pad, seq),
        i32(n, val_pad),
        f32(n, val_pad),
        i32(4),
    )
    fns = make_client_fns(
        cfg, pcfg, STLDConfig(mode="cond"), TrainConfig(), donate=True,
        loss_tail=SyntheticTask.loss_tail,
    )
    return fns.cohort_round_eval.lower(*args).compile(), _nbytes(args)
