"""Training's head and loss at the positions the loss keeps.

A task that states a ``loss_tail`` (the synthetic task: 1, since its batches
mask every position but the last) has the client programs run the final
norm, the head and the loss on the last ``loss_tail`` positions only.  Rows
are independent through all three and a masked position adds zero to the
loss, so with float32 compute the step must give the numbers the program
over every position gives: loss, accuracy, LoRA gradients, the Eq.-6
importances and the updated adapter.  A batch whose mask reaches before the
tail is refused on the host."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import FederatedConfig, PEFTConfig, STLDConfig, TrainConfig, get_config
from repro.core import peft as peft_lib
from repro.data import make_task
from repro.data.synthetic import SyntheticTask
from repro.federated.client import _model_batch, make_client_fns
from repro.models.losses import softmax_xent
from repro.models.registry import init_params, model_apply
from repro.optim import adamw_init

_TINY = dict(num_layers=3, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
             vocab_size=128, dtype="float32")
_PEFT = PEFTConfig(method="lora", lora_rank=2)
_TRAIN = TrainConfig(learning_rate=5e-3, total_steps=100, warmup_steps=2)
_SEQ, _BATCH = 16, 4
# the transformer family, the vision-prefix stub and the encoder-decoder
ARCHS = ["qwen3-1.7b", "internvl2-76b", "whisper-tiny"]


def _cfg(arch):
    return get_config(arch, smoke=True).replace(**_TINY)


def _task(cfg, **kw):
    return make_task(num_examples=128, vocab_size=cfg.vocab_size, seq_len=_SEQ, seed=0, **kw)


def _batches(task, steps):
    """``steps`` synthetic batches stacked on a leading step axis."""
    rows = np.arange(steps * _BATCH).reshape(steps, _BATCH)
    bs = [task.lm_batch(r) for r in rows]
    return {k: jnp.asarray(np.stack([b[k] for b in bs])) for k in ("tokens", "targets", "mask")}


def _params(cfg):
    key = jax.random.PRNGKey(0)
    base = init_params(key, cfg)
    peft = peft_lib.init_peft(jax.random.fold_in(key, 1), cfg, _PEFT)
    # b starts at zero, which would leave a's gradient zero: perturb it so
    # every LoRA factor carries gradient
    peft = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.fold_in(key, x.size), x.shape, x.dtype),
        peft,
    )
    return base, peft


def _assert_trees_close(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("arch", ARCHS)
def test_tail_logits_and_gradients_equal_every_position(arch):
    """``model_apply(tail=k)`` gives the last ``k`` rows of the full logits,
    and the masked loss through it the same loss, accuracy and LoRA
    gradients."""
    cfg = _cfg(arch)
    base, peft = _params(cfg)
    b = jax.tree.map(lambda x: x[0], _batches(_task(cfg), 1))
    batch = _model_batch(cfg, b["tokens"])

    def loss(peft, tail):
        logits, _, _ = model_apply(base, cfg, batch, peft=peft, tail=tail)
        targets, mask = b["targets"], b["mask"]
        if tail is not None:
            targets, mask = targets[:, -tail:], mask[:, -tail:]
        else:
            logits = logits[:, -_SEQ:]  # the vision stub's prefix
        out, metrics = softmax_xent(logits, targets, mask)
        return out, (metrics["accuracy"], logits)

    (l_all, (acc_all, logits_all)), g_all = jax.value_and_grad(loss, has_aux=True)(peft, None)
    (l_tail, (acc_tail, logits_tail)), g_tail = jax.value_and_grad(loss, has_aux=True)(peft, 1)
    assert logits_tail.shape == (_BATCH, 1, cfg.vocab_size)
    np.testing.assert_allclose(logits_tail, logits_all[:, -1:], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(l_tail, l_all, rtol=1e-6)
    assert float(acc_tail) == float(acc_all)
    assert any(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(g_all))
    _assert_trees_close(g_tail, g_all)


@pytest.mark.parametrize(
    "arch,mode",
    [
        ("qwen3-1.7b", "cond"),
        ("qwen3-1.7b", "gather"),
        ("internvl2-76b", "cond"),
        ("whisper-tiny", "cond"),
    ],
)
@pytest.mark.parametrize("steps", [1, 3], ids=["step", "round"])
def test_local_round_with_the_tail_equals_every_position(arch, mode, steps):
    """One step and a whole ``local_round``: the same loss, accuracy, grad
    norm, importances and updated adapter with and without the tail."""
    cfg = _cfg(arch)
    task = _task(cfg)
    assert task.loss_tail == 1
    stld_cfg = STLDConfig(mode=mode, mean_rate=0.5, gather_bucket=1)
    num_active = 2 if mode == "gather" else None
    base, peft = _params(cfg)
    batches = _batches(task, steps)
    outs = {}
    for tail in (None, task.loss_tail):
        fns = make_client_fns(
            cfg, _PEFT, stld_cfg, _TRAIN, donate=False, loss_tail=tail
        )
        peft_out, _, metrics, importance = fns.local_round(
            base, peft, adamw_init(peft), batches, jnp.float32(0.5),
            jax.random.PRNGKey(7), jnp.int32(0), num_active=num_active,
        )
        outs[tail] = (peft_out, metrics, importance)
    (p0, m0, i0), (p1, m1, i1) = outs[None], outs[task.loss_tail]
    for k in ("loss", "accuracy", "grad_norm", "active_layers"):
        np.testing.assert_allclose(m1[k], m0[k], rtol=1e-5, err_msg=k)
    assert float(m0["grad_norm"]) > 0
    np.testing.assert_allclose(i1, i0, rtol=1e-5, atol=1e-7)
    _assert_trees_close(p1, p0)


class _EveryPosition(SyntheticTask):
    """The synthetic task's batches, stated with no tail: the programs run
    head and loss over every position."""

    loss_tail = None


class _MaskBeforeTheTail(SyntheticTask):
    """Keeps the stated tail of 1 but masks the first position as well."""

    def lm_batch(self, idx):
        out = super().lm_batch(idx)
        out["mask"][:, 0] = 1.0
        return out


def _as(task, cls):
    return cls(**{f.name: getattr(task, f.name) for f in dataclasses.fields(task)})


def _runner(task, cohort_mode="batched", mode="cond"):
    cfg = _cfg("qwen3-1.7b")
    return api.build(
        "droppeft",
        cfg=cfg,
        peft_cfg=_PEFT,
        stld_cfg=STLDConfig(mode=mode, mean_rate=0.5, gather_bucket=1),
        fixed_rate=0.5,
        fed_cfg=FederatedConfig(num_devices=4, devices_per_round=2, local_steps=2,
                                batch_size=_BATCH),
        train_cfg=_TRAIN,
        schedule="sync",
        task=task,
        seed=3,
        cohort_mode=cohort_mode,
    )


@pytest.mark.parametrize(
    "cohort_mode,mode", [("batched", "cond"), ("batched", "gather"), ("sequential", "cond")]
)
def test_rounds_with_the_task_tail_equal_every_position(cohort_mode, mode):
    """Whole rounds through the engine, which takes the tail from the task:
    the same losses, accuracies and global adapter as the same data stated
    with no tail."""
    task = _task(_cfg("qwen3-1.7b"))
    runs = {}
    for t in (task, _as(task, _EveryPosition)):
        runner = _runner(t, cohort_mode, mode)
        assert runner.ctx.engine.loss_tail == t.loss_tail
        res = runner.run(rounds=2)
        runs[t.loss_tail] = (res, runner.state.global_peft)
    (r0, g0), (r1, g1) = runs[None], runs[1]
    np.testing.assert_allclose(r1.loss, r0.loss, rtol=1e-5)
    np.testing.assert_allclose(r1.accuracy, r0.accuracy, rtol=1e-6)
    _assert_trees_close(g1, g0)


def test_a_mask_before_the_tail_is_refused_on_the_host():
    task = _as(_task(_cfg("qwen3-1.7b")), _MaskBeforeTheTail)
    runner = _runner(task)
    with pytest.raises(ValueError, match="before the last 1 positions"):
        runner.run(rounds=1)


def test_loss_tail_must_be_positive():
    with pytest.raises(ValueError, match="at least 1"):
        make_client_fns(_cfg("qwen3-1.7b"), _PEFT, STLDConfig(), _TRAIN, loss_tail=0)
