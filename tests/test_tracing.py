"""The program's own tracing (``repro.obs``): the host spans of a sync round,
the layer bodies a client step runs in each gating mode, the rows it runs the
head at, and the named scopes of the client programs, which add metadata and
nothing else."""
import contextlib
import dataclasses
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.configs import FederatedConfig, PEFTConfig, STLDConfig, TrainConfig, get_config
from repro.data import make_task
from repro.data.synthetic import SyntheticTask
from repro.federated.client import make_client_fns

_CFG = get_config("qwen3-1.7b", smoke=True).replace(
    num_layers=4, d_model=32, d_ff=64, num_heads=2, num_kv_heads=2,
    vocab_size=128, dtype="float32",
)
_FED = FederatedConfig(num_devices=6, devices_per_round=3, local_steps=2, batch_size=4)
_TRAIN = TrainConfig(learning_rate=5e-3, total_steps=100, warmup_steps=2)
_PEFT = PEFTConfig(method="lora", lora_rank=2)
PHASES = ["configure", "stage", "dispatch", "pull", "aggregate", "report"]


def _runner(rate, seed=3, mode="cond", cohort_mode="batched", fed_cfg=_FED, task=None):
    return api.build(
        "droppeft",
        cfg=_CFG,
        peft_cfg=_PEFT,
        stld_cfg=STLDConfig(mode=mode, mean_rate=rate, gather_bucket=1),
        fixed_rate=rate,
        fed_cfg=fed_cfg,
        train_cfg=_TRAIN,
        schedule="sync",
        task=task,
        seed=seed,
        cohort_mode=cohort_mode,
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two sync rounds at rate 0.5 under the profiler; the runner and the
    program's host spans, ``(name, start_ns, end_ns, args)`` in order of
    start."""
    runner = _runner(0.5)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        runner.run(rounds=2)
    (path,) = out.rglob("*.xplane.pb")
    spans = sorted(
        ((e.name, e.start_ns, e.end_ns, dict(e.stats))
         for plane in ProfileData.from_file(str(path)).planes
         if plane.name.startswith("/host")
         for line in plane.lines for e in line.events if e.name.startswith("repro.")),
        key=lambda s: s[1],
    )
    return runner, spans


def test_init_span_carries_the_parameter_bytes(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        runner = _runner(0.5)
    (path,) = tmp_path.rglob("*.xplane.pb")
    (init,) = [dict(e.stats) for plane in ProfileData.from_file(str(path)).planes
               if plane.name.startswith("/host")
               for line in plane.lines for e in line.events if e.name == "repro.init"]
    base = jax.tree.leaves(runner.ctx.engine.base_params)
    assert init == {"bytes": sum(x.nbytes for x in base)}


def test_each_round_span_holds_its_phases_in_order(traced):
    runner, spans = traced
    rounds = [s for s in spans if s[0] == "repro.round"]
    assert [s[3] for s in rounds] == [{"round": 0}, {"round": 1}]
    for _, r0, r1, _ in rounds:
        inside = [s for s in spans if r0 <= s[1] and s[2] <= r1 and s[0] != "repro.round"]
        assert [s[0] for s in inside] == [f"repro.round.{p}" for p in PHASES]
        # the phases take their round from the span around them; the
        # dispatch carries the head's rows per client step (the synthetic
        # task's tail of 1 on each of the batch's rows) and the bytes a
        # client step keeps across its gates
        args = {s[0]: s[3] for s in inside}
        assert args.pop("repro.round.dispatch") == {
            "head_rows": _FED.batch_size,
            "saved_bytes": runner.ctx.engine.saved_activation_bytes_per_step(),
        }
        assert all(a == {} for a in args.values())
    # the evaluation that ends the run call follows the rounds
    (evaluate,) = [s for s in spans if s[0] == "repro.evaluate"]
    assert evaluate[1] >= rounds[-1][2]
    assert evaluate[3] == {"round": 2}


def test_kept_layers_within_the_bodies_run(traced):
    runner, _ = traced
    # the batched cohort gates with real conds: a step runs only the
    # layers it keeps, which the rounds' active share counts
    assert runner.ctx.engine.layer_bodies_per_step(0.5) is None
    active = [row["active"] for row in runner.state.history]
    for a in active:
        assert 0 < a * _CFG.num_layers <= _CFG.num_layers
    assert sum(active) < len(active)


@pytest.mark.parametrize(
    "mode,cohort_mode,rate,bodies",
    [
        ("cond", "batched", 0.0, None),  # the conds run only the kept layers
        ("gather", "batched", 0.5, 2),  # round(4 x 0.5), bucket 1
        ("cond", "sequential", 0.5, None),  # the cond runs only the kept layers
    ],
)
def test_layer_bodies_per_step_follows_the_gating_mode(mode, cohort_mode, rate, bodies):
    engine = _runner(rate, mode=mode, cohort_mode=cohort_mode).ctx.engine
    assert engine.layer_bodies_per_step(rate) == bodies


@pytest.mark.parametrize("mode", ["cond", "gather"])
def test_dispatch_span_carries_the_saved_bytes(tmp_path, mode):
    """``repro.round.dispatch`` carries ``saved_bytes``, the engine's
    reckoning of what a client step keeps across its STLD gates: every
    layer's products under the cond gates at rate 0.5, nothing in gather
    mode, which runs no gate."""
    runner = _runner(0.5, mode=mode)
    with jax.profiler.trace(str(tmp_path)):
        runner.run(rounds=1)
    (path,) = tmp_path.rglob("*.xplane.pb")
    (dispatch,) = [dict(e.stats) for plane in ProfileData.from_file(str(path)).planes
                   if plane.name.startswith("/host")
                   for line in plane.lines for e in line.events
                   if e.name == "repro.round.dispatch"]
    saved = runner.ctx.engine.saved_activation_bytes_per_step()
    assert dispatch["saved_bytes"] == saved
    # cond: q, k, v, o, gate, up and both factors of the q and v LoRA
    # products of each layer, over the step's rows, in float32
    b, s, r = _FED.batch_size, runner.ctx.engine.task.seq_len, _PEFT.lora_rank
    hd = _CFG.resolved_head_dim
    q, kv = _CFG.num_heads * hd, _CFG.num_kv_heads * hd
    widths = (q, kv, kv, _CFG.d_model, _CFG.d_ff, _CFG.d_ff) + (r, q) + (r, kv)
    per_layer = b * s * 4 * sum(widths)
    assert saved == (_CFG.num_layers * per_layer if mode == "cond" else 0)


class _EveryPosition(SyntheticTask):
    """The synthetic task stated with no loss tail."""

    loss_tail = None


@pytest.mark.parametrize("tail,rows", [(1, 8), (None, 8 * 128)], ids=["tail", "every"])
def test_head_positions_per_step_at_the_round_cell_shape(tail, rows):
    """8 rows of 128 tokens per client step, as in the qwen round cell: the
    head runs at the synthetic task's last position, or at every position
    of a task that states no tail."""
    task = make_task(num_examples=64, vocab_size=_CFG.vocab_size, seq_len=128, seed=0)
    if tail is None:
        task = _EveryPosition(**{f.name: getattr(task, f.name) for f in dataclasses.fields(task)})
    fed = FederatedConfig(num_devices=2, devices_per_round=2, local_steps=1, batch_size=8)
    engine = _runner(0.5, fed_cfg=fed, task=task).ctx.engine
    assert engine.loss_tail == tail
    assert engine.head_positions_per_step() == rows


_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")


def _program(text):
    """Optimized HLO text without its metadata, and with every instruction
    and computation name replaced by its order of first appearance: a named
    scope can move the number the name uniquifier appends to a few names."""
    names = {}
    text = _METADATA.sub("", text)
    return _NAME.sub(lambda m: names.setdefault(m.group(), f"%n{len(names)}"), text)


def _cohort_round_eval_text(runner):
    """The optimized HLO of the runner's batched ``cohort_round_eval``, built
    anew from ``make_client_fns`` at the arguments one round passes."""
    engine = runner.ctx.engine
    seen = {}
    fn = engine.client.cohort_round_eval

    def keep(*a, **kw):
        seen.setdefault("args", (a, kw))
        return fn(*a, **kw)

    engine.client = engine.client._replace(cohort_round_eval=keep)
    runner.run(rounds=1)
    a, kw = seen["args"]
    client = make_client_fns(_CFG, _PEFT, runner.ctx.stld_cfg, _TRAIN)
    return client.cohort_round_eval.lower(*a, **kw).compile().as_text()


def test_scopes_name_the_phases_and_change_nothing_else(monkeypatch):
    texts = {}
    for scoped in (True, False):  # one call site, so the stack frames match
        with monkeypatch.context() as m:
            if not scoped:
                m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            texts[scoped] = _cohort_round_eval_text(_runner(0.5))
    op_names = re.findall(r'op_name="([^"]*)"', texts[True])
    assert any("client.train" in n for n in op_names)
    assert any("client.validate" in n for n in op_names)
    assert "client.train" not in texts[False] and "client.validate" not in texts[False]
    assert _program(texts[True]) == _program(texts[False])
