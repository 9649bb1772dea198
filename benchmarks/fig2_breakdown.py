"""Paper Fig. 2: computation-time breakdown (forward vs backward).

Measured on the CPU smoke model: FFT (grads w.r.t. everything), PEFT
(grads w.r.t. LoRA only — backward shrinks, forward doesn't), and
DropPEFT/STLD at rate 0.5 (both passes shrink).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, sim_model_cfg, timeit
from repro.configs import PEFTConfig
from repro.core import peft as peft_lib
from repro.models import init_params, model_apply
from repro.models.losses import softmax_xent


def run(quick: bool = False):
    cfg = sim_model_cfg().replace(num_layers=8)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    peft = peft_lib.init_peft(key, cfg, PEFTConfig(method="lora", lora_rank=4))
    batch = jax.random.randint(key, (8, 64), 0, cfg.vocab_size)
    drops_none = jnp.zeros((8,), dtype=bool)
    drops_half = jnp.array([False, True] * 4)

    @jax.jit
    def fwd(p, pf, drops):
        logits, aux, _ = model_apply(p, cfg, {"tokens": batch}, peft=pf, drops=drops)
        loss, _ = softmax_xent(logits[:, :-1], batch[:, 1:])
        return loss

    @jax.jit
    def fwd_bwd_fft(p, pf, drops):
        return jax.grad(lambda pp: fwd(pp, pf, drops))(p)

    @jax.jit
    def fwd_bwd_peft(p, pf, drops):
        return jax.grad(lambda x: fwd(p, x, drops))(pf)

    t_fwd = timeit(fwd, params, peft, drops_none)
    t_fft = timeit(fwd_bwd_fft, params, peft, drops_none)
    t_peft = timeit(fwd_bwd_peft, params, peft, drops_none)
    t_drop_f = timeit(fwd, params, peft, drops_half)
    t_drop = timeit(fwd_bwd_peft, params, peft, drops_half)

    emit("fig2/forward", t_fwd)
    emit("fig2/fft_total", t_fft, f"bwd={t_fft - t_fwd:.0f}us;fwd_share={t_fwd/t_fft:.2f}")
    emit("fig2/peft_total", t_peft, f"bwd={t_peft - t_fwd:.0f}us;fwd_share={t_fwd/t_peft:.2f}")
    emit("fig2/droppeft_total", t_drop, f"fwd={t_drop_f:.0f}us")

    # paper claims: PEFT shortens backward but forward is untouched ->
    # forward share grows; STLD cuts BOTH.
    assert t_peft < t_fft
    assert t_drop < 0.9 * t_peft, f"STLD should cut total: {t_drop:.0f} vs {t_peft:.0f}"
