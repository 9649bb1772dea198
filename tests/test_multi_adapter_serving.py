"""Multi-tenant adapter serving: segmented kernel parity, pool hot-swap,
continuous batching, checkpoint round-trip, and stop handling.

The load-bearing claims:

- the segmented gather kernel matches the per-request switching reference
  BIT-FOR-BIT for a mixed-rank (hetlora) pool, including the blocked/padded
  N path — so a multi-tenant server provably changes no tenant's logits;
- a hot-swapped slot's stale high-rank tail is inert (the in-kernel rank
  mask, not a host-side zeroing pass, guarantees it);
- adapter hot-swap in steady state compiles ZERO new XLA programs;
- federated ``save_state`` checkpoints round-trip through the registry to
  identical serving logits.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import save_state
from repro.configs import PEFTConfig, get_config
from repro.core import peft as peft_lib
from repro.kernels.ops import segmented_lora
from repro.kernels.ref import segmented_lora_ref
from repro.launch.steps import make_serve_step
from repro.models.registry import init_params
from repro.serving.adapters import AdapterPoolCache, AdapterRegistry
from repro.serving.batcher import ContinuousBatcher, Request, batched_caches
from repro.serving.decode import generate


def _pool(key, *, m=6, k=32, n=192, ranks=(2, 4, 8)):
    """Random mixed-rank pool: rows cycle through the slots."""
    r_max = max(ranks)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) * 0.1
    a = jax.random.normal(ks[2], (len(ranks), k, r_max), jnp.float32) * 0.1
    b = jax.random.normal(ks[3], (len(ranks), r_max, n), jnp.float32) * 0.1
    # zero each adapter's tail beyond its true rank, as the pool cache does
    for s, r in enumerate(ranks):
        a = a.at[s, :, r:].set(0.0)
        b = b.at[s, r:, :].set(0.0)
    idx = jnp.arange(m, dtype=jnp.int32) % len(ranks)
    return x, w, a, b, idx, jnp.asarray(ranks, jnp.int32)


def test_segmented_kernel_bitexact_mixed_ranks(key):
    x, w, a, b, idx, ranks = _pool(key)  # n=192: exercises block padding
    ref = segmented_lora_ref(x, w, a, b, idx, ranks)
    for block_n in (64, 128):
        out = segmented_lora(x, w, a, b, idx, ranks, block_n=block_n)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), block_n


def test_segmented_kernel_xla_path_allclose(key):
    x, w, a, b, idx, ranks = _pool(key)
    ref = segmented_lora_ref(x, w, a, b, idx, ranks)
    out = segmented_lora(x, w, a, b, idx, ranks, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_hot_swap_stale_tail_inert(key):
    """A rank-4 adapter swapped into a slot that held rank-8 leaves garbage
    in rows/cols 4..8 of the pool; the rank mask must keep it inert."""
    x, w, a, b, idx, _ = _pool(key, ranks=(8, 8, 8))
    ranks = jnp.asarray([4, 8, 8], jnp.int32)  # slot 0 now serves rank 4
    dirty = segmented_lora(x, w, a, b, idx, ranks)
    clean_a = a.at[0, :, 4:].set(0.0)
    clean_b = b.at[0, 4:, :].set(0.0)
    clean = segmented_lora(x, w, clean_a, clean_b, idx, ranks)
    assert np.array_equal(np.asarray(dirty), np.asarray(clean))


def _two_tenant_setup(key, num_layers=2):
    cfg = get_config("qwen3-1.7b", smoke=True).replace(
        num_layers=num_layers, dtype="float32"
    )
    params = init_params(key, cfg)
    trees = {}
    for i, rank in enumerate((4, 8)):
        pcfg = PEFTConfig(method="lora", lora_rank=rank, lora_targets=("q", "v"))
        tree = peft_lib.init_peft(jax.random.fold_in(key, i), cfg, pcfg)
        # randomize b so adapters actually differ (LoRA init keeps b = 0)
        trees[f"client{i}"] = jax.tree.map(
            lambda x: x
            + 0.02 * jax.random.normal(jax.random.fold_in(key, 99), x.shape),
            tree,
        )
    return cfg, params, trees


def test_batched_mixed_adapters_match_per_request_switching(key):
    """Tokens from one mixed-adapter batch == each request served alone
    (whole batch pinned to its adapter) through the same compiled step."""
    cfg, params, trees = _two_tenant_setup(key)
    reg = AdapterRegistry()
    for name, tree in trees.items():
        reg.register(name, tree)
    pool = AdapterPoolCache(reg, n_slots=2)
    serve = make_serve_step(cfg)

    B = 3
    prompts = [[5, 7, 11], [13, 17], [19, 23, 29, 31]]
    adapters = ["client0", "client1", "client0"]

    batcher = ContinuousBatcher(
        serve, params, cfg, pool, batch=B, max_len=16, cache_dtype=jnp.float32
    )
    for j in range(B):
        batcher.submit(
            Request(prompt=prompts[j], adapter=adapters[j], max_new_tokens=4, uid=j)
        )
    done = {c.uid: c for c in batcher.run()}
    assert len(done) == B

    for j in range(B):
        solo = ContinuousBatcher(
            serve, params, cfg, pool, batch=B, max_len=16, cache_dtype=jnp.float32
        )
        # uniform batch: every row is a copy of request j (adapter switching)
        for z in range(B):
            solo.submit(
                Request(
                    prompt=prompts[j],
                    adapter=adapters[j],
                    max_new_tokens=4,
                    uid=f"{j}.{z}",
                )
            )
        ref = {c.uid: c for c in solo.run()}[f"{j}.0"]
        assert done[j].tokens == ref.tokens, j
        assert done[j].finish_reason == ref.finish_reason


@pytest.mark.parametrize("budgets", [(2, 8), (8, 2)])
def test_hot_swap_mid_generation_matches_solo(key, budgets):
    """3 tenants through a 2-slot pool at batch 2: admitting the queued
    third request evicts a slot (hot-swap) while the other row is still
    generating — neither request's tokens may change vs running alone.

    Both budget orders matter: with ``(8, 2)`` the LONG-running row's
    adapter is the LRU-order eviction candidate when t2 admits, so only
    live-row pinning keeps the mid-generation row on its own weights."""
    cfg, params, trees = _two_tenant_setup(key)
    reg = AdapterRegistry()
    for i in range(3):
        reg.register(f"t{i}", trees[f"client{i % 2}"])
    serve = make_serve_step(cfg)

    def serve_all(requests, batch):
        pool = AdapterPoolCache(reg, n_slots=2)
        b = ContinuousBatcher(
            serve, params, cfg, pool, batch=batch, max_len=16,
            cache_dtype=jnp.float32,
        )
        for r in requests:
            b.submit(r)
        return {c.uid: c.tokens for c in b.run()}, pool.swaps

    reqs = [
        Request(prompt=[5, 7], adapter="t0", max_new_tokens=budgets[0], uid=0),
        Request(prompt=[11, 13], adapter="t1", max_new_tokens=budgets[1], uid=1),
        Request(prompt=[17, 19], adapter="t2", max_new_tokens=3, uid=2),
    ]
    got, swaps = serve_all(reqs, batch=2)
    assert swaps == 3  # t2's admission really displaced a resident adapter
    for r in reqs:
        solo, _ = serve_all(
            [Request(prompt=r.prompt, adapter=r.adapter,
                     max_new_tokens=r.max_new_tokens, uid=r.uid)], batch=2
        )
        assert got[r.uid] == solo[r.uid], r.uid


def test_admission_defers_until_slot_free(key):
    """batch > n_slots with all-distinct adapters: the third request must
    wait in the queue (not evict a live row's slot) and admit only after a
    completion releases its pin — tokens still match running alone."""
    cfg, params, trees = _two_tenant_setup(key)
    reg = AdapterRegistry()
    for i in range(3):
        reg.register(f"t{i}", trees[f"client{i % 2}"])
    serve = make_serve_step(cfg)

    def serve_all(requests):
        pool = AdapterPoolCache(reg, n_slots=2)
        b = ContinuousBatcher(
            serve, params, cfg, pool, batch=3, max_len=16,
            cache_dtype=jnp.float32,
        )
        for r in requests:
            b.submit(r)
        return {c.uid: c.tokens for c in b.run()}

    reqs = [
        Request(prompt=[5, 7], adapter="t0", max_new_tokens=6, uid=0),
        Request(prompt=[11, 13], adapter="t1", max_new_tokens=2, uid=1),
        Request(prompt=[17, 19], adapter="t2", max_new_tokens=3, uid=2),
    ]
    got = serve_all(reqs)
    assert len(got) == 3
    for r in reqs:
        solo = serve_all([Request(prompt=r.prompt, adapter=r.adapter,
                                  max_new_tokens=r.max_new_tokens, uid=r.uid)])
        assert got[r.uid] == solo[r.uid], r.uid


def test_batcher_guards(key):
    """submit() rejects prompts that would wrap the KV ring; run() raises
    instead of silently dropping in-flight work on step-budget exhaustion
    or a queue stalled by external pins; lookup() rejects more distinct
    adapters than slots."""
    cfg, params, trees = _two_tenant_setup(key, num_layers=1)
    reg = AdapterRegistry()
    for i in range(3):
        reg.register(f"t{i}", trees[f"client{i % 2}"])
    serve = make_serve_step(cfg)

    def make(pool):
        return ContinuousBatcher(
            serve, params, cfg, pool, batch=2, max_len=8,
            cache_dtype=jnp.float32,
        )

    b = make(AdapterPoolCache(reg, n_slots=2))
    with pytest.raises(ValueError, match="cache positions"):
        b.submit(Request(prompt=list(range(8)), adapter="t0"))

    b.submit(Request(prompt=[3, 5], adapter="t0", max_new_tokens=4, uid=0))
    with pytest.raises(RuntimeError, match="max_steps"):
        b.run(max_steps=1)

    pool = AdapterPoolCache(reg, n_slots=2)
    pool.pin("t0")
    pool.pin("t1")
    b2 = make(pool)
    b2.submit(Request(prompt=[3, 5], adapter="t2", max_new_tokens=2, uid=0))
    with pytest.raises(RuntimeError, match="pinned"):
        b2.run()
    pool.unpin("t0")
    assert len(b2.run()) == 1  # releasing a pin unblocks the queue

    with pytest.raises(ValueError, match="distinct adapters"):
        pool.lookup(["t0", "t1", "t2"])


def test_checkpoint_roundtrip_identical_logits(key, tmp_path):
    """save_state -> load_checkpoint serves logits identical to in-process
    registration of the same trees."""
    cfg, params, trees = _two_tenant_setup(key)

    direct = AdapterRegistry()
    for i, (name, tree) in enumerate(sorted(trees.items())):
        direct.register(f"client{i}", tree)

    state = {
        "device_peft": {str(i): t for i, t in enumerate(
            [t for _, t in sorted(trees.items())]
        )},
    }
    save_state(str(tmp_path), 3, state)
    loaded = AdapterRegistry().load_checkpoint(str(tmp_path))
    assert sorted(loaded.names()) == ["client0", "client1"]

    serve = make_serve_step(cfg)
    B = 2
    token = jnp.asarray([[5], [7]], jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    logits = {}
    for tag, reg in (("direct", direct), ("checkpoint", loaded)):
        pool = AdapterPoolCache(reg, n_slots=2)
        peft = pool.pooled_peft(pool.lookup(["client0", "client1"]))
        caches = batched_caches(cfg, B, 8, dtype=jnp.float32)
        out, _, _ = serve(params, token, pos, caches, peft=peft)
        logits[tag] = np.asarray(out)
    assert np.array_equal(logits["direct"], logits["checkpoint"])


def test_adapter_hot_swap_zero_recompiles(key):
    """Rotating tenants through a full pool (LRU eviction + slot rewrite)
    must not compile a single new XLA program in steady state."""
    from repro.analysis.recompile_guard import recompile_guard

    cfg, params, trees = _two_tenant_setup(key)
    reg = AdapterRegistry()
    for i in range(4):  # 4 tenants, 2 slots -> every rotation hot-swaps
        reg.register(f"t{i}", trees[f"client{i % 2}"])
    pool = AdapterPoolCache(reg, n_slots=2)
    serve = make_serve_step(cfg)
    batcher = ContinuousBatcher(
        serve, params, cfg, pool, batch=2, max_len=16, cache_dtype=jnp.float32
    )

    def round_trip(tenants):
        for j, t in enumerate(tenants):
            batcher.submit(
                Request(prompt=[3 + j, 5], adapter=t, max_new_tokens=3, uid=t)
            )
        return batcher.run()

    round_trip(["t0", "t1"])  # warm: compiles step, slot write, row reset
    swaps_before = pool.swaps
    with recompile_guard(0, label="adapter hot-swap"):
        out = round_trip(["t2", "t3"])
        out += round_trip(["t0", "t1"])
    assert len(out) == 4
    assert pool.swaps - swaps_before == 4  # eviction really happened


def test_pool_lru_eviction_and_pinning(key):
    cfg, params, trees = _two_tenant_setup(key, num_layers=1)
    reg = AdapterRegistry()
    for i in range(3):
        reg.register(f"t{i}", trees[f"client{i % 2}"])
    pool = AdapterPoolCache(reg, n_slots=2)
    s0, s1 = pool.slot_of("t0"), pool.slot_of("t1")
    assert {s0, s1} == {0, 1}
    pool.slot_of("t0")  # refresh t0 -> t1 becomes LRU
    s2 = pool.slot_of("t2")
    assert s2 == s1  # t1 evicted, not t0
    pool.pin("t0")
    pool.pin("t2")
    with pytest.raises(RuntimeError):
        pool.slot_of("t1")  # all slots pinned
    pool.unpin("t2")
    assert pool.slot_of("t1") == s2


def test_generate_eos_and_budget_stops():
    """Per-row stop handling with a deterministic stub step: rows freeze
    independently on EOS or budget; frozen rows emit pad_id."""

    def stub_step(params, token, pos, caches):
        nxt = token + 1
        return None, nxt, caches

    first = jnp.asarray([[5], [10]], jnp.int32)
    toks, _ = generate(
        stub_step, None, jnp.zeros(()), first, 0, 6,
        eos_id=8, max_new_tokens=4,
    )
    assert toks[0].tolist() == [6, 7, 8, 0, 0, 0]  # EOS itself is emitted
    assert toks[1].tolist() == [11, 12, 13, 14, 0, 0]  # budget stop

    # per-row budgets and no-stop path both behave
    toks2, _ = generate(
        stub_step, None, jnp.zeros(()), first, 0, 5,
        max_new_tokens=jnp.asarray([2, 4]),
    )
    assert toks2[0].tolist() == [6, 7, 0, 0, 0]
    assert toks2[1].tolist() == [11, 12, 13, 14, 0]
    toks3, _ = generate(stub_step, None, jnp.zeros(()), first, 0, 3)
    assert toks3[0].tolist() == [6, 7, 8]
