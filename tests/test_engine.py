"""Serving engine behaviour: paged KV, scheduler, preemption, morphing loop,
state preservation across swaps (DESIGN.md §7)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServingConfig, reduced, MORPH_LLAMA2_7B, ASSIGNED
from repro.core import tree_bytes
from repro.engine import (EngineConfig, MorphServeEngine, TraceRequest,
                          azure_like)
from repro.engine.kv_cache import BlockAllocator, PagedKVPool, kv_block_bytes
from repro.engine.request import RState
from repro.models import lm


@pytest.fixture(scope="module")
def model():
    cfg = reduced(MORPH_LLAMA2_7B)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_engine(cfg, params, *, blocks=24, policy="morph", mode="performance",
                slots=4, compute="real", seed=0, **ecfg_kw):
    wb = tree_bytes(params)
    bb = kv_block_bytes(cfg, 16, 4)
    budget = int((wb + blocks * bb) / 0.95) + 2 * bb
    sc = ServingConfig(hbm_budget_bytes=budget, kv_block_size=16,
                       max_batch_slots=slots, max_seq_len=256,
                       swap_levels=(0, 1, 2, 4), mode=mode,
                       kv_resize_step_frac=0.25)
    return MorphServeEngine(cfg, params, sc,
                            EngineConfig(policy=policy, compute=compute,
                                         seed=seed, **ecfg_kw))


# --------------------------------------------------------------------------
# block allocator
# --------------------------------------------------------------------------
def test_allocator_basics():
    a = BlockAllocator(10)             # blocks 1..9
    ids = a.alloc(4)
    assert ids == [1, 2, 3, 4]
    assert a.alloc(6) is None           # only 5 left
    a.release(ids[:2])
    assert a.n_free == 7
    a.grow(14)
    assert a.n_free == 11
    assert a.num_blocks == 14


def test_allocator_shrink_tail_only():
    a = BlockAllocator(10)
    ids = a.alloc(3)                    # 1,2,3 used
    assert a.shrink(4)                  # tail 4..9 free -> ok
    assert a.num_blocks == 4
    assert not a.shrink(3)              # block 3 in use


def test_pool_resize_grow_preserves_content():
    cfg = reduced(MORPH_LLAMA2_7B)
    pool = PagedKVPool(cfg, 8, 4)
    pool.k = pool.k.at[0, 3].set(1.5)
    assert pool.resize(12)
    assert pool.num_blocks == 12
    assert float(pool.k[0, 3, 0, 0, 0]) == 1.5


def test_pool_within_bucket_resize_is_metadata_only():
    """Capacity bucketing: grows/shrinks inside the power-of-two capacity
    bucket must not copy the device pool (same array objects) nor change
    its shape (no new decode jit specialization)."""
    cfg = reduced(MORPH_LLAMA2_7B)
    pool = PagedKVPool(cfg, 9, 4)            # capacity bucket = 16
    assert pool.capacity == 16
    k_obj, v_obj = pool.k, pool.v
    assert pool.resize(12) and pool.resize(15) and pool.resize(10)
    assert pool.k is k_obj and pool.v is v_obj
    assert pool.copies == 0
    assert pool.num_blocks == 10             # logical size tracked apart


def test_pool_cross_bucket_resize_copies_once_and_preserves():
    cfg = reduced(MORPH_LLAMA2_7B)
    pool = PagedKVPool(cfg, 9, 4)            # capacity 16
    pool.k = pool.k.at[0, 3].set(1.5)
    k_obj = pool.k
    assert pool.resize(20)                   # bucket 16 -> 32: one copy
    assert pool.capacity == 32 and pool.copies == 1
    assert pool.k is not k_obj
    assert float(pool.k[0, 3, 0, 0, 0]) == 1.5
    # shrink back below the bucket boundary: exactly one more copy
    assert pool.resize(8)
    assert pool.capacity == 8 and pool.copies == 2
    assert float(pool.k[0, 3, 0, 0, 0]) == 1.5


def test_pool_bucketing_disabled_matches_seed_behavior():
    cfg = reduced(MORPH_LLAMA2_7B)
    pool = PagedKVPool(cfg, 8, 4, bucket_capacity=False)
    assert pool.capacity == 8 and pool.k.shape[1] == 8
    assert pool.resize(12)
    assert pool.capacity == 12 and pool.k.shape[1] == 12


def test_pool_capacity_bucket_stops_at_max_capacity():
    """The power-of-two bucket never passes ``max_capacity`` (the most
    blocks the budget can hold) but always holds the logical count."""
    cfg = reduced(MORPH_LLAMA2_7B)
    pool = PagedKVPool(cfg, 17, 4, max_capacity=20)
    assert pool.capacity == 20 and pool.k.shape[1] == 20
    assert pool.resize(24)                   # beyond the cap: exact size
    assert pool.capacity == 24 and pool.k.shape[1] == 24
    assert pool.resize(9)                    # below it: the bucket again
    assert pool.capacity == 16


# pool update -> the arrays it rewrites
_POOL_UPDATES = {"scatter": ("k", "v"), "quantize": ("k", "v"),
                 "dequantize": ("k", "v", "k_scale", "v_zero"),
                 "move": ("k", "v", "qk", "qv", "k_scale", "v_zero"),
                 "clear": ("k_scale", "k_zero", "v_scale", "v_zero"),
                 "scatter_quant": ("k", "v", "qk", "k_scale", "v_zero")}


def _pool_update(pool, op):
    """Run one device-side pool update on blocks 1-2 (filled, then
    quantized, unless the update is that step); return what it rewrote."""
    shape = (pool.k.shape[0], 2, pool.block_size, pool.kvh, pool.dh)
    kv = np.ones(shape, np.float32)
    if op != "scatter":
        pool.scatter_blocks([1, 2], kv, kv)
    if op not in ("scatter", "quantize"):
        pool.quantize_blocks([1, 2])
    payload = pool.gather_quant_blocks([1, 2]) if pool.qbits else None
    before = [getattr(pool, name) for name in _POOL_UPDATES[op]]
    {"scatter": lambda: pool.scatter_blocks([1, 2], kv, kv),
     "quantize": lambda: pool.quantize_blocks([1, 2]),
     "dequantize": lambda: pool.dequantize_blocks([1, 2]),
     "move": lambda: pool.move_blocks([1, 2], [5, 6]),
     "clear": lambda: pool.clear_quant([1, 2]),
     "scatter_quant": lambda: pool.scatter_quant_blocks([5, 6], payload),
     }[op]()
    return before


@pytest.mark.parametrize("op", sorted(_POOL_UPDATES))
def test_pool_updates_are_in_place(op):
    """Every device-side pool update donates the arrays it rewrites (the
    old buffers are gone, not copied): a pool sized to most of the chip's
    memory has no room for a second copy of itself."""
    pool = PagedKVPool(reduced(MORPH_LLAMA2_7B), 8, 4)
    before = _pool_update(pool, op)
    assert all(a.is_deleted() for a in before), op


def test_engine_pool_capacity_within_budget(model):
    """The engine caps the pool's capacity bucket at what its budget can
    ever book, so the device never holds a pool the ledger has no bytes
    for: a pinned fp16 pool never grows, so its capacity is its size; a
    morphing pool may grow into the bytes the lightest level frees."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=40, policy="static_fp16")
    assert eng.pool.capacity == eng.pool.num_blocks < 64
    eng = make_engine(cfg, params, blocks=40)
    most = eng.ledger.max_kv_blocks(
        min(eng.plan.weight_bytes(l) for l in eng.plan.levels)) + 1
    assert eng.pool.num_blocks <= eng.pool.capacity <= most


def test_landed_swap_counts_over_budget_booking(model):
    """A swap landing whose weights the pool cannot make room for (a busy
    tail blocks the shrink) is booked and counted; one the pool can make
    room for shrinks it and stays within budget."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=24, compute="sim")
    led = eng.ledger
    extra = 4 * led.kv_block_bytes
    # a free pool: the landing shrinks the pool by the 4 blocks it needs
    blocks = led.kv_blocks
    eng._commit_landed_weights(led.weight_bytes + extra)
    assert led.ok() and led.kv_blocks == blocks - 4
    assert eng.over_budget_landings == 0
    # every block held: nothing can shrink, the landing is booked over
    assert eng.pool.alloc.alloc(eng.pool.alloc.n_free) is not None
    eng._commit_landed_weights(led.weight_bytes + extra)
    assert not led.ok() and eng.over_budget_landings == 1


# --------------------------------------------------------------------------
# end-to-end engine runs (real compute)
# --------------------------------------------------------------------------
def test_engine_serves_trace_real(model):
    cfg, params = model
    eng = make_engine(cfg, params, blocks=30)
    trace = [TraceRequest(0.0, 20, 5), TraceRequest(0.01, 35, 6),
             TraceRequest(0.02, 10, 4)]
    rep = eng.run_trace(trace)
    assert rep.n_finished == 3
    fin = [r for r in eng.all_requests if r.state == RState.FINISHED]
    for r in fin:
        assert len(r.generated) == r.max_new_tokens
        assert len(r.token_times) == r.max_new_tokens
    # all blocks returned
    assert eng.pool.alloc.n_used == 0


def test_engine_preempts_under_block_exhaustion(model):
    cfg, params = model
    eng = make_engine(cfg, params, blocks=8, policy="static_fp16", slots=4)
    # two long requests that cannot both hold blocks to completion
    trace = [TraceRequest(0.0, 40, 40), TraceRequest(0.0, 40, 40)]
    rep = eng.run_trace(trace, max_steps=4000)
    assert rep.preemptions >= 1
    assert rep.n_finished == 2          # recompute path completes them


def test_engine_morphs_and_restores(model):
    """Pressure -> swap level rises + pool grows; drain -> restores to 0."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=6, mode="performance")
    trace = [TraceRequest(0.001 * i, 30, 32) for i in range(10)]
    rep = eng.run_trace(trace, max_steps=6000)
    levels = [t.swap_level for t in eng.monitor.history]
    assert max(levels) > 0, "pressure never triggered a swap"
    assert eng.actuator.level == 0 or levels[-1] <= max(levels)
    blocks = [t.kv_total_blocks for t in eng.monitor.history]
    assert max(blocks) > blocks[0], "KV pool never grew"
    assert rep.n_finished == len(trace)
    assert 0 < rep.degraded_token_frac < 1.0


def test_state_preserving_swap(model):
    """The paper's core state-preservation claim: a swap mid-decode does not
    disturb block tables or positions, and after restore the engine produces
    the same tokens as a never-swapped run (greedy, same seeds)."""
    cfg, params = model
    trace = [TraceRequest(0.0, 24, 8), TraceRequest(0.0, 18, 8)]
    eng_fp = make_engine(cfg, params, blocks=30, policy="static_fp16", seed=7)
    rep_fp = eng_fp.run_trace(trace)
    toks_fp = [r.generated for r in eng_fp.all_requests]

    eng_m = make_engine(cfg, params, blocks=30, policy="morph", seed=7)
    # force a swap to level 2 then immediately restore before any decode
    eng_m.actuator.issue(2, now=0.0)
    eng_m.actuator.poll(now=1e9)
    eng_m.actuator.issue(0, now=0.0)
    eng_m.actuator.poll(now=1e9)
    rep_m = eng_m.run_trace(trace)
    toks_m = [r.generated for r in eng_m.all_requests]
    assert toks_fp == toks_m, "swap->restore must be bit-transparent"


def test_quantized_decode_token_overlap(model):
    """Static int4 decode should mostly agree with fp16 on a trained-ish
    model? On random weights agreement is weaker — just require the engine
    runs and produces the right counts at full quantization."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=30, policy="static_int4")
    trace = [TraceRequest(0.0, 16, 6)]
    rep = eng.run_trace(trace)
    assert rep.n_finished == 1
    assert rep.degraded_token_frac == 1.0


def test_scheduler_fifo_order(model):
    cfg, params = model
    eng = make_engine(cfg, params, blocks=30, slots=1)   # serialize
    trace = [TraceRequest(0.0, 10, 3), TraceRequest(0.0, 10, 3),
             TraceRequest(0.0, 10, 3)]
    eng.run_trace(trace)
    firsts = [r.first_token_s for r in eng.all_requests]
    assert firsts == sorted(firsts)


def test_ledger_invariant_throughout_run(model):
    cfg, params = model
    eng = make_engine(cfg, params, blocks=6, mode="performance")
    trace = [TraceRequest(0.001 * i, 30, 24) for i in range(10)]
    for tr in trace:
        eng.submit(tr)
    for _ in range(3000):
        if not any(r.state in (RState.QUEUED, RState.RUNNING,
                               RState.PREEMPTED)
                   for r in eng.all_requests):
            break
        eng.step()
        assert eng.ledger.ok(), "ledger invariant violated mid-run"
        assert eng.pool.num_blocks - 1 >= eng.pool.alloc.n_used
    assert eng.ledger.ok()


# --------------------------------------------------------------------------
# SSM serving (beyond-paper: elasticity for attention-free archs)
# --------------------------------------------------------------------------
def test_engine_serves_mamba(model):
    cfg = reduced(ASSIGNED["mamba2-780m"])
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    eng = make_engine(cfg, params, blocks=16)
    trace = [TraceRequest(0.0, 12, 4), TraceRequest(0.0, 20, 4)]
    rep = eng.run_trace(trace, max_steps=2000)
    assert rep.n_finished == 2


def test_engine_serves_hybrid():
    cfg = reduced(ASSIGNED["hymba-1.5b"])
    params = lm.init_params(cfg, jax.random.PRNGKey(2))
    wb = tree_bytes(params)
    bb = kv_block_bytes(cfg, 16, 4)
    sc = ServingConfig(hbm_budget_bytes=int((wb + 24 * bb) / 0.95) + 2 * bb,
                       kv_block_size=16, max_batch_slots=4, max_seq_len=128,
                       swap_levels=(0, 1, 2), mode="performance")
    eng = MorphServeEngine(cfg, params, sc,
                           EngineConfig(policy="morph", compute="real"))
    trace = [TraceRequest(0.0, 12, 4)]
    rep = eng.run_trace(trace, max_steps=1000)
    assert rep.n_finished == 1


def test_engine_paged_decode_matches_dense(model):
    """Engine's paged decode must equal the dense-cache decode path."""
    cfg, params = model
    from repro.models.registry import get_model
    api = get_model(cfg)
    prompt = list(np.random.default_rng(0).integers(0, cfg.vocab, 12))
    # dense-cache greedy continuation
    cache = api.init_cache(cfg, 1, 64)
    toks = jnp.array([prompt])
    full = lm.forward(cfg, params, toks, moe_cf=-1.0)
    nxt = int(jnp.argmax(full[0, -1]))
    dense_out = [nxt]
    for t in range(len(prompt)):
        _, cache = api.decode_step(cfg, params, cache, toks[:, t:t+1])
    for _ in range(4):
        logits, cache = api.decode_step(cfg, params, cache,
                                        jnp.array([[dense_out[-1]]]))
        dense_out.append(int(jnp.argmax(logits[0, 0])))
    # engine run with the same prompt
    eng = make_engine(cfg, params, blocks=30, policy="static_fp16")
    r = eng.submit(TraceRequest(0.0, len(prompt), 5))
    r.prompt = prompt
    while r.state != RState.FINISHED:
        eng.step()
    assert r.generated == dense_out, (r.generated, dense_out)


# --------------------------------------------------------------------------
# quantized fast path (fused wNa16 data plane)
# --------------------------------------------------------------------------
def test_engine_quant_kernel_token_identity(model):
    """Engine with ``use_quant_kernel=True`` (Pallas interpret mode) must be
    token-identical to the jnp dequant path on a morph trace that crosses
    swap levels AND performs pressure-driven KV resizes."""
    from repro.kernels import ops as kops
    cfg, params = model
    trace = [TraceRequest(0.001 * i, 24, 12) for i in range(8)]

    def run(use_qk):
        prev = kops.set_quant_kernel_mode(
            "pallas_interpret" if use_qk else "xla")
        try:
            eng = make_engine(cfg, params, blocks=6, mode="performance",
                              seed=3, use_quant_kernel=use_qk)
            eng.run_trace(trace, max_steps=4000)
        finally:
            kops.set_quant_kernel_mode(prev)
        return eng

    eng_jnp = run(False)
    eng_fused = run(True)
    # the scenario must actually exercise both runtime mechanisms
    assert max(t.swap_level for t in eng_fused.monitor.history) > 0
    assert eng_fused.resize_log, "no KV resize happened on this trace"
    toks_jnp = [r.generated for r in eng_jnp.all_requests]
    toks_fused = [r.generated for r in eng_fused.all_requests]
    assert toks_jnp == toks_fused


def test_engine_pool_copies_only_at_bucket_transitions(model):
    """On a morph trace, the pool pays a device copy exactly when a resize
    crosses a power-of-two capacity bucket — never within a bucket."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=6, mode="performance", seed=3)
    cap = eng.pool.capacity
    trace = [TraceRequest(0.001 * i, 24, 12) for i in range(8)]
    eng.run_trace(trace, max_steps=4000)
    assert eng.resize_log
    transitions = 0
    for _, nb in eng.resize_log:
        b = eng.pool._cap_bucket(nb + 1)
        if b != cap:
            transitions += 1
            cap = b
    assert eng.pool.copies == transitions, (eng.pool.copies, transitions)


def test_engine_serves_mla_with_absorbed_weight_cache():
    """MLA engine decode: the absorbed w_ukv dequant/reshape is hoisted out
    of the jitted step and cached per swap level."""
    from repro.configs.archs import ASSIGNED
    cfg = reduced(ASSIGNED["deepseek-v3-671b"])
    params = lm.init_params(cfg, jax.random.PRNGKey(3))
    eng = make_engine(cfg, params, blocks=24, policy="static_fp16")
    trace = [TraceRequest(0.0, 12, 4), TraceRequest(0.0, 18, 4)]
    rep = eng.run_trace(trace, max_steps=2000)
    assert rep.n_finished == 2
    assert eng.exec._absorb_cache, "absorbed-weight cache never populated"
    (_, prepared), = list(eng.exec._absorb_cache.values())[:1]
    mla_p = [p for p in prepared
             if isinstance(p, dict) and "attn" in p and "wk_abs" in p["attn"]]
    assert mla_p, "no decode layer carries the absorbed projection"
    assert all("w_ukv" not in p["attn"] for p in mla_p)


def test_absorbed_weights_match_quantized_dequant():
    """absorb_mla_decode_weights on a *quantized* w_ukv equals the in-step
    dequant it replaces (regression for model_exec.py per-token dequant)."""
    from repro.engine.model_exec import absorb_mla_decode_weights
    from repro.quant import quantize_tensor
    from repro.configs.archs import ASSIGNED
    cfg = reduced(ASSIGNED["deepseek-v3-671b"])
    m = cfg.mla
    K = m.kv_lora_rank
    N = cfg.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N)) * 0.05
    qt = quantize_tensor(w, bits=4, group=32)
    (prep,) = absorb_mla_decode_weights(cfg, ({"attn": {"w_ukv": qt}},))
    wd = qt.dequantize(jnp.float32).reshape(
        K, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)
    np.testing.assert_array_equal(
        np.asarray(prep["attn"]["wk_abs"]),
        np.asarray(wd[..., :m.qk_nope_head_dim]))
    np.testing.assert_array_equal(
        np.asarray(prep["attn"]["wv_abs"]),
        np.asarray(wd[..., m.qk_nope_head_dim:]))


def test_block_accounting_invariant(model):
    """Allocator usage == sum of blocks held by requests at every step
    (regression test for the stale-running-list preemption leak)."""
    cfg, params = model
    eng = make_engine(cfg, params, blocks=8, mode="performance")
    trace = [TraceRequest(0.001 * i, 30, 24) for i in range(10)]
    for tr in trace:
        eng.submit(tr)
    for _ in range(3000):
        if not any(r.state in (RState.QUEUED, RState.RUNNING,
                               RState.PREEMPTED) for r in eng.all_requests):
            break
        eng.step()
        held = sum(len(r.block_ids) for r in eng.all_requests)
        assert held == eng.pool.alloc.n_used, (held, eng.pool.alloc.n_used)
    assert eng.pool.alloc.n_used == 0
