"""Quantized-KV relief tier (ISSUE 10): pool sidecar semantics, the
pressure-ladder ordering (evict-cached -> quantize-cold -> shrink/swap, with
restore reversing), ledger/telemetry accounting, the prefix cache's
cross-level hit modes (adopt vs requantize-on-hit), and round-trip-free
migration of quantized request state.

Kernel-level parity of the in-kernel dequant against the explicit-dequant
gather oracle lives in test_kernels.py; migration wire mechanics in
test_migration.py; ledger byte identities in test_memory_ledger.py.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs import ServingConfig, reduced, MORPH_LLAMA2_7B
from repro.engine import (EngineConfig, KVQuantConfig, MorphServeEngine,
                          TraceRequest)
from repro.engine.kv_cache import PagedKVPool
from repro.engine.request import RState

BS = 16
RCFG = reduced(MORPH_LLAMA2_7B)


def _fill(pool, ids, seed=0):
    """Scatter random fp content into ``ids``; returns the (k, v) written."""
    rng = np.random.default_rng(seed)
    shape = (RCFG.n_layers, len(ids), pool.block_size, pool.kvh, pool.dh)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    pool.scatter_blocks(ids, jnp.asarray(k), jnp.asarray(v))
    return k, v


def _quant_atol(pool, ids):
    """Worst-case affine rounding error over ``ids`` (<= scale/2 per elt)."""
    idx = jnp.asarray(ids, jnp.int32)
    s = jnp.maximum(jnp.max(pool.k_scale[:, idx]),
                    jnp.max(pool.v_scale[:, idx]))
    return float(s) * 0.51 + 1e-6


# --------------------------------------------------------------------------
# pool sidecar unit semantics (real arrays)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [8, 4])
def test_pool_quantize_dequantize_roundtrip(bits):
    pool = PagedKVPool(RCFG, 8, BS)
    ids = [1, 2, 3]
    k0, v0 = _fill(pool, ids)
    assert pool.kv_quant_bundle() is None          # quant-off: no sidecar
    done = pool.quantize_blocks([1, 2], bits=bits)
    assert done == [1, 2] and pool.qbits == {1: bits, 2: bits}
    assert pool.kv_quant_bundle() is not None
    # quantized fp slots are zeroed (the FMA reads the sidecar instead);
    # the untouched block keeps its fp content
    assert not np.any(np.asarray(pool.k[:, [1, 2]]))
    np.testing.assert_array_equal(np.asarray(pool.k[:, [3]]), k0[:, 2:3])
    atol = _quant_atol(pool, [1, 2])
    # re-quantizing a held block is a no-op, not a double compression
    assert pool.quantize_blocks([1], bits=bits) == []
    assert pool.dequantize_blocks([1, 2]) == [1, 2]
    assert pool.qbits == {} and pool.kv_quant_bundle() is None
    np.testing.assert_allclose(np.asarray(pool.k[:, ids]), k0, atol=atol)
    np.testing.assert_allclose(np.asarray(pool.v[:, ids]), v0, atol=atol)
    # sidecar rows zero again == fp semantics for any later kernel read
    assert not np.any(np.asarray(pool.k_scale[:, [1, 2]]))
    assert not np.any(np.asarray(pool.k_zero[:, [1, 2]]))


def test_pool_clear_quant_leaves_stale_payload_inert():
    """clear_quant (the block-free choke point) zeroes only the scale/zero
    rows: a freed-then-reallocated block must read exactly its new fp
    content through the kernels' branch-free FMA even though the stale int8
    payload is still sitting in the mirror."""
    pool = PagedKVPool(RCFG, 8, BS)
    _fill(pool, [1, 2])
    pool.quantize_blocks([1, 2], bits=8)
    pool.clear_quant([1])
    assert pool.qbits == {2: 8}
    assert np.any(np.asarray(pool.qk[:, 1]))       # payload intentionally stale
    assert not np.any(np.asarray(pool.k_scale[:, 1]))
    assert not np.any(np.asarray(pool.k_zero[:, 1]))
    # reallocate: new fp content lands in the slot
    k1, _ = _fill(pool, [1], seed=9)
    fma = (np.asarray(pool.k[:, 1])
           + np.asarray(pool.qk[:, 1], np.float32)
           * np.asarray(pool.k_scale[:, 1])[:, None, None, None]
           + np.asarray(pool.k_zero[:, 1])[:, None, None, None])
    np.testing.assert_array_equal(fma, k1[:, 0])


def test_pool_move_block_carries_quant_state():
    pool = PagedKVPool(RCFG, 8, BS)
    k0, v0 = _fill(pool, [1])
    pool.quantize_blocks([1], bits=8)
    atol = _quant_atol(pool, [1])
    pool.move_blocks([1], [5])
    assert pool.qbits == {5: 8}
    assert not np.any(np.asarray(pool.k_scale[:, 1]))   # src rows zeroed
    pool.dequantize_blocks([5])
    np.testing.assert_allclose(np.asarray(pool.k[:, [5]]), k0, atol=atol)
    np.testing.assert_allclose(np.asarray(pool.v[:, [5]]), v0, atol=atol)


def test_pool_quant_payload_gather_scatter_round_trip():
    """gather_quant_blocks -> scatter_quant_blocks moves a compressed block
    between pools without a decompress/recompress round trip (the migration
    wire path): the destination dequantizes to the source's content."""
    src = PagedKVPool(RCFG, 8, BS)
    k0, v0 = _fill(src, [1, 2])
    src.quantize_blocks([1, 2], bits=8)
    atol = _quant_atol(src, [1, 2])
    payload = src.gather_quant_blocks([1, 2])
    dst = PagedKVPool(RCFG, 8, BS)
    dst.scatter_quant_blocks([4, 6], payload, bits=8)
    assert dst.qbits == {4: 8, 6: 8}
    dst.dequantize_blocks([4, 6])
    np.testing.assert_allclose(np.asarray(dst.k[:, [4, 6]]), k0, atol=atol)
    np.testing.assert_allclose(np.asarray(dst.v[:, [4, 6]]), v0, atol=atol)


def test_pool_resize_preserves_sidecar_across_bucket_crossing():
    pool = PagedKVPool(RCFG, 8, BS)
    k0, _ = _fill(pool, [1])
    pool.quantize_blocks([1], bits=8)
    atol = _quant_atol(pool, [1])
    assert pool.resize(20)                  # 8 -> 32 capacity bucket: copy
    assert pool.capacity >= 20 and pool.qbits == {1: 8}
    assert pool.resize(6)                   # shrink back below
    assert pool.qbits == {1: 8}
    pool.dequantize_blocks([1])
    np.testing.assert_allclose(np.asarray(pool.k[:, [1]]), k0, atol=atol)


def test_pool_sim_marks_never_touch_device_arrays():
    pool = PagedKVPool(RCFG, 8, BS)
    assert pool.mark_quantized([1, 2], bits=8) == [1, 2]
    assert pool.qbits == {1: 8, 2: 8}
    assert pool.qk is None and pool.kv_quant_bundle() is None
    assert pool.mark_dequantized([1, 2, 3]) == [1, 2]
    assert pool.qbits == {}


# --------------------------------------------------------------------------
# sim engine: pressure-ladder ordering
# --------------------------------------------------------------------------
def sim_engine(**kw):
    sc = ServingConfig(hbm_budget_bytes=64 * 2**20, kv_block_size=BS,
                       max_batch_slots=4, max_seq_len=256,
                       swap_levels=(0, 1, 2, 4), mode="performance",
                       kv_resize_step_frac=0.25)
    return MorphServeEngine(RCFG, None, sc,
                            EngineConfig(policy="morph", compute="sim",
                                         seed=3, **kw))


def toks(rng, n):
    return tuple(int(x) for x in rng.integers(0, RCFG.vocab, size=n))


def _running_fleet(eng, n=3, blocks=4, steps=120):
    """Admit ``n`` requests with ``blocks`` full prompt blocks each and step
    until all are decoding (RUNNING) with their prompts fully paged."""
    rng = np.random.default_rng(11)
    rs = [eng.submit(TraceRequest(eng.now, blocks * BS, 120,
                                  toks(rng, blocks * BS)))
          for _ in range(n)]
    for _ in range(steps):
        if all(r.state == RState.RUNNING for r in rs):
            break
        eng.step()
    assert all(r.state == RState.RUNNING for r in rs)
    return rs


def test_quantize_cold_engages_before_level_escalation():
    """Tier ordering under sustained HIGH pressure: the controller spends
    the quantize-cold headroom (paced batches, level pinned at 0) before it
    issues the first relief swap; escalation happens only once headroom
    reads 0."""
    eng = sim_engine(kv_quant=KVQuantConfig(enabled=True, batch_blocks=4,
                                            max_quant_frac=0.5))
    _running_fleet(eng)
    assert eng._quant_headroom() > 0
    escalated_at_headroom = None
    for _ in range(20):
        eng.now += eng.sc.monitor_window_s + 0.1
        eng.monitor.kv_usage = 0.99
        before = eng._quant_headroom()
        eng._morph_tick()
        if eng.actuator.level > 0 or eng.actuator.busy:
            escalated_at_headroom = before
            break
        assert len(eng.pool.qbits) > 0, \
            "HIGH pressure tick with headroom must compress a batch"
    assert escalated_at_headroom == 0, \
        "escalated to a relief swap while quantize-cold headroom remained"
    assert len(eng.pool.qbits) > 0
    assert eng.ledger.kv_quant_blocks == len(eng.pool.qbits)
    assert eng.ledger.bytes_relieved > 0


def test_quant_candidates_respect_tail_shared_and_frac():
    qc = KVQuantConfig(enabled=True, keep_tail_blocks=1, max_quant_frac=0.5)
    eng = sim_engine(kv_quant=qc)
    rs = _running_fleet(eng, n=2, blocks=4)
    cand = eng._quant_candidates()
    held = {b for r in rs for b in r.block_ids}
    assert cand and set(cand) <= held
    for r in rs:
        n_full = min(r.context_len // BS, len(r.block_ids))
        protected = set(r.block_ids[n_full - qc.keep_tail_blocks:])
        shared = set(r.block_ids[:r.shared_blocks])
        assert not (set(cand) & protected), "attention-window tail quantized"
        assert not (set(cand) & shared), "shared prefix block quantized"
    assert len(cand) <= int(qc.max_quant_frac * eng.pool.alloc.n_used)


def test_restore_reverses_ladder_dequantize_only_at_level_zero():
    """Calm restore walks the ladder back in reverse: level returns to 0
    first, and only then do dequantize batches drain the quantized set;
    ledger relief returns to zero once the pool is all-fp again."""
    eng = sim_engine(kv_quant=KVQuantConfig(enabled=True, batch_blocks=4,
                                            max_quant_frac=0.5))
    _running_fleet(eng)
    for _ in range(20):                      # drive quantize + escalation
        eng.now += eng.sc.monitor_window_s + 0.1
        eng.monitor.kv_usage = 0.99
        eng._morph_tick()
        if eng.actuator.level > 0 or eng.actuator.busy:
            break
    assert len(eng.pool.qbits) > 0
    assert eng.actuator.level > 0 or eng.actuator.busy
    # calm: pressure drains, restore walks back
    eng.monitor.kv_usage = 0.1
    eng.monitor.queue_delay = eng.monitor.urgent_delay = 0.0
    eng.monitor.queue_len = 0.0
    prev_q = len(eng.pool.qbits)
    for _ in range(40):
        eng.now += max(eng.sc.monitor_window_s,
                       eng.sc.restore_patience_s) + 0.1
        eng._morph_tick()
        q = len(eng.pool.qbits)
        if q < prev_q:
            assert eng.actuator.level == 0, \
                "dequantize fired before the swap level returned to 0"
        prev_q = q
        if eng.actuator.level == 0 and not eng.actuator.busy and q == 0:
            break
    assert eng.actuator.level == 0 and len(eng.pool.qbits) == 0
    assert eng.ledger.kv_quant_blocks == 0
    assert eng.ledger.bytes_relieved == 0
    kinds = [k for _, k, _ in eng.kv_quant_events]
    assert "quantize" in kinds and "dequantize" in kinds


def test_telemetry_reports_quant_residency():
    eng = sim_engine(kv_quant=KVQuantConfig(enabled=True, batch_blocks=8,
                                            max_quant_frac=0.5))
    _running_fleet(eng)
    eng.now += eng.sc.monitor_window_s + 0.1
    eng.monitor.kv_usage = 0.99
    eng._morph_tick()
    assert len(eng.pool.qbits) > 0
    eng.controller.decide = lambda sig: None        # freeze the ladder
    eng.step()
    t = eng.monitor.history[-1]
    assert t.kv_quant_blocks == len(eng.pool.qbits) > 0
    assert t.kv_bytes_relieved == eng.ledger.bytes_relieved > 0


# --------------------------------------------------------------------------
# cross-level prefix hits: adopt vs requantize-on-hit
# --------------------------------------------------------------------------
def _finish(eng, prompt, gen=4):
    r = eng.submit(TraceRequest(eng.now, len(prompt), gen, prompt))
    for _ in range(400):
        if r.state == RState.FINISHED:
            break
        eng.step()
    assert r.state == RState.FINISHED
    return r


def _morph_to(eng, lvl):
    eng.actuator.issue(lvl, eng.now)
    eng.actuator.poll(eng.now + 1e9)
    eng.controller.commit(lvl)
    eng.ledger.set_weights(eng.actuator.weight_bytes())


@pytest.mark.parametrize("mode", ["adopt", "requant"])
def test_cross_level_hit_modes(mode):
    """A level-2 reader hits a chain published at level 0. "adopt" reuses
    the entries as-is (writer level preserved, nothing compressed);
    "requant" re-homes them at the reader's level through the codec and
    tags the tolerance on both entry and request."""
    eng = sim_engine(prefix_caching=True,
                     kv_quant=KVQuantConfig(enabled=True,
                                            cross_level_mode=mode))
    eng.controller.decide = lambda sig: None        # manual level control
    rng = np.random.default_rng(7)
    prefix = toks(rng, 3 * BS)
    _finish(eng, prefix + toks(rng, 5))             # publish at level 0
    assert eng.prefix_cache.resident_blocks >= 3
    _morph_to(eng, 2)
    r2 = _finish(eng, prefix + toks(rng, 9))
    assert eng.prefix_hit_requests == 1
    assert eng.prefix_cache.cross_level_hits >= 3
    shared = [e for e in eng.prefix_cache.entries.values()]
    if mode == "adopt":
        assert eng.pool.qbits == {}, "adopt mode must not compress anything"
        assert eng.prefix_cache.requant_hits == 0
        assert all(e.level == 0 and e.tol is None for e in shared)
        assert r2.tol is None
    else:
        assert eng.prefix_cache.requant_hits >= 3
        rehomed = [e for e in shared if e.tol == "int8"]
        assert len(rehomed) >= 3
        assert all(e.level == 2 for e in rehomed)
        assert r2.tol == "int8"
        assert set(eng.pool.qbits) >= {e.block_id for e in rehomed}
        assert eng.ledger.kv_quant_blocks == len(eng.pool.qbits) > 0
    # the books stay consistent through the next reclaim tick
    eng._morph_tick()
    eng.prefix_cache.check(eng.pool.alloc)
    assert eng.ledger.kv_quant_blocks == len(eng.pool.qbits)


# --------------------------------------------------------------------------
# quantized request state migrates compressed (sim data plane)
# --------------------------------------------------------------------------
def test_quantized_request_state_migrates_and_stream_is_identical():
    rng = np.random.default_rng(5)
    prompt = toks(rng, 4 * BS)
    tr = TraceRequest(0.0, len(prompt), 12, prompt)
    # reference: undisturbed run to completion
    ref = sim_engine(kv_quant=KVQuantConfig(enabled=True))
    want = _finish(ref, prompt, gen=12).generated
    # source: run to mid-decode, quantize a cold block, export
    src = sim_engine(kv_quant=KVQuantConfig(enabled=True))
    r = src.submit(tr)
    for _ in range(400):
        if r.state == RState.RUNNING and len(r.generated) >= 3:
            break
        src.step()
    assert r.state == RState.RUNNING
    src.pool.mark_quantized([r.block_ids[0]], bits=8)
    src.ledger.quantize_kv(1)
    st = src.export_request_state(r)
    assert st is not None and st.quant_idx == [0] and st.quant_bits == 8
    src.detach_request(r)
    assert src.ledger.kv_quant_blocks == 1          # reconciled next tick
    src._morph_tick()
    assert src.ledger.kv_quant_blocks == len(src.pool.qbits) == 0
    # destination adopts the block still compressed, books it, finishes
    dst = sim_engine(kv_quant=KVQuantConfig(enabled=True))
    r2 = dst.import_request_state(st)
    assert r2 is not None
    assert dst.pool.qbits == {r2.block_ids[0]: 8}
    assert dst.ledger.kv_quant_blocks == 1
    for _ in range(400):
        if r2.state == RState.FINISHED:
            break
        dst.step()
    assert r2.state == RState.FINISHED
    assert r2.generated == want, "migrated stream diverged from reference"


def test_kv_quant_trace_smoke_books_stay_consistent():
    """End-to-end sim trace with the tier enabled under a tight budget:
    every request finishes and the quant books reconcile at exit."""
    from repro.engine.traces import azure_like
    eng = sim_engine(prefix_caching=True,
                     kv_quant=KVQuantConfig(enabled=True, batch_blocks=4))
    trace = azure_like(duration_s=10.0, base_rps=2.0, seed=4,
                       prompt_mean=96, gen_mean=24,
                       prompt_max=192, gen_max=48)
    rep = eng.run_trace(trace, max_steps=6000)
    assert rep.n_finished == len(trace)
    assert eng.ledger.kv_quant_blocks == len(eng.pool.qbits)
    assert eng.ledger.ok()
