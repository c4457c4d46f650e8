"""Serving plane: paged KV cache, continuous batching, HTTP surface.

The allocator/cache tests are bookkeeping (rows go into the device
pools by hand, ``kv_rows``, not through a model program); the
engine tests run the real jitted prefill/decode on a tiny model (the
jit wrappers are process-cached, so the whole file pays each shape's
compile once).
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dmlc_tpu import telemetry
from dmlc_tpu.base import DMLCError
from dmlc_tpu.serving import (
    AdmissionFull,
    BlockAllocator,
    ContinuousBatchScheduler,
    InferenceEngine,
    PagedKVCache,
    Request,
    RequestTooLarge,
    ServingHTTPServer,
)
from dmlc_tpu.serving.scheduler import (ACTIVE, DONE, WAITING,
                                        PRIORITY_CLASSES, coerce_priority)
from kv_rows import kv_get, kv_put


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

def test_allocator_exhaustion_is_all_or_nothing():
    a = BlockAllocator(4)
    got = a.alloc_many(3)
    assert got is not None and len(got) == 3 and a.n_free == 1
    # over-ask must not partially drain the free list
    assert a.alloc_many(2) is None
    assert a.n_free == 1
    assert a.alloc() is not None
    assert a.alloc() is None


def test_allocator_free_reuse_and_double_free():
    a = BlockAllocator(2)
    got = a.alloc_many(2)
    a.free(got)
    assert a.n_free == 2 and a.n_in_use == 0
    again = a.alloc_many(2)
    assert sorted(again) == sorted(got)  # same physical blocks recycle
    with pytest.raises(DMLCError):
        a.free([99])  # foreign block
    with pytest.raises(DMLCError):
        a.free([again[0], 99])  # atomic: valid id must NOT free either
    assert a.n_in_use == 2
    a.free(again)
    with pytest.raises(DMLCError):
        a.free([again[0]])  # double free


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------

def _mk_cache(**kw):
    kw.setdefault("n_blocks", 8)
    kw.setdefault("block_size", 4)
    return PagedKVCache(2, 2, 3, **kw)  # L=2, H=2, D=3


def _seq_kv(cache, n, seed):
    rng = np.random.default_rng(seed)
    shape = (cache.n_layers, n, cache.n_heads, cache.head_dim)
    return rng.standard_normal(shape).astype(np.float32), \
        rng.standard_normal(shape).astype(np.float32)


def test_kv_put_get_roundtrip_across_blocks():
    cache = _mk_cache()
    k, v = _seq_kv(cache, 10, seed=0)  # 10 tokens = 2.5 blocks
    assert cache.allocate(1, 10)
    kv_put(cache, 1, k, v, start=0)
    gk, gv, lens = kv_get(cache, [1])
    assert lens.tolist() == [10]
    assert gk.shape[2] % cache.block_size == 0
    np.testing.assert_array_equal(gk[:, 0, :10], k)
    np.testing.assert_array_equal(gv[:, 0, :10], v)
    # one more token lands at position 10 (same block reservation is
    # insufficient: 11 tokens need a 3rd block, so extend first)
    assert cache.extend(1, 1)
    k1, v1 = _seq_kv(cache, 1, seed=1)
    kv_put(cache, 1, k1, v1)
    gk, gv, lens = kv_get(cache, [1])
    assert lens.tolist() == [11]
    np.testing.assert_array_equal(gk[:, 0, 10], k1[:, 0])


def test_kv_exhaustion_then_free_then_reuse_without_aliasing():
    cache = _mk_cache(n_blocks=4, block_size=4)  # 16 tokens total
    ka, va = _seq_kv(cache, 8, seed=0)
    kc, vc = _seq_kv(cache, 8, seed=2)
    assert cache.allocate(1, 8)          # seq A: blocks 0-1
    kv_put(cache, 1, ka, va)
    assert cache.allocate(3, 8)          # seq C: blocks 2-3
    kv_put(cache, 3, kc, vc)
    assert not cache.allocate(2, 4)      # pool exhausted
    assert not cache.extend(1, 1)
    cache.free(1)                        # eviction frees A's blocks
    reused = set()
    assert cache.allocate(2, 8)          # seq B reuses A's blocks
    reused = set(cache.block_table(2)) & set([0, 1, 2, 3])
    assert reused, "freed blocks must be reused"
    kb, vb = _seq_kv(cache, 8, seed=1)
    kv_put(cache, 2, kb, vb)
    # B reads back B's data, and surviving C is untouched (no aliasing)
    gk, gv, lens = kv_get(cache, [2, 3])
    np.testing.assert_array_equal(gk[:, 0, :8], kb)
    np.testing.assert_array_equal(gk[:, 1, :8], kc)
    np.testing.assert_array_equal(gv[:, 1, :8], vc)


def test_kv_fragmentation_bounded_under_mixed_length_churn():
    cache = _mk_cache(n_blocks=16, block_size=4)
    rng = np.random.default_rng(7)
    live = {}
    sid = 0
    for it in range(120):
        if live and (len(live) >= 5 or rng.random() < 0.45):
            victim = int(rng.choice(list(live)))
            cache.free(victim)
            del live[victim]
        else:
            sid += 1
            n = int(rng.integers(1, 14))
            if cache.allocate(sid, n):
                k, v = _seq_kv(cache, n, seed=sid)
                kv_put(cache, sid, k, v)
                live[sid] = (n, k)
        # invariants every iteration: conservation + bounded usage
        s = cache.stats()
        assert s["blocks_in_use"] + s["blocks_free"] == 16
        assert s["blocks_in_use"] == sum(
            cache.blocks_for(n) for n, _ in live.values())
        # the O(1) running token counter matches the ground truth sum,
        # and waste = allocated slots minus cached tokens
        assert s["cached_tokens"] == sum(n for n, _ in live.values())
        assert s["waste_tokens"] == (s["blocks_in_use"] * 4
                                     - s["cached_tokens"])
    # every surviving sequence still reads back its own data
    for seq, (n, k) in live.items():
        gk, _, lens = kv_get(cache, [seq])
        assert lens[0] == n
        np.testing.assert_array_equal(gk[:, 0, :n], k)
    for seq in list(live):
        cache.free(seq)
    assert cache.n_free_blocks == 16  # no leaked blocks after churn
    assert cache.n_blocks_in_use == 0


def test_kv_block_tables_pad_the_batch_with_dead_rows():
    cache = _mk_cache()
    k, v = _seq_kv(cache, 3, seed=0)
    assert cache.allocate(1, 3)
    kv_put(cache, 1, k, v)
    tables, lens = cache.block_tables_array([1], pad_batch=4, pad_width=2)
    assert tables.shape == (4, 2) and tables.dtype == np.int32
    assert lens.tolist() == [3, 0, 0, 0]
    assert tables[0].tolist() == cache.block_table(1) + [0]
    assert not tables[1:].any()  # dead rows: page 0, behind length 0
    # an explicit pad_width pins the jit shape: insufficiency must
    # raise, never silently widen
    assert cache.extend(1, 6)
    k9, v9 = _seq_kv(cache, 6, seed=3)
    kv_put(cache, 1, k9, v9)  # now 9 tokens = 3 blocks > pad_width 2
    with pytest.raises(ValueError):
        cache.block_tables_array([1], pad_width=2)


def test_kv_advance_past_reservation_raises():
    cache = _mk_cache()
    assert cache.allocate(1, 4)
    with pytest.raises(DMLCError, match="past reservation"):
        cache.advance_many([(1, 5)])  # 5 tokens in a 1-block reservation
    assert cache.length(1) == 0 and cache.stats()["cached_tokens"] == 0


# ---------------------------------------------------------------------------
# scheduler policy (no jax)
# ---------------------------------------------------------------------------

def test_scheduler_admission_respects_slots_and_blocks():
    cache = _mk_cache(n_blocks=4, block_size=4)
    sched = ContinuousBatchScheduler(cache, max_active=1)
    r1 = Request([1] * 4, 4)
    r2 = Request([2] * 4, 4)
    sched.enqueue(r1)
    sched.enqueue(r2)
    got = sched.next_prefill()
    assert got is r1
    assert cache.allocate(r1.id, 4)
    sched.activate(r1)
    assert sched.next_prefill() is None  # max_active reached
    sched.finish(r1)
    assert r1.state == DONE and r1.wait(0)
    # blocks freed by finish → r2 admissible
    big = Request([3] * 100, 4)  # needs 26 blocks > 4 free: blocked
    sched._waiting.appendleft(big)
    assert sched.next_prefill() is None
    sched._waiting.popleft()
    assert sched.next_prefill() is r2


def test_scheduler_preempts_youngest_and_requeues_front():
    cache = _mk_cache(n_blocks=8, block_size=4)
    sched = ContinuousBatchScheduler(cache, max_active=4)
    old = Request([1, 2], 4)
    young = Request([3, 4], 4)
    for r in (old, young):
        sched.enqueue(r)
        assert sched.next_prefill() is r
        assert cache.allocate(r.id, 2)
        sched.activate(r)
    young.generated = [7, 8]
    victim = sched.preempt_youngest()
    assert victim is young and young.state == WAITING
    assert young.preemptions == 1
    assert old.state == ACTIVE
    assert young.id not in cache.live_sequences()
    # resumes from the FRONT, context keeps generated-but-unconsumed
    assert sched.next_prefill() is young
    assert young.context_ids() == [3, 4, 7]  # last token not yet consumed


def test_coerce_priority_contract():
    assert PRIORITY_CLASSES == {"batch": 0, "standard": 1, "interactive": 2}
    assert coerce_priority(None, 3, 1) == 1          # None → default
    assert coerce_priority("interactive", 3, 1) == 2
    assert coerce_priority("batch", 3, 1) == 0
    assert coerce_priority(0, 3, 1) == 0
    assert coerce_priority(2, 3, 1) == 2
    # a named class above the configured level count is out of range
    with pytest.raises(ValueError):
        coerce_priority("interactive", 2, 0)
    for bad in ("gold", "", 3, -1, True, False, 1.5, [1], {"p": 1}):
        with pytest.raises(ValueError):
            coerce_priority(bad, 3, 1)


def test_scheduler_never_evicts_high_priority_over_low():
    """Satellite regression: a high-priority request is NEVER the
    eviction victim while any lower-priority request holds blocks,
    even when the high-priority one is the youngest."""
    cache = _mk_cache(n_blocks=16, block_size=4)
    sched = ContinuousBatchScheduler(cache, max_active=4)
    lo_old = Request([1, 2], 4, priority=0)
    lo_young = Request([3, 4], 4, priority=0)
    hi = Request([5, 6], 4, priority=2)       # youngest of the three
    lo_young.submit_t = lo_old.submit_t + 1.0
    hi.submit_t = lo_old.submit_t + 2.0
    for r in (lo_old, lo_young, hi):
        sched.enqueue(r)
    for _ in range(3):
        r = sched.next_prefill()
        assert cache.allocate(r.id, 2)
        sched.activate(r)
    # victims: youngest within the LOWEST class first, high class last
    assert sched.preempt_youngest() is lo_young
    assert hi.state == ACTIVE
    assert sched.preempt_youngest() is lo_old
    assert hi.state == ACTIVE, "high priority evicted before low"
    assert sched.preempt_youngest() is hi    # only when nothing lower
    assert sched.preempt_youngest() is None


def test_scheduler_admits_high_priority_first_fifo_within_class():
    cache = _mk_cache(n_blocks=16, block_size=4)
    sched = ContinuousBatchScheduler(cache, max_active=4)
    lo1 = Request([1], 4, priority=0)
    hi1 = Request([2], 4, priority=2)
    lo2 = Request([3], 4, priority=0)
    hi2 = Request([4], 4, priority=2)
    for r in (lo1, hi1, lo2, hi2):
        sched.enqueue(r)
    order = []
    while True:
        r = sched.next_prefill()
        if r is None:
            break
        assert cache.allocate(r.id, 1)
        sched.activate(r)
        order.append(r)
    assert order == [hi1, hi2, lo1, lo2]


# ---------------------------------------------------------------------------
# engine + model (real jitted compute, tiny config)
# ---------------------------------------------------------------------------

def _tiny_model():
    import jax

    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2, head_dim=8,
                                d_ff=64, n_layers=2, n_experts=1,
                                microbatches=1)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _greedy_oracle(params, cfg, prompt, n):
    """Greedy continuation via repeated full forward (no cache)."""
    from dmlc_tpu.models import transformer as tfm

    ctx = list(prompt)
    for _ in range(n):
        lg, _, _ = tfm.forward_prefill(
            params, np.array([ctx], np.int32), cfg)
        ctx.append(int(np.argmax(np.asarray(lg[0, -1]))))
    return ctx[len(prompt):]


def test_engine_continuous_batching_end_to_end():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8, admit_timeout_s=2.0)
    eng.start()
    try:
        reqs = [eng.submit([i + 1, i + 2, i + 3], max_new_tokens=5)
                for i in range(4)]  # 4 requests over 3 active slots
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
            assert r.error is None
            assert r.n_generated == 5
            assert r.ttft_s is not None and r.ttft_s > 0
        # greedy parity through the paged cache for one of them
        assert reqs[0].generated == _greedy_oracle(
            params, cfg, [1, 2, 3], 5)
        st = eng.stats()
        assert st["kv"]["blocks_in_use"] == 0  # all returned
        assert st["ledger"].get("steps", 0) > 0  # ledger was driven
    finally:
        eng.close()


def test_engine_registers_the_grouped_call_counter_without_experts():
    """``serving.moe_grouped_calls`` (the held experts' grouped products)
    is registered at 0 by every engine's start, a model without held
    experts too, and that model's programs never grow it."""
    params, cfg = _tiny_model()
    telemetry.reset()
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=4,
                          max_active=2, queue_depth=4)
    eng.start()
    try:
        assert telemetry.counters_snapshot()["serving"][
            "moe_grouped_calls"] == 0
        r = eng.submit([1, 2, 3], max_new_tokens=4)
        assert r.wait(300) and r.error is None
    finally:
        eng.close()
    c = telemetry.counters_snapshot()["serving"]
    assert c["paged_decode_steps"] == 3 and c["moe_grouped_calls"] == 0


def test_engine_single_step_interleaves_admission():
    """Iteration-level scheduling: a request submitted mid-generation
    joins the running batch instead of waiting for drain."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8)
    r1 = eng.submit([1, 2, 3], max_new_tokens=8)
    eng.step()   # prefill r1
    eng.step()   # decode r1
    assert r1.n_generated >= 2 and r1.state == ACTIVE
    r2 = eng.submit([4, 5, 6], max_new_tokens=2)
    eng.step()   # prefill r2 AND decode r1 in one iteration
    assert r2.n_generated >= 1
    assert r1.state == ACTIVE  # r1 still going: no drain barrier
    for _ in range(12):
        if r1.wait(0) and r2.wait(0):
            break
        eng.step()
    assert r1.n_generated == 8 and r2.n_generated == 2
    eng.close()


def test_engine_preemption_under_kv_pressure_still_completes():
    params, cfg = _tiny_model()
    before = telemetry.snapshot()["counters"].get(
        "serving", {}).get("preemptions", 0)
    # 6 blocks × 4 slots = 24 cached tokens; 3 × (4 prompt + 10 gen)
    # cannot coexist, so decode must evict and resume
    eng = InferenceEngine(params, cfg, n_blocks=6, block_size=4,
                          max_active=3, queue_depth=8)
    eng.start()
    try:
        reqs = [eng.submit([i + 1] * 4, max_new_tokens=10)
                for i in range(3)]
        for r in reqs:
            assert r.wait(300)
            assert r.error is None
            assert r.n_generated == 10
        after = telemetry.snapshot()["counters"]["serving"]["preemptions"]
        assert after > before, "tiny pool must have forced preemption"
        assert eng.cache.n_blocks_in_use == 0
        # preemption must be output-invisible: resume recomputes the
        # context without re-sampling, so every request still matches
        # the no-cache greedy oracle (a resume that re-derived its last
        # token would duplicate it and drop the final one)
        for i, r in enumerate(reqs):
            assert r.generated == _greedy_oracle(
                params, cfg, [i + 1] * 4, 10), (
                f"request {i} output corrupted by preemption "
                f"(preemptions={r.preemptions})")
    finally:
        eng.close()


def test_decode_capacity_eviction_of_already_checked_survivor():
    """Regression: activation order is not age order once a preempted
    request resumes.  When a LATER request's extend evicts an EARLIER
    survivor of the same capacity pass, that survivor must not reach
    the decode batch (its cache sequence is gone)."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=5, block_size=4,
                          max_active=4, queue_depth=8)
    x = Request([1, 2], 4)   # younger (submitted later) but FIRST in
    y = Request([3, 4], 4)   # the active list, older second: inversion
    y.submit_t = x.submit_t - 10.0
    assert eng.cache.allocate(x.id, 13)   # 4 blocks; extend stays inside
    assert eng.cache.allocate(y.id, 4)    # 1 full block; extend needs +1
    # the paged engine's cache is device-resident: the capacity pass
    # sees lengths alone
    eng.cache.advance_many([(x.id, 13), (y.id, 4)])
    eng.scheduler.activate(x)
    eng.scheduler.activate(y)
    alive, n_preempted = eng._ensure_decode_capacity([x, y])
    assert alive == [y], "evicted survivor leaked into the decode batch"
    assert n_preempted == 1
    assert x.state == WAITING and x.preemptions == 1
    assert x.id not in eng.cache.live_sequences()
    eng.close()


def test_engine_rejects_oversized_and_overflowing_requests():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=4, block_size=4,
                          max_active=2, queue_depth=2,
                          admit_timeout_s=0.05)
    # could never fit even an empty cache → 413-shaped, not a slot
    with pytest.raises(RequestTooLarge):
        eng.submit([1] * 10, max_new_tokens=20)
    # bad content is the client's ValueError (HTTP 400), not a size issue
    with pytest.raises(ValueError):
        eng.submit([cfg.vocab + 5], max_new_tokens=1)
    # queue_depth=2 slots drain only when the engine runs; it is NOT
    # started, so the third submit must time out with AdmissionFull
    eng.submit([1, 2], max_new_tokens=1)
    eng.submit([3, 4], max_new_tokens=1)
    before = telemetry.snapshot()["counters"].get(
        "serving", {}).get("rejected", 0)
    with pytest.raises(AdmissionFull):
        eng.submit([5, 6], max_new_tokens=1)
    after = telemetry.snapshot()["counters"]["serving"]["rejected"]
    assert after == before + 1
    eng.close()


def test_engine_priority_and_tenant_validation_and_plumbing():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8, admit_timeout_s=2.0)
    try:
        # invalid classes are the client's ValueError (HTTP 400)
        for bad_prio in ("gold", 7, -1, True):
            with pytest.raises(ValueError):
                eng.submit([1, 2], max_new_tokens=2, priority=bad_prio)
        for bad_tenant in ("", 42, "x" * 65):
            with pytest.raises(ValueError):
                eng.submit([1, 2], max_new_tokens=2, tenant=bad_tenant)
        r = eng.submit([1, 2, 3], max_new_tokens=2,
                       priority="interactive", tenant="paid")
        while not r.wait(0):
            eng.step()
        doc = r.result()
        assert doc["priority"] == 2 and doc["tenant"] == "paid"
        # defaults: configured default class + the "default" tenant
        r2 = eng.submit([4, 5], max_new_tokens=1)
        assert r2.priority == eng.priority_default
        assert r2.tenant == "default"
    finally:
        eng.close()


def test_jit_program_cache_ignores_scenario_lock_hook():
    """The process-wide prefill/decode jit cache outlives any one
    engine: if the first engine of the process is built inside an
    interleaving-explorer scenario (the explorer's lock-factory hook
    active), the cached profiled wrappers must NOT capture
    scheduler-owned SchedLocks — a later engine would inherit a lock
    wired to a finished controller and park forever."""
    from dmlc_tpu import concurrency
    from dmlc_tpu.serving import engine as eng_mod

    offered = []

    def hook(name, reentrant):
        offered.append(name)
        return None

    saved = dict(eng_mod._JIT_CACHE)
    eng_mod._JIT_CACHE.clear()
    concurrency.set_lock_factory_hook(hook)
    try:
        eng_mod._jitted_programs()
        assert offered == [], (
            f"program-cache locks were offered to the scenario lock "
            f"hook: {offered}")
        # and the hook is back in place afterwards for the scenario
        assert concurrency._lock_factory_hook is hook
    finally:
        concurrency.set_lock_factory_hook(None)
        eng_mod._JIT_CACHE.clear()
        eng_mod._JIT_CACHE.update(saved)


def test_engine_close_fails_pending_requests():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=4,
                          max_active=2, queue_depth=4)
    req = eng.submit([1, 2, 3], max_new_tokens=50)  # engine never started
    eng.close()
    assert req.wait(5)
    assert req.state == "failed" and "shut down" in req.error


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------

def _post(url, doc, timeout=300):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_http_generate_metrics_healthz():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    try:
        doc = _post(srv.url, {"prompt": [1, 2, 3], "max_tokens": 4})
        assert doc["state"] == "done" and doc["n_generated"] == 4
        assert doc["ttft_s"] > 0 and len(doc["output_ids"]) == 4
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": "not a list"})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": [1] * 500, "max_tokens": 500})
        assert e.value.code == 413
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": [cfg.vocab + 7], "max_tokens": 2})
        assert e.value.code == 400  # bad content, NOT 413
        text = urllib.request.urlopen(
            srv.url + "/metrics", timeout=30).read().decode()
        from dmlc_tpu.telemetry.exporters import validate_exposition_text

        assert validate_exposition_text(text) > 0
        for fam in ("dmlc_serving_requests", "dmlc_serving_ttft_secs",
                    "dmlc_serving_tokens_generated",
                    "dmlc_serving_kv_blocks_in_use", "dmlc_step_count"):
            assert fam in text, f"{fam} missing from /metrics"
        hz = json.loads(urllib.request.urlopen(
            srv.url + "/healthz", timeout=30).read())
        assert hz["status"] == "ok" and "kv" in hz and "ledger" in hz
    finally:
        srv.close()
        eng.close()


def test_http_429_when_admission_queue_full():
    params, cfg = _tiny_model()
    # engine NOT started: slots never drain, so the queue fills
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=1,
                          admit_timeout_s=0.05)
    srv = ServingHTTPServer(eng, port=0)
    try:
        eng.submit([1, 2], max_new_tokens=1)  # occupies the only slot
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": [3, 4], "max_tokens": 1}, timeout=30)
        assert e.value.code == 429
        assert e.value.headers.get("Retry-After") == "1"
    finally:
        srv.close()
        eng.close()


def test_concurrent_http_streams_complete():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=64, block_size=4,
                          max_active=4, queue_depth=16)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    results = []
    lock = threading.Lock()

    def client(i):
        doc = _post(srv.url, {"prompt": [i + 1, i + 2], "max_tokens": 3})
        with lock:
            results.append(doc)

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert time.monotonic() - t0 < 300
        assert len(results) == 6
        assert all(r["n_generated"] == 3 for r in results)
    finally:
        srv.close()
        eng.close()


# ---------------------------------------------------------------------------
# graceful drain (ISSUE 7): preemption notice must not drop in-flight work
# ---------------------------------------------------------------------------

def test_drain_finishes_active_rejects_new():
    """drain(): already-submitted generations complete; new /generate
    requests get 503 + Retry-After for the whole drain window."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=64, block_size=4,
                          max_active=4, queue_depth=16)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    results = {}
    try:
        # a long-ish generation in flight when the notice lands
        req = eng.submit([1, 2, 3], max_new_tokens=12)

        def draining():
            results["clean"] = srv.drain(timeout_s=60)

        t = threading.Thread(target=draining, daemon=True)
        t.start()
        # wait for the drain to take effect, then poke the front door
        deadline = time.monotonic() + 10
        while not eng.draining and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.draining
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": [5, 6], "max_tokens": 1},
                  timeout=30)
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") == "5"
        t.join(120)
        assert results["clean"] is True
        # the in-flight generation was finished, not dropped
        assert req.wait(5)
        assert req.error is None and len(req.generated) == 12
        # direct submits are refused too (embedded users)
        from dmlc_tpu.serving.engine import EngineDraining

        with pytest.raises(EngineDraining):
            eng.submit([1], max_new_tokens=1)
    finally:
        srv.close()
        eng.close()


def test_drain_deadline_fails_leftovers():
    """An engine that cannot finish (never started) hits the drain
    deadline: drain() returns False and the backlog is failed, not
    leaked."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    # NOT started: the queued request can never decode
    req = eng.submit([1, 2], max_new_tokens=4)
    srv = ServingHTTPServer(eng, port=0)
    try:
        assert srv.drain(timeout_s=0.3) is False
        assert req.wait(5)
        assert req.error is not None
    finally:
        srv.close()
        eng.close()


# ---------------------------------------------------------------------------
# idempotency dedupe (ISSUE 13): the router retry/hedge primitive
# ---------------------------------------------------------------------------

def test_dedupe_duplicate_while_live_returns_same_request():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "dedupe_hits", 0)
    r1 = eng.submit([1, 2, 3], max_new_tokens=4, request_id="dup-live")
    r2 = eng.submit([9, 9, 9], max_new_tokens=9, request_id="dup-live")
    assert r2 is r1, "duplicate while live must not start a second " \
        "generation"
    after = telemetry.counters_snapshot()["serving"]["dedupe_hits"]
    assert after == before + 1
    eng.start()
    assert r1.wait(300) and r1.error is None
    # duplicate after a successful finish: same finished request from
    # the dedupe ring, same output — not a new generation
    r3 = eng.submit([1, 2, 3], max_new_tokens=4, request_id="dup-live")
    assert r3 is r1 and r3.generated == r1.generated
    # a DIFFERENT id is fresh work
    r4 = eng.submit([1, 2, 3], max_new_tokens=4, request_id="other")
    assert r4 is not r1
    assert r4.wait(300)
    eng.close()


def test_dedupe_ring_is_bounded_and_failed_ids_are_fresh():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    eng._dedupe.capacity = 2
    eng.start()
    reqs = {}
    for key in ("k1", "k2", "k3"):
        reqs[key] = eng.submit([1, 2], max_new_tokens=2, request_id=key)
        assert reqs[key].wait(300)
    # ring capacity 2: k1 was evicted, so its id is fresh work again
    assert eng.submit([1, 2], max_new_tokens=2,
                      request_id="k3") is reqs["k3"]
    r1b = eng.submit([1, 2], max_new_tokens=2, request_id="k1")
    assert r1b is not reqs["k1"]
    assert r1b.wait(300)
    eng.close()
    # FAILED requests leave the table: a retry is a fresh attempt
    eng2 = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    rf = eng2.submit([1, 2], max_new_tokens=2, request_id="will-fail")
    eng2.close()  # engine never ran: the sweep fails it
    assert rf.wait(5) and rf.error is not None
    assert eng2._dedupe.get("will-fail") is None


def test_dedupe_admission_failure_wakes_duplicates_then_resets():
    """A claimed id whose admission then fails (queue full) must (a)
    wake any duplicate parked on it with the busy verdict and (b)
    leave the table so a later retry is a fresh attempt."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=1,
                          admit_timeout_s=0.05)  # NOT started
    eng.submit([1, 2], max_new_tokens=1)  # occupies the only slot
    with pytest.raises(AdmissionFull):
        eng.submit([3, 4], max_new_tokens=1, request_id="busy-key")
    assert eng._dedupe.get("busy-key") is None
    eng.close()


def test_http_request_id_dedupes_and_echoes():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    try:
        d1 = _post(srv.url, {"prompt": [1, 2, 3], "max_tokens": 4,
                             "request_id": "http-key"})
        d2 = _post(srv.url, {"prompt": [1, 2, 3], "max_tokens": 4,
                             "request_id": "http-key"})
        assert d1["request_id"] == d2["request_id"] == "http-key"
        assert d1["id"] == d2["id"]  # same internal request, not a rerun
        assert d1["output_ids"] == d2["output_ids"]
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": [1], "request_id": 42})
        assert e.value.code == 400  # non-string key is the client's bug
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.url, {"prompt": [1], "request_id": "x" * 200})
        assert e.value.code == 400
    finally:
        srv.close()
        eng.close()


# ---------------------------------------------------------------------------
# requeue-on-crash (ISSUE 13): an engine-iteration crash is
# output-invisible up to the crash budget
# ---------------------------------------------------------------------------

def test_crash_requeue_resumes_and_matches_oracle():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8)
    real = eng._decode
    crashes = []

    def crashing(*a, **kw):
        if not crashes:
            crashes.append(1)
            raise RuntimeError("simulated decode crash")
        return real(*a, **kw)

    eng._decode = crashing
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "crash_requeues", 0)
    eng.start()
    try:
        reqs = [eng.submit([i + 1, i + 2], max_new_tokens=6)
                for i in range(2)]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
            assert r.error is None, r.error
            assert r.n_generated == 6
        after = telemetry.counters_snapshot()["serving"]["crash_requeues"]
        assert after > before, "crash must requeue, not fail"
        # recompute-resume is output-invisible: greedy parity holds
        # straight through the crash episode
        for i, r in enumerate(reqs):
            assert r.generated == _greedy_oracle(
                params, cfg, [i + 1, i + 2], 6)
        assert eng.cache.n_blocks_in_use == 0
    finally:
        eng.close()


def test_crash_requeue_during_drain_still_completes():
    """A crash requeue moves a request active -> waiting BACKWARD
    through drain()'s flow-order scan; the re-read of the wait queue
    must keep drain honest: the requeued request completes (resumed,
    not swept) and drain reports clean."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    real = eng._decode
    crashes = []

    def crash_once_draining(*a, **kw):
        if eng.draining and not crashes:
            crashes.append(1)
            raise RuntimeError("crash during drain")
        return real(*a, **kw)

    eng._decode = crash_once_draining
    eng.start()
    req = eng.submit([1, 2, 3], max_new_tokens=10)
    clean = eng.drain(timeout_s=120)
    assert crashes, "the crash never fired while draining"
    assert clean is True
    assert req.wait(5)
    assert req.error is None, req.error
    assert req.n_generated == 10
    assert req.crash_requeues == 1


def test_crash_requeue_budget_bounds_poisonous_request():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    eng._crash_requeue_max = 2

    def always_crash(*a, **kw):
        raise RuntimeError("poisoned decode")

    eng._decode = always_crash
    eng.start()
    try:
        r = eng.submit([1, 2, 3], max_new_tokens=4)
        assert r.wait(60), "poisonous request must FAIL, not loop forever"
        assert r.error is not None and "iteration failed" in r.error
        assert r.crash_requeues == 2  # budget fully spent first
        assert eng.cache.n_blocks_in_use == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# drain admission race (ISSUE 13): requests hitting the window between
# begin_drain() and the 503 path either complete or get a clean 503
# ---------------------------------------------------------------------------

def test_drain_admission_race_never_hangs():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=64, block_size=4,
                          max_active=4, queue_depth=32,
                          admit_timeout_s=0.2)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    outcomes = []
    lock = threading.Lock()
    stop = threading.Event()

    def hammer(i):
        j = 0
        while not stop.is_set():
            j += 1
            try:
                _post(srv.url, {"prompt": [i + 1, j % 16 + 1],
                                "max_tokens": 2}, timeout=60)
                code = 200
            except urllib.error.HTTPError as e:
                code = e.code
            except (urllib.error.URLError, OSError):
                code = -1  # listener already closed: clean refusal
            with lock:
                outcomes.append(code)

    threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
               for i in range(6)]
    drained = {}
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with lock:
                if len(outcomes) >= 6:
                    break  # traffic is flowing; drain mid-burst
            time.sleep(0.01)
        drain_t = threading.Thread(
            target=lambda: drained.setdefault(
                "clean", srv.drain(timeout_s=60)), daemon=True)
        drain_t.start()
        drain_t.join(120)
        assert not drain_t.is_alive(), "drain wedged"
    finally:
        stop.set()
        for t in threads:
            # a hung handler would park the client past the drain: the
            # join timeout IS the no-hang assertion
            t.join(90)
            assert not t.is_alive(), \
                "a client hung across the drain window"
        srv.close()
        eng.close()
    assert drained.get("clean") is True
    with lock:
        seen = list(outcomes)
    assert seen.count(200) >= 6, f"no traffic completed: {seen[:20]}"
    bad = [c for c in seen if c not in (200, 503, 429, -1)]
    assert not bad, f"non-clean statuses across the drain window: {bad}"


# ---------------------------------------------------------------------------
# loadgen (ISSUE 13): Retry-After honored, retried-then-ok counted
# ---------------------------------------------------------------------------

class _BackpressureOnce:
    """Answers each distinct request_id with one 429/503 (Retry-After
    set), then 200 — the loadgen retry contract in miniature."""

    def __init__(self, code=429, retry_after="0.4"):
        import json as _json
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        outer = self
        self.seen = {}
        self.sleeps = []

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                doc = _json.loads(self.rfile.read(n))
                rid = doc.get("request_id")
                outer.seen[rid] = outer.seen.get(rid, 0) + 1
                if outer.seen[rid] == 1:
                    body = _json.dumps({"error": "busy"}).encode()
                    self.send_response(code)
                    if retry_after is not None:
                        self.send_header("Retry-After", retry_after)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                body = _json.dumps(
                    {"state": "done", "output_ids": [1],
                     "n_generated": 1, "ttft_s": 0.01,
                     "latency_s": 0.02}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_loadgen_honors_retry_after_and_counts_retried_ok():
    from dmlc_tpu.serving import LoadGenerator

    fake = _BackpressureOnce(code=429, retry_after="0.4")
    try:
        gen = LoadGenerator(fake.url, n_streams=2, requests_per_stream=1,
                            prompt_len=(2, 4), max_tokens=1,
                            retry_429_s=0.01)
        t0 = time.monotonic()
        summary = gen.run()
        elapsed = time.monotonic() - t0
        assert summary["n_requests_ok"] == 2
        assert summary["n_requests_failed"] == 0
        assert summary["n_requests_retried_ok"] == 2
        assert summary["n_rejections_429"] == 2
        # the header value (0.4s), not the 0.01s fallback, was honored
        assert elapsed >= 0.4, f"Retry-After ignored ({elapsed:.3f}s)"
        # every retry reused its request's idempotency key
        assert all(n == 2 for n in fake.seen.values())
    finally:
        fake.close()


def test_loadgen_retries_503_and_counts_separately():
    from dmlc_tpu.serving import LoadGenerator

    fake = _BackpressureOnce(code=503, retry_after="0.05")
    try:
        gen = LoadGenerator(fake.url, n_streams=1, requests_per_stream=2,
                            prompt_len=(2, 4), max_tokens=1,
                            retry_429_s=0.01)
        summary = gen.run()
        assert summary["n_requests_ok"] == 2
        assert summary["n_requests_failed"] == 0
        assert summary["n_requests_retried_ok"] == 2
        assert summary["n_backoffs_503"] == 2
        assert summary["n_rejections_429"] == 0
    finally:
        fake.close()


def test_loadgen_terminal_503_fails_once_with_error_body():
    """A 503 WITHOUT Retry-After is a terminal per-request verdict
    (engine failure, generation timeout): no retry amplification, and
    the server's error body survives into the failure record."""
    from dmlc_tpu.serving import LoadGenerator

    fake = _BackpressureOnce(code=503, retry_after=None)
    try:
        gen = LoadGenerator(fake.url, n_streams=1, requests_per_stream=1,
                            prompt_len=(2, 4), max_tokens=1,
                            retry_429_s=0.01)
        summary = gen.run()
        assert summary["n_requests_ok"] == 0
        assert summary["n_requests_failed"] == 1
        assert summary["n_backoffs_503"] == 0
        assert "busy" in gen.failures[0]["error"]  # body preserved
        # exactly ONE attempt: no fresh-generation amplification
        assert all(n == 1 for n in fake.seen.values())
    finally:
        fake.close()


def test_engine_fails_only_nonfinite_logit_request():
    """A non-finite logit row fails exactly that request with a clear
    error; the other request in the same decode batch (and the engine)
    keep serving."""
    from dmlc_tpu import telemetry

    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8)
    r1 = eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=6)
    r2 = eng.submit([4, 5, 6], max_new_tokens=3)
    eng.step()  # prefill r1
    eng.step()  # prefill r2 (+ decode r1)
    real = eng._decode
    fired = []

    def poisoned(p, feed, positions, pools, tables, lengths, extra, c):
        # a NaN in the second K page of r1's row (activation order;
        # block 0 is what padded table entries name): the program's own
        # epilogue has to report the row, and no other, as non-finite
        k_pool, v_pool = pools
        if not fired:
            k_pool = k_pool.at[0, int(tables[0, 1])].set(np.nan)
            fired.append(True)
        out = real(p, feed, positions, (k_pool, v_pool), tables, lengths,
                   extra, c)
        if len(fired) == 1:
            fired.append(np.asarray(out[1]).ravel().tolist())
        return out

    eng._decode = poisoned
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "nonfinite_failures", 0)
    for _ in range(20):
        if r1.wait(0) and r2.wait(0):
            break
        eng.step()
    assert r1.error is not None and "non-finite" in r1.error
    assert r2.error is None and r2.n_generated == 3
    assert fired[1] == [False, True, True]  # r1, r2, a dead row
    after = telemetry.counters_snapshot().get("serving", {}).get(
        "nonfinite_failures", 0)
    assert after == before + 1
    st = eng.stats()
    assert st["kv"]["blocks_in_use"] == 0  # failed request freed its blocks
    eng.close()


# ---------------------------------------------------------------------------
# the iteration measured from inside: spans, counters, bytes
# ---------------------------------------------------------------------------

def _engine_thread_spans():
    """The ring without the per-request rows RequestLedger draws."""
    return [r for r in telemetry.spans()
            if not r["thread"].startswith("req ")]


def _warm_engine(**kw):
    """An engine whose prefill and decode programs are compiled and
    whose pools are on the device, with one request still decoding."""
    params, cfg = _tiny_model()
    kw.setdefault("max_active", 4)
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          queue_depth=8, **kw)
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=40)
    # four steps: the decode program of the block-table width the next
    # three steps use is compiled (a width lasts block_size steps)
    for _ in range(4):
        eng.step()
    return eng


def test_engine_iteration_yields_the_span_tree():
    eng = _warm_engine()
    telemetry.reset()
    req = eng.submit([9, 8, 7, 6, 5, 4], max_new_tokens=6)
    assert eng.step()  # one iteration: a prefill and a decode window
    recs = _engine_thread_spans()
    by_id = {r["id"]: r for r in recs}
    tree = sorted((r["name"], by_id[r["parent"]]["name"]
                   if r["parent"] else None) for r in recs)
    assert tree == sorted([
        ("serving.iteration", None),
        ("serving.schedule", "serving.iteration"),   # next_prefill
        ("serving.schedule", "serving.iteration"),   # allocation
        ("serving.prefill", "serving.iteration"),
        ("serving.prefill.run", "serving.prefill"),
        ("serving.prefill.dispatch", "serving.prefill.run"),
        ("serving.prefill.fetch", "serving.prefill.run"),
        ("serving.kv_write", "serving.iteration"),
        ("serving.first_token", "serving.iteration"),
        ("serving.schedule", "serving.iteration"),   # next_prefill: none
        ("serving.schedule", "serving.iteration"),   # the decode batch
        ("serving.decode", "serving.iteration"),
        ("step", "serving.decode"),                  # the step ledger's
        ("serving.decode.dispatch", "step"),
        ("serving.decode.fetch", "step"),
        ("serving.decode.commit", "step"),
        ("serving.decode.deliver", "serving.decode"),
        ("serving.decode.bookkeeping", "serving.decode"),
    ])
    (it,) = [r for r in recs if r["name"] == "serving.iteration"]
    for r in recs:
        if r["name"].startswith("serving."):
            assert r["args"]["iter"] == it["args"]["iter"], r
    owned = {r["name"] for r in recs
             if r.get("args", {}).get("req") == req.id}
    assert owned == {"serving.schedule", "serving.prefill",
                     "serving.prefill.run", "serving.prefill.dispatch",
                     "serving.prefill.fetch", "serving.kv_write",
                     "serving.first_token"}
    eng.close()


def test_engine_iteration_children_are_disjoint_and_account_for_it():
    eng = _warm_engine()
    telemetry.reset()
    eng.submit([9, 8, 7, 6, 5, 4], max_new_tokens=30)
    for _ in range(6):
        assert eng.step()
    recs = _engine_thread_spans()
    iterations = [r for r in recs if r["name"] == "serving.iteration"]
    assert len(iterations) == 6
    covered = total = 0.0
    for it in iterations:
        kids = sorted((r for r in recs if r["parent"] == it["id"]),
                      key=lambda r: r["ts"])
        assert kids[0]["ts"] >= it["ts"]
        assert kids[-1]["ts"] + kids[-1]["dur"] <= it["ts"] + it["dur"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"], (a, b)  # disjoint
        covered += sum(k["dur"] for k in kids)
        total += it["dur"]
    # what the children leave out is python between two spans
    assert covered >= 0.95 * total, (covered, total)
    # ... and the counters say the same as the ring
    c = telemetry.counters_snapshot()["serving"]
    assert c["iteration_count"] == 6 and c["decode_count"] == 6
    assert c["iteration_secs"] == pytest.approx(total / 1e6)
    assert c["prefill_count"] == 1 and c["prefill_tokens"] == 6
    eng.close()


def test_prefill_dispatch_and_fetch_add_up_to_run():
    """The prefill's device call is split as the decode's is: the
    program's call, then the blocking read, both inside ``.run`` and
    leaving out only the python between two spans."""
    eng = _warm_engine()
    telemetry.reset()
    real_prefill = eng._prefill

    def prefill(*a):
        # a toy prefill is launched in half a millisecond, of which the
        # three spans' own bookkeeping is a tenth: give the call a
        # length that a real one has
        time.sleep(0.005)
        return real_prefill(*a)

    eng._prefill = prefill
    for i in range(6):
        eng.submit([9, 8, 7, 6, 5, 4 + i], max_new_tokens=2)
    for _ in range(12):
        eng.step()
    recs = _engine_thread_spans()
    runs = [r for r in recs if r["name"] == "serving.prefill.run"]
    assert len(runs) == 6
    inside = 0.0
    for run in runs:
        kids = sorted((r for r in recs if r["parent"] == run["id"]),
                      key=lambda r: r["ts"])
        assert [k["name"] for k in kids] == ["serving.prefill.dispatch",
                                             "serving.prefill.fetch"]
        assert kids[0]["ts"] >= run["ts"]
        assert kids[0]["ts"] + kids[0]["dur"] <= kids[1]["ts"]
        assert kids[1]["ts"] + kids[1]["dur"] <= run["ts"] + run["dur"]
        assert all(k["args"]["req"] == run["args"]["req"] for k in kids)
        inside += kids[0]["dur"] + kids[1]["dur"]
    assert inside >= 0.95 * sum(r["dur"] for r in runs)
    c = telemetry.counters_snapshot()["serving"]
    assert c["prefill_dispatch_count"] == c["prefill_fetch_count"] == 6
    assert c["prefill_dispatch_secs"] + c["prefill_fetch_secs"] \
        == pytest.approx(inside / 1e6)
    eng.close()


def test_engine_construction_is_a_span_with_both_children():
    params, cfg = _tiny_model()
    telemetry.reset()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    recs = [r for r in telemetry.spans()
            if r["name"].startswith("serving.engine_init")]
    (init,) = [r for r in recs if r["name"] == "serving.engine_init"]
    kids = sorted((r for r in recs if r["parent"] == init["id"]),
                  key=lambda r: r["ts"])
    assert [k["name"] for k in kids] == ["serving.engine_init.weights",
                                         "serving.engine_init.cache"]
    assert len(recs) == 3
    assert kids[0]["ts"] >= init["ts"]
    assert kids[0]["ts"] + kids[0]["dur"] <= kids[1]["ts"]
    assert kids[1]["ts"] + kids[1]["dur"] <= init["ts"] + init["dur"]
    # on the constructing thread, a child of nothing
    assert init["parent"] is None
    assert init["tid"] == threading.get_ident()
    c = telemetry.counters_snapshot()["serving"]
    assert c["engine_init_count"] == 1
    assert c["engine_init_secs"] == pytest.approx(init["dur"] / 1e6)
    assert c["engine_init_weights_secs"] + c["engine_init_cache_secs"] \
        <= c["engine_init_secs"]
    # start() zeroes the iteration's families, not the construction's
    eng.start()
    after = telemetry.counters_snapshot()["serving"]
    eng.close()
    assert after["engine_init_secs"] == c["engine_init_secs"]


def test_engine_starved_is_one_span_per_episode():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    telemetry.reset()
    passes = 25

    def idle_passes(n):
        """Wait for n passes of the loop that found no work.  A pass
        ticks ``_step_seq`` twice; one more tick covers the pass that
        was finishing the last request when the wait began."""
        want = eng._step_seq + 2 * n + 1
        deadline = time.monotonic() + 300
        while eng._step_seq < want:
            assert time.monotonic() < deadline, "the loop stopped passing"
            time.sleep(0.001)

    eng.start()
    try:
        idle_passes(passes)  # many empty passes of the loop: ONE episode
        assert eng.submit([1, 2, 3], max_new_tokens=3).wait(300)
        idle_passes(passes)  # a second episode
        assert eng.submit([4, 5, 6], max_new_tokens=3).wait(300)
        idle_passes(2)  # the third has opened
    finally:
        eng.close()  # the loop's exit closes the episode in flight
    recs = _engine_thread_spans()
    starved = [r for r in recs if r["name"] == "serving.starved"]
    assert len(starved) == 3, [r["dur"] for r in starved]
    # one span over the episode's passes, each of which slept 2 ms (the
    # first may have opened it, the last may be cut by the submit)
    for episode in starved[:2]:
        assert episode["dur"] >= (passes - 2) * 0.002e6
    # an iteration is a step() that found work: none inside an episode,
    # none empty
    iterations = [r for r in recs if r["name"] == "serving.iteration"]
    assert iterations and all(r["parent"] is None for r in iterations)
    for it in iterations:
        assert any(r["parent"] == it["id"] for r in recs)
        for s in starved:
            assert (it["ts"] >= s["ts"] + s["dur"]
                    or it["ts"] + it["dur"] <= s["ts"])
    assert telemetry.counters_snapshot()["serving"]["starved_count"] == 3


def test_engine_byte_counters_equal_what_crossed():
    """The picked ids and their finiteness are all that crosses the
    link, for a prefill and for a decode step: never the logits.
    Nothing goes back up."""
    eng = _warm_engine()
    telemetry.reset()
    crossed = {"prefill": 0, "decode": 0}
    real_prefill, real_decode = eng._prefill, eng._decode

    def prefill(*a):
        out = real_prefill(*a)
        crossed["prefill"] += sum(np.asarray(o).nbytes for o in out[:2])
        return out

    def decode(*a):
        out = real_decode(*a)
        crossed["decode"] += sum(np.asarray(o).nbytes for o in out[:2])
        return out

    eng._prefill, eng._decode = prefill, decode
    eng.submit([9, 8, 7, 6, 5, 4], max_new_tokens=4)
    for _ in range(3):
        eng.step()
    c = telemetry.counters_snapshot()["serving"]
    assert c["prefill_d2h_bytes"] == crossed["prefill"] > 0
    assert c["decode_d2h_bytes"] == crossed["decode"] > 0
    assert c.get("kv_upload_bytes", 0) == 0
    recs = _engine_thread_spans()
    assert not [r for r in recs if r["name"] == "serving.kv_upload"]
    assert sum(r["args"]["bytes"] for r in recs
               if r["name"] == "serving.decode.fetch") \
        == c["decode_d2h_bytes"]
    picked = 4 + 1  # an int32 id and a bool
    assert c["prefill_d2h_bytes"] == picked
    # every step fetches the whole padded batch: max_active rows
    assert c["decode_d2h_bytes"] == 3 * eng.max_active * picked
    eng.close()


def _loop_answers(eng, prompts, max_new):
    """Run ``prompts`` through the engine's own thread; their ids."""
    eng.start()
    try:
        reqs = [eng.submit(list(p), max_new_tokens=m)
                for p, m in zip(prompts, max_new)]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
    finally:
        eng.close()
    assert [r.error for r in reqs] == [None] * len(reqs)
    return [list(r.generated) for r in reqs]


def test_the_loop_reads_a_prefills_pick_behind_the_next_step():
    """Under the engine's own thread a prefill's ``.run`` is its
    dispatch alone; its pick is read in a ``serving.prefill.fetch`` under
    the decode span that reads the step that went out with its row,
    before that step's own ids, and the first token is taken there, TTFT
    with it.  The ids are a single-stepped engine's."""
    params, cfg = _tiny_model()
    prompts = [[(3 * i + j) % 60 + 1 for i in range(3 + j)] for j in range(6)]
    max_new = [3, 7, 4, 6, 2, 5]
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8)
    want = []
    for p, m in zip(prompts, max_new):
        req = eng.submit(list(p), max_new_tokens=m)
        while not req.wait(0):
            eng.step()
        want.append(list(req.generated))
    eng.close()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=3, queue_depth=8)
    telemetry.reset()
    assert _loop_answers(eng, prompts, max_new) == want
    recs = _engine_thread_spans()
    by_id = {r["id"]: r for r in recs}
    runs = [r for r in recs if r["name"] == "serving.prefill.run"]
    assert len(runs) == len(prompts)
    for run in runs:
        kids = [r["name"] for r in recs if r["parent"] == run["id"]]
        assert kids == ["serving.prefill.dispatch"]

    def enclosing(rec):  # the nearest engine span around it
        rec = by_id[rec["parent"]]
        while not rec["name"].startswith("serving."):
            rec = by_id[rec["parent"]]  # the ledger's step
        return rec

    for name in ("serving.prefill.fetch", "serving.first_token"):
        spans = [r for r in recs if r["name"] == name]
        assert len(spans) == len(prompts)
        assert {enclosing(r)["name"] for r in spans} == {"serving.decode"}
    own = {enclosing(r)["id"]: r for r in recs
           if r["name"] == "serving.decode.fetch"}
    for r in recs:
        if r["name"] == "serving.prefill.fetch":
            assert r["ts"] + r["dur"] <= own[enclosing(r)["id"]]["ts"]
    c = telemetry.counters_snapshot()["serving"]
    assert c["prefills_deferred"] == c["prefill_count"] == len(prompts)
    assert c["prefill_fetch_count"] == len(prompts)
    assert c["prefill_d2h_bytes"] == 5 * len(prompts)


def test_prefills_deferred_is_zeroed_at_start_and_counts_the_loops():
    """``serving.prefills_deferred`` reads 0 from ``start()`` on, stays 0
    for a single-stepped engine, and counts every prefill the loop's
    own thread reads behind a later step."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    telemetry.reset()
    for i in range(3):
        eng.submit([5, 4, 3, 2 + i], max_new_tokens=3)
    for _ in range(12):
        eng.step()
    c = telemetry.counters_snapshot()["serving"]
    assert c["prefill_count"] == 3 and "prefills_deferred" not in c
    eng.close()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    telemetry.reset()
    eng.start()
    assert telemetry.counters_snapshot()["serving"]["prefills_deferred"] == 0
    eng.close()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    _loop_answers(eng, [[5, 4, 3, 2 + i] for i in range(3)], [3, 3, 3])
    c = telemetry.counters_snapshot()["serving"]
    assert c["prefills_deferred"] == c["prefill_count"] == 3


def test_prefill_overlap_share_reads_through_the_ratio_reader():
    """The per-layer metric ``prefill_overlap_share``: deferred prefills
    over the ``serving.prefill`` span's count, x100, in every serve cell,
    beside ``decode_overlap_share`` with the same layer, direction and
    end-to-end metric; a program without the counter reads nothing."""
    from benchmarks import harness

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    mine, model = entries["prefill_overlap_share"], entries[
        "decode_overlap_share"]
    # appended last when it came, and kept in that place since: later
    # metrics are appended after it
    assert bench["per_layer"][68] is mine
    assert dict(mine, name="decode_overlap_share") == model
    serve = [w["name"] for w in bench["workloads"]
             if w["name"].startswith("serve-")]
    assert mine["workloads"] == serve
    for cell in serve:
        (m,) = [m for m in harness.load_cell(cell).per_layer
                if m["name"] == "prefill_overlap_share"]
        read = harness.layer_reader(m["spec"])
        params = m["spec"]["params"]
        numbers = {"counters.serving.prefills_deferred": 57.0,
                   "counters.serving.prefill_count": 60.0}
        assert read({"numbers": numbers}, params) == pytest.approx(95.0)
        # the parent's program: no counter
        del numbers["counters.serving.prefills_deferred"]
        assert read({"numbers": numbers}, params) is None
        # no prefill in the window
        assert read({"numbers": {
            "counters.serving.prefills_deferred": 0.0,
            "counters.serving.prefill_count": 0.0}}, params) is None


def test_engine_start_zeroes_its_phase_counters():
    from dmlc_tpu.serving.engine import _ZEROED_COUNTERS

    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    telemetry.reset()
    eng.start()
    c = telemetry.counters_snapshot()["serving"]
    eng.close()
    # a window in which a phase never ran reads 0, not "nothing"
    assert set(_ZEROED_COUNTERS) <= set(c)
    # ... among them the K/V round trip's, which the paged path never
    # opens: the benchmark's readers still find a number there
    for name in ("decode_fetch_secs", "decode_fetch_count", "http_count",
                 "kv_upload_bytes", "queue_wait_count", "starved_secs",
                 "prefill_kv_to_host_secs", "prefill_kv_to_host_count",
                 "kv_upload_secs", "kv_upload_count"):
        assert c[name] == 0.0, name


def test_request_rows_carry_the_iteration_that_drew_them():
    eng = _warm_engine()
    telemetry.reset()
    req = eng.submit([9, 8, 7, 6], max_new_tokens=3)
    while not req.wait(0):
        eng.step()
    rows = {r["name"]: r for r in telemetry.spans()
            if r["thread"] == f"req {req.id}"}
    assert set(rows) == {"serving.queue", "serving.prefill",
                         "serving.decode"}
    (ran,) = [r for r in _engine_thread_spans()
              if r["name"] == "serving.prefill"]
    assert rows["serving.queue"]["args"]["iter"] == ran["args"]["iter"]
    assert rows["serving.prefill"]["args"]["iter"] == ran["args"]["iter"]
    # the decode slice closes in the iteration that finished the request
    assert rows["serving.decode"]["args"]["iter"] == eng._iter
    c = telemetry.counters_snapshot()["serving"]
    assert c["queue_wait_count"] == 1 and c["queue_wait_secs"] > 0
    eng.close()


def test_http_span_times_the_request_outside_the_engine():
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    telemetry.reset()
    try:
        doc = _post(srv.url, {"prompt": [1, 2, 3], "max_tokens": 4})
        with pytest.raises(urllib.error.HTTPError):
            _post(srv.url, {"prompt": "not a list"})
    finally:
        srv.close()
        eng.close()
    http = [r for r in telemetry.spans() if r["name"] == "serving.http"]
    assert len(http) == 2
    assert http[0]["args"] == {"req": doc["id"]}  # known once admitted
    assert "args" not in http[1]                  # refused before that
    assert http[0]["thread"] != "serving-engine"
    c = telemetry.counters_snapshot()["serving"]
    assert c["http_count"] == 2
    # body parse to response written encloses submit to finish
    assert c["http_secs"] > c["latency_secs"] > 0


def test_engine_spans_are_host_events_of_a_profile(tmp_path):
    """A real (CPU) profiler capture holds the engine's spans as host
    events: what benchmarks/reduce_trace.py attributes idle time to."""
    import glob

    import jax

    eng = _warm_engine()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    seen = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serving."):
                        seen[ev.name] = seen.get(ev.name, 0) + 1
    assert seen.get("serving.iteration") == 3, seen
    for name in ("serving.schedule", "serving.decode",
                 "serving.decode.dispatch", "serving.decode.fetch",
                 "serving.decode.commit", "serving.decode.deliver",
                 "serving.decode.bookkeeping"):
        assert seen.get(name, 0) >= 3, (name, seen)
