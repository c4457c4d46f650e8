"""The decode loop without the host on its critical path: the programs
pick the token, a step's picks stay on the device for the next step,
and the engine reads a step while the next one runs; a prefill's pick
too is read behind the step that went out with its row.  Every family
(MHA, latent, hybrid) at a small size on the CPU; ``step(lookahead=
True)`` is the loop's way, driven here from the test's own thread."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_hybrid_family as hybrid
import test_latent_family as latent
import test_nemotron_h_family as pattern
from dmlc_tpu import telemetry
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.serving import InferenceEngine, PagedKVCache, Request
from dmlc_tpu.serving.scheduler import (ACTIVE, DONE, WAITING,
                                        ContinuousBatchScheduler)

FAMILIES = ("mha", "mla", "kda_mla")
BS = 8

#: five requests through a batch of two: rows end at different steps
#: and the queue refills their places
PROMPTS = ([5, 6, 7], [9, 8, 7, 6, 5, 4, 3, 2, 1], [11, 12], [3, 1, 4, 1, 5],
           [2, 7, 1, 8, 2, 8])
MAX_NEW = (7, 3, 9, 1, 6)


def _model(family):
    if family == "mha":
        cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                                    head_dim=8, d_ff=64, n_layers=2,
                                    n_experts=1, microbatches=1)
        return tfm.init_params(jax.random.PRNGKey(0), cfg), cfg
    if family == "mla":
        cfg = latent.small()
        return latent.weights(cfg), cfg
    if family == "nemotron_h":  # Mamba-2 state beside a K/V pool
        cfg = pattern.small()
        return pattern.weights(cfg), cfg
    cfg = hybrid.small(n_layers=4)  # dense + KDA, MLA, KDA + KDA
    return hybrid.weights(cfg), cfg


_MODELS = {}


def _engine(family, **kw):
    if family not in _MODELS:
        _MODELS[family] = _model(family)
    params, cfg = _MODELS[family]
    kw = dict(dict(n_blocks=32, block_size=BS, max_active=2,
                   queue_depth=8), **kw)
    return InferenceEngine(params, cfg, **kw)


def _drive(eng, lookahead, prompts=PROMPTS, max_new=MAX_NEW, limit=400):
    """Submit everything, then single-step until all are answered."""
    reqs = [eng.submit(list(p), max_new_tokens=m)
            for p, m in zip(prompts, max_new)]
    for _ in range(limit):
        if all(r.wait(0) for r in reqs):
            break
        eng.step(lookahead=lookahead)
    assert all(r.wait(0) for r in reqs), "not answered within the limit"
    return reqs


def _grew(before, *names):
    now = telemetry.counters_snapshot().get("serving", {})
    return [now.get(n, 0) - before.get(n, 0) for n in names]


def _serving():
    return dict(telemetry.counters_snapshot().get("serving", {}))


def _recording(eng):
    """Wrap the engine's decode program: ``calls`` gets ``(table width,
    live rows)`` of every dispatch."""
    real, calls = eng._decode, []

    def decode(p, feed, positions, pools, tables, lengths, *rest):
        calls.append((tables.shape[1], int((lengths > 0).sum())))
        return real(p, feed, positions, pools, tables, lengths, *rest)

    eng._decode = decode
    return calls


@pytest.fixture(scope="module")
def settled():
    """Each family's answers from an engine that reads every step at
    once (what the parent did), with what its decode program ran."""
    out = {}

    def get(family):
        if family not in out:
            eng = _engine(family)
            calls = _recording(eng)
            before = _serving()
            reqs = _drive(eng, lookahead=False)
            steps, overlapped = _grew(before, "paged_decode_steps",
                                      "decode_steps_overlapped")
            eng.close()
            assert all(r.error is None for r in reqs)
            assert overlapped == 0
            out[family] = ([list(r.generated) for r in reqs], calls, steps)
        return out[family]

    return get


# ---------------------------------------------------------------------------
# the epilogue
# ---------------------------------------------------------------------------

_ROWS = {
    "ties": [[1.0, 3.0, 3.0, 2.0], [0.0, 0.0, 0.0, 0.0]],
    "nan": [[1.0, np.nan, 9.0, np.nan], [1.0, 2.0, 3.0, 4.0]],
    "all_neg_inf": [[-np.inf] * 4, [-np.inf, 0.5, -np.inf, 0.5]],
    "pos_inf": [[1.0, np.inf, 2.0, np.inf], [np.inf, np.nan, 0.0, 0.0]],
    "neg_inf_beside_the_top": [[-np.inf, 2.0, 1.0, -np.inf],
                               [3.0, 2.0, 1.0, 0.0]],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_ROWS))
def test_epilogue_picks_as_numpy_does(case, dtype):
    """``greedy_pick`` is ``np.argmax`` (the first index of the
    maximum, a NaN row on its first NaN) and the finiteness of the
    picked logit, in the dtype the logits have."""
    logits = jnp.asarray(np.array(_ROWS[case], np.float32)[:, None, :],
                         dtype)
    ids, finite = jax.jit(tfm.greedy_pick)(logits)
    host = np.asarray(logits.astype(jnp.float32))
    want = np.argmax(host, axis=-1)
    assert ids.dtype == jnp.int32 and ids.shape == (2, 1)
    np.testing.assert_array_equal(np.asarray(ids), want)
    np.testing.assert_array_equal(
        np.asarray(finite),
        np.isfinite(np.take_along_axis(host, want[..., None], -1))[..., 0])


def test_decode_program_takes_each_row_from_the_host_or_the_device():
    """``picking_decode`` feeds row b ``prev_ids[src[b]]`` or, where
    ``src[b]`` < 0, ``host_ids[b]``; ``picking_prefill`` swaps the
    logits for the pick and writes the pick into row ``row`` of the ids
    it is handed, or nowhere where the row is out of range."""
    def forward(params, ids, scale):
        return jax.nn.one_hot(ids, 16) * scale, "pool"

    host = np.array([[3], [4], [5], [6]], np.int32)
    prev = np.array([[10], [11], [12], [13]], np.int32)
    src = np.array([2, -1, 0, -1], np.int32)
    ids, finite, rest = tfm.picking_decode(forward)(
        None, (host, prev, src), 2.0)
    assert np.asarray(ids)[:, 0].tolist() == [12, 4, 10, 6]
    assert np.asarray(finite).all() and rest == "pool"
    ids, finite, fed, rest = tfm.picking_prefill(forward)(
        None, host[:1, 0], (1.0, prev, np.int32(2)))
    assert np.asarray(ids).tolist() == [3] and rest == "pool"
    assert np.asarray(fed)[:, 0].tolist() == [10, 11, 3, 13]
    for row in (4, 9):
        fed = tfm.picking_prefill(forward)(
            None, host[:1, 0], (1.0, prev, np.int32(row)))[2]
        np.testing.assert_array_equal(np.asarray(fed), prev)


# ---------------------------------------------------------------------------
# the scheduler's third set
# ---------------------------------------------------------------------------

def test_a_retiring_request_gives_its_place_and_is_still_running():
    cache = PagedKVCache(1, 1, 4, n_blocks=4, block_size=4)
    sched = ContinuousBatchScheduler(cache, max_active=1)
    a, b = Request([1, 2, 3], 4), Request([4, 5], 4)
    sched.enqueue(a)
    sched.enqueue(b)
    assert sched.next_prefill() is a and cache.allocate(a.id, 3)
    sched.activate(a)
    assert sched.next_prefill() is None  # the batch is full
    sched.retire(a)
    assert a.state == ACTIVE and a.id not in cache.live_sequences()
    assert sched.counts() == (1, 1) and sched.n_active == 1
    assert sched.active_requests() == []
    assert sched.running_requests() == [a] and a in sched.all_pending()
    assert sched.preempt_youngest() is None  # nothing of a's to free
    assert sched.next_prefill() is b  # a's place
    sched.activate(b)
    assert sched.counts() == (2, 0)
    # the crash requeue takes a retiring request back too
    assert sched.requeue_active(a) and a.state == WAITING
    assert sched.counts() == (1, 1) and a.crash_requeues == 1
    sched.retire(b)
    sched.finish(b)
    assert b.state == DONE and sched.counts() == (0, 1)


# ---------------------------------------------------------------------------
# the engine, every family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_lookahead_answers_as_the_settled_engine_does(family, settled):
    """Rows that end at different steps and are refilled from the
    queue: the same ids, the same steps at the same table widths with
    the same live rows (a row that ends by count is out of the next
    step and its place is the next prefill's, as if the step had been
    read), all but the first of a run dispatched over an unread step,
    and no token discarded."""
    want, want_calls, want_steps = settled(family)
    eng = _engine(family)
    calls = _recording(eng)
    before = _serving()
    reqs = _drive(eng, lookahead=True)
    steps, overlapped, discarded = _grew(
        before, "paged_decode_steps", "decode_steps_overlapped",
        "lookahead_discarded_tokens")
    assert eng._inflight is None and eng.cache.n_blocks_in_use == 0
    eng.close()
    assert [r.error for r in reqs] == [None] * len(reqs)
    assert [list(r.generated) for r in reqs] == want
    assert [len(g) for g in want] == list(MAX_NEW)
    assert calls == want_calls and steps == want_steps == len(calls)
    assert discarded == 0
    # one run of lookahead: only its first step had nothing before it
    assert overlapped == steps - 1


@pytest.mark.parametrize("family", FAMILIES)
def test_single_stepped_engine_reads_every_step_at_once(family, settled):
    """``step()`` from outside the loop leaves nothing in flight, and
    takes over from a loop that did."""
    eng = _engine(family)
    reqs = [eng.submit(list(p), max_new_tokens=m)
            for p, m in zip(PROMPTS[:2], (7, 5))]
    eng.step()
    assert eng._inflight is None
    assert [r.n_generated for r in reqs] == [2, 2]
    eng.step(lookahead=True)
    assert eng._inflight is not None
    assert [r.n_generated for r in reqs] == [2, 2]  # unread
    eng.step(lookahead=True)
    assert [r.n_generated for r in reqs] == [3, 3]  # the step before
    eng.step()
    assert eng._inflight is None
    assert [r.n_generated for r in reqs] == [5, 5]  # both
    for _ in range(10):
        eng.step()
    eng.close()
    want = settled(family)[0]
    assert reqs[0].generated == want[0]  # 7 tokens
    assert reqs[1].n_generated == 5 and reqs[1].generated[:3] == want[1]


def _eos_for(want):
    """A token that ends exactly one of the first two rows early, at a
    decode step: ``(eos, row, its answer cut there)``."""
    for row in (0, 1):
        other = want[1 - row]
        for k in range(1, len(want[row]) - 1):
            tok = want[row][k]
            if tok not in want[row][:k] and tok not in other:
                return tok, row, want[row][:k + 1]
    raise AssertionError(f"no usable eos in {want}")


@pytest.mark.parametrize("family", FAMILIES)
def test_a_row_ending_by_eos_discards_exactly_one_token(family):
    """The row that emits ``eos_id`` at step n is in step n+1, which was
    dispatched before n was read: that one token is no output, the
    other row goes on undisturbed, and a recurrent family's state bytes
    count the rows each step really ran."""
    prompts, max_new = PROMPTS[:2], (8, 8)
    eng = _engine(family)
    want = [list(r.generated)
            for r in _drive(eng, False, prompts, max_new)]
    eng.close()
    eos, row, cut = _eos_for(want)
    want[row] = cut
    eng = _engine(family, eos_id=eos)
    calls = _recording(eng)
    before = _serving()
    reqs = _drive(eng, True, prompts, max_new)
    discarded, state_bytes = _grew(before, "lookahead_discarded_tokens",
                                   "kda_state_rw_bytes")
    assert [list(r.generated) for r in reqs] == want
    assert [r.error for r in reqs] == [None, None]
    assert discarded == 1
    assert eng.cache.n_blocks_in_use == 0 and eng._inflight is None
    # the row that had ended ran in one step more than it has tokens
    assert sum(rows for _, rows in calls) == sum(
        len(g) - 1 for g in want) + 1
    assert state_bytes == eng._state_rw_bytes * sum(r for _, r in calls)
    assert (family == "kda_mla") == bool(state_bytes)
    eng.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_a_nonfinite_row_fails_alone_under_lookahead(family, settled):
    """An injected non-finite pick fails that request, after the tokens
    it had; the step that was already dispatched with the row discards
    its token, and every other request gets the settled engine's ids."""
    want = settled(family)[0]
    eng = _engine(family)
    real, n = eng._decode, [0]

    def decode(*a):
        out = real(*a)
        n[0] += 1
        if n[0] != 3:
            return out
        finite = np.asarray(out[1]).copy()
        finite[0] = False  # the first request's row
        return (out[0], finite) + tuple(out[2:])

    eng._decode = decode
    before = _serving()
    reqs = _drive(eng, lookahead=True)
    failures, discarded = _grew(before, "nonfinite_failures",
                                "lookahead_discarded_tokens")
    eng.close()
    assert reqs[0].error is not None and "non-finite" in reqs[0].error
    assert reqs[0].generated == want[0][:3]  # prefill + two steps
    assert [r.error for r in reqs[1:]] == [None] * 4
    assert [list(r.generated) for r in reqs[1:]] == want[1:]
    assert (failures, discarded) == (1, 1)
    assert eng.cache.n_blocks_in_use == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_preemption_reads_the_step_in_flight_first(family):
    """Two rows outgrow a pool of three blocks: the victim is requeued
    with every token it had generated, the one in flight included, and
    both answers are those of an engine that never preempts."""
    prompts, max_new = ([1, 2, 3, 4, 5, 6], [6, 5, 4, 3, 2, 1]), (12, 12)
    eng = _engine(family)
    want = [list(r.generated)
            for r in _drive(eng, False, prompts, max_new)]
    eng.close()
    eng = _engine(family, n_blocks=3)
    reqs = _drive(eng, True, prompts, max_new)
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.error for r in reqs] == [None, None]
    assert [list(r.generated) for r in reqs] == want
    assert eng.cache.n_blocks_in_use == 0 and eng._inflight is None
    eng.close()


@pytest.mark.parametrize("pools_lost", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_crash_requeue_reads_the_step_in_flight_first(family, pools_lost,
                                                      settled):
    """A decode dispatch that fails under the loop, with a step unread
    behind it: that step's picks are output (its ids were not in the
    failed call's hands), the live rows resume from there, and the
    answers are the settled engine's."""
    want = settled(family)[0]
    eng = _engine(family)
    real, n = eng._decode, [0]
    seen = {}

    def decode(p, feed, positions, pools, *rest):
        n[0] += 1
        if n[0] == 4:
            seen["unread"] = eng._inflight is not None
            if pools_lost:
                for a in pools:
                    a.delete()
            raise RuntimeError("injected decode failure")
        return real(p, feed, positions, pools, *rest)

    eng._decode = decode
    before = _serving()
    eng.start()
    try:
        reqs = [eng.submit(list(p), max_new_tokens=m)
                for p, m in zip(PROMPTS, MAX_NEW)]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
    finally:
        eng.close()
    (requeues,) = _grew(before, "crash_requeues")
    assert seen["unread"] and requeues >= 1
    assert [r.error for r in reqs] == [None] * len(reqs)
    assert [list(r.generated) for r in reqs] == want
    # what was requeued had the unread step's token: one more than the
    # failing dispatch saw
    assert max(r.crash_requeues for r in reqs) == 1
    assert eng.cache.n_blocks_in_use == 0


@pytest.mark.parametrize("family", ["mha", "mla"])
def test_a_speculative_window_leaves_nothing_in_flight(family, settled,
                                                       monkeypatch):
    """``DMLC_SERVE_SPEC_K`` > 0: how many tokens a step commits depends
    on its ids, so the loop's way reads every step at once, and the ids
    are the ones plain decode gives."""
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "2")
    eng = _engine(family)
    assert eng._spec_window == 3
    before = _serving()
    reqs = [eng.submit(list(p), max_new_tokens=m)
            for p, m in zip(PROMPTS, MAX_NEW)]
    for _ in range(400):
        if all(r.wait(0) for r in reqs):
            break
        eng.step(lookahead=True)
        assert eng._inflight is None
    overlapped, discarded, steps = _grew(
        before, "decode_steps_overlapped", "lookahead_discarded_tokens",
        "paged_decode_steps")
    eng.close()
    assert [list(r.generated) for r in reqs] == settled(family)[0]
    assert (overlapped, discarded) == (0, 0) and steps > 0


@pytest.mark.parametrize("family", FAMILIES)
def test_the_loop_runs_ahead_and_a_stop_reads_what_is_left(family, settled):
    """Under its own thread the engine dispatches over unread steps,
    answers as the settled engine does, and leaves nothing unread
    behind a close."""
    eng = _engine(family)
    before = _serving()
    eng.start()
    try:
        reqs = [eng.submit(list(p), max_new_tokens=m)
                for p, m in zip(PROMPTS, MAX_NEW)]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
    finally:
        eng.close()
    steps, overlapped = _grew(before, "paged_decode_steps",
                              "decode_steps_overlapped")
    assert [list(r.generated) for r in reqs] == settled(family)[0]
    assert eng._inflight is None
    # a run of lookahead ends where the batch empties; five requests
    # make at most five runs
    assert steps - 5 <= overlapped < steps


# ---------------------------------------------------------------------------
# a prefill's pick, read behind the step that goes out with its row
# ---------------------------------------------------------------------------

#: eight short requests through a batch of two: each refill's prefill
#: lands while a decode step is in flight
STREAM = [[(7 * i + 3 * j) % 60 + 1 for i in range(2 + j % 5)]
          for j in range(8)]
STREAM_NEW = [2 + (5 * j) % 7 for j in range(8)]


def _logging(eng, events):
    """Wrap the programs and the prefill's read: ``events`` gets
    "prefill", "decode" or "read" as each happens; returns the feeds
    the decode program was handed."""
    prefill, decode, read = eng._prefill, eng._decode, eng._read_prefill
    feeds = []

    def logged_prefill(*a):
        events.append("prefill")
        return prefill(*a)

    def logged_decode(p, feed, *rest):
        events.append("decode")
        feeds.append(feed)
        return decode(p, feed, *rest)

    def logged_read(pf):
        events.append("read")
        return read(pf)

    eng._prefill, eng._decode = logged_prefill, logged_decode
    eng._read_prefill = logged_read
    return feeds


@pytest.mark.parametrize("family", FAMILIES + ("nemotron_h",))
def test_prefills_land_behind_a_step_in_flight(family):
    """Under the engine's own thread a stream of short requests refills
    the batch while a step is in flight: every prefill's pick is read
    behind the step that went out with its row, and the ids are a
    single-stepped engine's, a recurrent model's state slots joining
    with their rows."""
    eng = _engine(family)
    before = _serving()
    want = [list(r.generated) for r in _drive(eng, False, STREAM,
                                               STREAM_NEW)]
    (settled_deferred,) = _grew(before, "prefills_deferred")
    eng.close()
    eng = _engine(family)
    before = _serving()
    eng.start()
    try:
        reqs = [eng.submit(list(p), max_new_tokens=m)
                for p, m in zip(STREAM, STREAM_NEW)]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
    finally:
        eng.close()
    deferred, prefills, discarded = _grew(
        before, "prefills_deferred", "prefill_count",
        "lookahead_discarded_tokens")
    assert settled_deferred == 0
    assert [r.error for r in reqs] == [None] * len(reqs)
    assert [list(r.generated) for r in reqs] == want
    assert [len(g) for g in want] == STREAM_NEW
    assert deferred == prefills == len(STREAM) and discarded == 0
    assert all(r.ttft_s is not None for r in reqs)
    assert eng.cache.n_blocks_in_use == 0 and not eng._fresh


@pytest.mark.parametrize("family", FAMILIES)
def test_prefills_of_one_iteration_go_out_before_any_is_read(family):
    """Four requests admitted in one iteration: the four prefills and
    the step that takes their rows go out before any pick is read, each
    pick in a row of its own of the ids that step is handed; the picks
    are read with that step, after the next one went out."""
    prompts, max_new = PROMPTS[:4], (5, 4, 6, 3)
    eng = _engine(family, max_active=4)
    want = [list(r.generated) for r in _drive(eng, False, prompts,
                                              max_new)]
    eng.close()
    eng = _engine(family, max_active=4)
    events = []
    feeds = _logging(eng, events)
    reqs = _drive(eng, True, prompts, max_new)
    eng.close()
    assert [list(r.generated) for r in reqs] == want
    assert events[:10] == ["prefill"] * 4 + ["decode"] * 2 + ["read"] * 4
    _, prev_ids, src = feeds[0]
    assert np.asarray(src).tolist() == [0, 1, 2, 3]
    assert np.asarray(prev_ids)[:, 0].tolist() == [w[0] for w in want]


def _first_token_eos(want):
    """``(row, eos)``: a row's first token that the other row never
    emits, so that it ends that row alone."""
    for row in (0, 1):
        if want[row][0] not in want[1 - row]:
            return row, want[row][0]
    raise AssertionError(f"no usable eos in {want}")


@pytest.mark.parametrize("end", ["eos", "max_tokens"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_first_token_that_ends_its_request(family, end):
    """A first token that is ``eos_id`` is found out where the pick is
    read, after the step that took the row and the one behind it went
    out: it is the one token delivered and those two are discarded.
    One that ends the request by count is known at the dispatch: the row
    joins no step and nothing is discarded."""
    prompts, max_new = PROMPTS[:2], [6, 6]
    eng = _engine(family)
    want = [list(r.generated) for r in _drive(eng, False, prompts,
                                              max_new)]
    eng.close()
    row, eos = _first_token_eos(want)
    kw = {}
    if end == "eos":
        kw["eos_id"] = eos
    else:
        max_new[row] = 1
    want[row] = want[row][:1]
    eng = _engine(family, **kw)
    calls = _recording(eng)
    before = _serving()
    reqs = _drive(eng, True, prompts, max_new)
    discarded, deferred = _grew(before, "lookahead_discarded_tokens",
                                "prefills_deferred")
    eng.close()
    assert [list(r.generated) for r in reqs] == want
    assert [r.error for r in reqs] == [None, None]
    assert deferred == 2
    rows = [n for _, n in calls]
    if end == "eos":
        assert discarded == 2 and rows[:2] == [2, 2] and max(rows[2:]) == 1
    else:
        assert discarded == 0 and max(rows) == 1
    assert eng.cache.n_blocks_in_use == 0 and eng._inflight is None


@pytest.mark.parametrize("family", FAMILIES)
def test_a_nonfinite_first_pick_fails_its_request_under_lookahead(family,
                                                                  settled):
    """A prefill whose pick is not finite fails its request where the
    pick is read: no token is delivered, the two steps that went out
    with the row discard theirs, and the other requests get the settled
    engine's ids."""
    want = settled(family)[0]
    eng = _engine(family)
    real, n = eng._prefill, [0]

    def prefill(*a):
        out = real(*a)
        n[0] += 1
        if n[0] != 3:
            return out
        return (out[0], np.zeros(1, bool)) + tuple(out[2:])

    eng._prefill = prefill
    before = _serving()
    reqs = _drive(eng, lookahead=True)
    failures, discarded = _grew(before, "nonfinite_failures",
                                "lookahead_discarded_tokens")
    eng.close()
    assert reqs[2].error is not None and "non-finite" in reqs[2].error
    assert reqs[2].generated == [] and reqs[2].ttft_s is None
    assert [r.error for i, r in enumerate(reqs) if i != 2] == [None] * 4
    assert [list(r.generated) for i, r in enumerate(reqs) if i != 2] \
        == [w for i, w in enumerate(want) if i != 2]
    assert (failures, discarded) == (1, 2)
    assert eng.cache.n_blocks_in_use == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_resumed_request_joins_at_its_dispatch(family):
    """A preempted request's re-prefill needs no pick: the step after it
    goes out with the row before anything is read, and the answers are
    an engine's that never preempts."""
    prompts, max_new = ([1, 2, 3, 4, 5, 6], [6, 5, 4, 3, 2, 1]), (12, 12)
    eng = _engine(family)
    want = [list(r.generated) for r in _drive(eng, False, prompts, max_new)]
    eng.close()
    eng = _engine(family, n_blocks=3)
    events = []
    _logging(eng, events)
    before = _serving()
    reqs = _drive(eng, True, prompts, max_new)
    deferred, prefills = _grew(before, "prefills_deferred", "prefill_count")
    eng.close()
    resumes = sum(r.preemptions for r in reqs)
    assert resumes >= 1 and prefills == 2 + resumes
    assert [list(r.generated) for r in reqs] == want
    # every prefill, the resumes too, is followed by a dispatch
    at = [i for i, e in enumerate(events) if e == "prefill"]
    assert all(events[i + 1] in ("prefill", "decode") for i in at)
    assert deferred == prefills


@pytest.mark.parametrize("family", ["mha", "mla"])
def test_a_speculative_window_reads_each_pick_at_once(family, settled,
                                                      monkeypatch):
    """Under a verify window nothing stays in flight, the prefills'
    picks included: each is read inside its own prefill."""
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "2")
    eng = _engine(family)
    events = []
    _logging(eng, events)
    before = _serving()
    reqs = _drive(eng, lookahead=True)
    (deferred,) = _grew(before, "prefills_deferred")
    eng.close()
    assert [list(r.generated) for r in reqs] == settled(family)[0]
    at = [i for i, e in enumerate(events) if e == "prefill"]
    assert len(at) == len(PROMPTS)
    assert all(events[i + 1] == "read" for i in at)
    assert deferred == 0
