"""Bucketed-overlap gradient reduction (parallel/overlap.py): future
exception transport, bit-parity of the bucketed path against the
synchronous collective, elastic mid-bucket shrink safety, and the step
ledger's exposed-vs-overlapped collective split (ISSUE 9)."""

import threading
import time

import numpy as np
import pytest

from dmlc_tpu import telemetry
from dmlc_tpu.parallel.overlap import (
    CollectiveFuture,
    GradientBucketer,
    bucket_bytes,
    reverse_topological,
)
from dmlc_tpu.tracker import RabitTracker, TrackerClient, WorldResized


# ---------------------------------------------------------------------------
# CollectiveFuture: the defined exception path off the worker thread
# ---------------------------------------------------------------------------

def test_future_result_and_exception_transport():
    fut = CollectiveFuture()
    assert not fut.done()
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    fut.set_result(41)
    assert fut.done() and fut.result() == 41 and fut.exception() is None

    fut = CollectiveFuture()
    err = WorldResized("shrunk", gen=3)

    def worker():
        time.sleep(0.02)
        fut.set_exception(err)

    threading.Thread(target=worker, daemon=True).start()
    with pytest.raises(WorldResized) as ei:
        fut.result(timeout=5)
    assert ei.value is err and ei.value.gen == 3
    assert fut.exception() is err


def test_bucket_bytes_knob(monkeypatch):
    monkeypatch.setenv("DMLC_COLL_BUCKET_MB", "2")
    assert bucket_bytes() == 2 << 20
    monkeypatch.setenv("DMLC_COLL_BUCKET_MB", "0.25")
    assert bucket_bytes() == 1 << 18
    assert reverse_topological(4) == [3, 2, 1, 0]


# ---------------------------------------------------------------------------
# GradientBucketer against a local "collective" (no sockets): packing /
# unpacking round-trip, all-or-nothing failure, worker reuse
# ---------------------------------------------------------------------------

def test_bucketer_roundtrip_preserves_shapes_and_values():
    calls = []

    def fake_allreduce(buf):
        calls.append(buf.size)
        return buf * 2.0

    b = GradientBucketer(fake_allreduce, bucket_bytes_=4 * 4)  # 4 elems
    leaves = [np.arange(6, dtype=np.float32).reshape(2, 3),
              np.asarray(7.0, np.float32),  # 0-d leaf
              np.arange(5, dtype=np.float32)]
    out = b.reduce_leaves(leaves)
    assert [o.shape for o in out] == [(2, 3), (), (5,)]
    for o, leaf in zip(out, leaves):
        np.testing.assert_array_equal(o, np.asarray(leaf) * 2.0)
    # 12 elems / 4-elem buckets = 3 buckets, every bucket full
    assert calls == [4, 4, 4]
    b.close()


def test_bucketer_failure_is_all_or_nothing_and_reusable():
    boom = [True]

    def flaky(buf):
        if boom[0] and buf[0] >= 4:  # second bucket fails
            raise WorldResized("mid-bucket shrink", gen=1)
        return buf + 1.0

    b = GradientBucketer(flaky, bucket_bytes_=4 * 4)
    leaves = [np.arange(12, dtype=np.float32)]
    snapshot = leaves[0].copy()
    with pytest.raises(WorldResized):
        b.reduce_leaves(leaves)
    # inputs untouched, worker drained and immediately reusable
    np.testing.assert_array_equal(leaves[0], snapshot)
    boom[0] = False
    out = b.reduce_leaves(leaves)
    np.testing.assert_array_equal(out[0], snapshot + 1.0)
    b.close()


# ---------------------------------------------------------------------------
# Bit-parity against the synchronous collective through a REAL tracker
# ---------------------------------------------------------------------------

def _run_workers(n, fn, elastic=False):
    tracker = RabitTracker("127.0.0.1", n)
    tracker.start(n)
    results = [None] * n
    errors = []

    def work(i):
        try:
            c = TrackerClient("127.0.0.1", tracker.port, jobid=f"ov{i}")
            c.start()
            results[i] = fn(c)
            c.shutdown()
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append((i, e))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors
    tracker.join(timeout=30)
    tracker.close()
    return results


@pytest.mark.parametrize("n", [2, 3, 5])
@pytest.mark.parametrize("bucket_elems", [7, 64, 4096])
def test_bucketed_matches_sync_bitwise(n, bucket_elems):
    """Bucketed-overlapped allreduce must be bit-identical to the
    synchronous path for sum/max/min across odd worlds, world=2, and
    bucket sizes smaller than one gradient leaf (7 f32 elems = 28
    bytes against 100-elem leaves)."""

    def fn(c):
        rng = np.random.default_rng(c.rank)
        # integer-valued floats: exactly representable, so even a
        # reduction order change could not hide behind fp noise
        leaves = [rng.integers(-1000, 1000, (4, 25)).astype(np.float32),
                  rng.integers(-1000, 1000, 33).astype(np.float32),
                  rng.integers(-1000, 1000, (2, 2, 2)).astype(np.float32)]
        flat = np.concatenate([lf.reshape(-1) for lf in leaves])
        out = {}
        for op in ("sum", "max", "min"):
            sync = c.allreduce(flat, op)
            b = GradientBucketer(lambda a, op=op: c.allreduce(a, op),
                                 bucket_bytes_=bucket_elems * 4)
            red = b.reduce_leaves(leaves)
            b.close()
            out[op] = (sync, np.concatenate([r.reshape(-1) for r in red]))
        return out

    for res in _run_workers(n, fn):
        for op, (sync, bucketed) in res.items():
            np.testing.assert_array_equal(sync, bucketed, err_msg=op)


def test_reduce_tree_restores_structure():
    """reduce_tree packs reverse-topologically but returns the reduced
    pytree in the ORIGINAL structure with matching shapes."""
    jax = pytest.importorskip("jax")

    order_seen = []

    def fake_allreduce(buf):
        order_seen.append(buf.copy())
        return buf

    tree = {"a": np.full((2, 2), 1.0, np.float32),
            "b": [np.full(3, 2.0, np.float32),
                  np.full(1, 3.0, np.float32)]}
    b = GradientBucketer(fake_allreduce, bucket_bytes_=1 << 20)
    out = b.reduce_tree(tree)
    b.close()
    assert jax.tree_util.tree_structure(out) == \
        jax.tree_util.tree_structure(tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"][0], tree["b"][0])
    np.testing.assert_array_equal(out["b"][1], tree["b"][1])
    # one bucket, filled in reverse flatten order: b[1], b[0], then a
    np.testing.assert_array_equal(
        order_seen[0], np.asarray([3, 2, 2, 2, 1, 1, 1, 1], np.float32))


# ---------------------------------------------------------------------------
# Elastic interplay: a WorldResized on the collective thread transports
# to the caller; a mid-bucket shrink neither hangs nor corrupts inputs
# ---------------------------------------------------------------------------

MISS = 0.5
GRACE = 0.5


def test_mid_bucket_world_shrink_propagates_and_recovers():
    tracker = RabitTracker("127.0.0.1", 3, miss_window_s=MISS,
                           elastic=True, elastic_grace_s=GRACE)
    tracker.start(3)
    barrier = threading.Barrier(3)
    results = {}
    errors = []

    class Worker(threading.Thread):
        def __init__(self, i):
            super().__init__(daemon=True)
            self.i = i
            self._halt = threading.Event()

        def _beats(self, c):
            while not self._halt.wait(0.1):
                try:
                    c.send_metrics('{"counters": {}}')
                except OSError:
                    return

        def run(self):
            try:
                c = TrackerClient("127.0.0.1", tracker.port,
                                  jobid=f"sh{self.i}").start()
                threading.Thread(target=self._beats, args=(c,),
                                 daemon=True).start()
                try:
                    results[self.i] = self.fn(c)
                finally:
                    self._halt.set()
            except BaseException as e:  # noqa: BLE001
                errors.append((self.i, e))

        def fn(self, c):
            leaves = [np.full(100, float(c.rank + 1), np.float32)
                      for _ in range(4)]
            snapshot = [lf.copy() for lf in leaves]
            b = GradientBucketer(c.allreduce_sum, bucket_bytes_=100 * 4)
            first = b.reduce_leaves(leaves)
            np.testing.assert_array_equal(first[0],
                                          np.full(100, 6.0, np.float32))
            barrier.wait(timeout=20)
            if c.rank == 2:
                c._links_down()  # vanish mid-job, no handshake
                b.close()
                return ("died",)
            # keep reducing until the shrink lands; the exception MUST
            # surface at the join (no hang) and leave inputs untouched
            deadline = time.monotonic() + 30
            while True:
                assert time.monotonic() < deadline, \
                    "mid-bucket shrink never surfaced"
                try:
                    b.reduce_leaves(leaves)
                    time.sleep(0.05)
                except WorldResized:
                    break
            for lf, snap in zip(leaves, snapshot):
                np.testing.assert_array_equal(lf, snap)
            c.resize()
            assert c.world_size == 2
            # the bucketer (and its worker thread) survives the resize
            post = b.reduce_leaves(leaves)
            np.testing.assert_array_equal(
                post[0], np.full(100, 3.0, np.float32))
            b.close()
            out = ("survived", c.rank)
            c.shutdown()
            return out

    workers = [Worker(i) for i in range(3)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(90)
    assert not errors, errors
    tracker.join(timeout=30)
    tracker.close()
    assert sorted(len(r) for r in results.values()) == [1, 2, 2]


# ---------------------------------------------------------------------------
# Step ledger: exposed vs overlapped collective split
# ---------------------------------------------------------------------------

def test_ledger_splits_exposed_vs_overlapped():
    telemetry.reset()
    telemetry.reset_steps()
    led = telemetry.ledger()

    # events, not two sleeps racing: a loaded host oversleeps by more
    # than the 10 ms the race left
    started, joining = threading.Event(), threading.Event()

    def background_collective():
        with telemetry.core.span("collective.allreduce",
                                 stage="collective"):
            started.set()
            joining.wait(5)
            time.sleep(0.02)

    led.step_begin()
    th = threading.Thread(target=background_collective)
    th.start()
    started.wait(5)
    time.sleep(0.04)  # stepping thread computes: the worker's span hides
    with telemetry.core.span("collective.join", stage="collective"):
        joining.set()
        th.join()  # the remainder is paid here, exposed
    rec = led.step_end(tokens=10)
    # worker time under the stepping thread's compute is overlapped;
    # the join span (and the worker time underneath it) is exposed
    assert rec["collective_overlapped_s"] >= 0.02
    assert rec["collective_s"] >= 0.005
    summary = led.summary()
    assert summary["collective_overlapped_fraction"] > 0
    assert summary["collective_exposed_fraction"] > 0


def test_ledger_overlap_clipped_to_step_window():
    """A background collective span that started BEFORE the step only
    contributes the part inside the step window."""
    telemetry.reset()
    telemetry.reset_steps()
    led = telemetry.ledger()
    started = threading.Event()

    def long_collective():
        with telemetry.core.span("collective.allreduce",
                                 stage="collective"):
            started.set()
            time.sleep(0.1)

    th = threading.Thread(target=long_collective)
    th.start()
    started.wait(5)
    time.sleep(0.06)  # >half the span burns before the step opens
    led.step_begin()
    th.join()
    rec = led.step_end()
    assert 0 < rec["collective_overlapped_s"] < 0.06


class _SlowLeaf:
    """Array-like whose materialization sleeps — mimics the per-leaf
    device->host fetch the bucketer overlaps collectives under."""

    def __init__(self, a):
        self._a = a

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.01)
        return self._a if dtype is None else self._a.astype(dtype)


def test_bucketer_drives_ledger_overlap_metrics():
    """End-to-end: a GradientBucketer reduction whose packing genuinely
    runs while earlier buckets reduce produces a nonzero overlapped
    share and the per-bucket counters."""
    telemetry.reset()
    telemetry.reset_steps()

    def slow_allreduce(buf):
        time.sleep(0.02)
        return buf.copy()

    b = GradientBucketer(slow_allreduce, bucket_bytes_=64)
    led = telemetry.ledger()
    led.step_begin()
    b.reduce_leaves([_SlowLeaf(np.zeros(16, np.float32))
                     for _ in range(4)])
    rec = led.step_end()
    b.close()
    assert rec["collective_overlapped_s"] > 0
    snap = telemetry.snapshot()
    assert snap["counters"]["collective"]["overlap_buckets"] >= 4
    timings = b.last_timings()
    assert len(timings) == 4 and all(s > 0 for _, s in timings)


def test_ledger_join_blocked_worker_time_is_not_overlapped():
    """A degenerate 'overlap' where the stepping thread immediately
    blocks in the join hides nothing: worker collective time spent
    while the stepping thread sits in a collective span of its own must
    count as EXPOSED, or a total loss of overlap would still report an
    overlapped share (and the perf-smoke overlap gate would pass
    vacuously)."""
    telemetry.reset()
    telemetry.reset_steps()
    led = telemetry.ledger()
    b = GradientBucketer(lambda a: (time.sleep(0.05), a)[1],
                         bucket_bytes_=1 << 20)
    led.step_begin()
    b.reduce_leaves([np.ones(8, np.float32)])  # packing is instant
    rec = led.step_end()
    b.close()
    assert rec["collective_s"] >= 0.04
    assert rec["collective_overlapped_s"] < 0.01


def test_bucketer_zero_size_leaves_roundtrip():
    """Zero-size leaves (an unused parameter's empty gradient) pack and
    unpack cleanly instead of tripping np.concatenate([])."""
    b = GradientBucketer(lambda a: a, bucket_bytes_=64)
    r = b.reduce_leaves([np.ones(3, np.float32),
                         np.zeros((0,), np.float32),
                         np.zeros((0, 3), np.float32),
                         np.full(2, 7.0, np.float32)])
    b.close()
    assert [x.shape for x in r] == [(3,), (0,), (0, 3), (2,)]
    assert np.array_equal(r[0], np.ones(3, np.float32))
    assert np.array_equal(r[3], np.full(2, 7.0, np.float32))
