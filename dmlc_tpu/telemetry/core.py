"""Telemetry core: thread-safe counters, gauges, histograms, and spans.

Successor of the flat ``dmlc_tpu.metrics`` counters (which remains as a
thin shim over this module).  The reference substrate's only visibility
was ad-hoc "X MB/sec" prints (basic_row_iter.h:68-75); pod-scale runs
need *distributions* (which rank is the straggler, what does the stall
tail look like), so every ``timed`` block now feeds a fixed-bucket
histogram with p50/p90/p99 summaries in addition to the flat
``<name>_secs`` counter the old call sites read.

Four primitives, all process-global and thread-safe:

  * ``inc(stage, name, v)``        monotonic counters (dict add under a lock)
  * ``set_gauge(stage, name, v)``  last-write-wins gauges
  * ``observe(stage, name, v)``    fixed-bucket histograms (p50/p90/p99)
  * ``span(name, stage=...)``      THE way to time a block: a nested,
                                   thread-aware span in a bounded ring
                                   buffer (Chrome-trace exportable; see
                                   telemetry.exporters), a
                                   ``jax.profiler.TraceAnnotation`` of
                                   the same name (so the block is a
                                   host event on the device trace's
                                   clock), and the counter pair
                                   ``<suffix>_secs`` / ``<suffix>_count``

``timed`` records the counter and the histogram under ``<name>_secs``
only (no span); ``annotate(name)`` is ``span(name, stage="annotate")``.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import math
import sys
import threading
import time
from bisect import bisect_left
from collections import defaultdict, deque
from typing import Dict, List, Optional

from ..base import get_env
from ..concurrency import make_lock

__all__ = [
    "Histogram",
    "DEFAULT_BOUNDS",
    "inc",
    "set_gauge",
    "observe",
    "observe_duration",
    "timed",
    "record_span",
    "span",
    "spans",
    "spans_since",
    "open_spans",
    "current_span",
    "anchor_epoch",
    "annotate",
    "snapshot",
    "counters_snapshot",
    "reset",
]

# geometric bounds 1 µs .. ~134 s (doubling): one bucket set serves both
# microsecond-scale parse latencies and multi-second checkpoint saves
DEFAULT_BOUNDS = tuple(1e-6 * 2.0 ** i for i in range(28))

# spans ring capacity; bounded so a week-long run cannot OOM the host
_MAX_SPANS = get_env("DMLC_TELEMETRY_MAX_SPANS", 8192)


class Histogram:
    """Fixed-bucket histogram with percentile summaries.

    Bucket ``i`` covers ``(bounds[i-1], bounds[i]]``; the final bucket is
    the ``+Inf`` overflow — the same cumulative ``le`` semantics as a
    Prometheus histogram, so export is a direct rendering.  Percentiles
    interpolate linearly inside the bucket and clamp to the observed
    min/max, which keeps p50 exact-ish even with coarse buckets.
    Mutation is NOT internally locked: callers go through the
    module-level functions, which hold the registry lock.
    """

    __slots__ = ("bounds", "counts", "total", "count", "vmin", "vmax")

    def __init__(self, bounds=None):
        self.bounds = tuple(bounds) if bounds is not None else DEFAULT_BOUNDS
        self.counts = [0] * (len(self.bounds) + 1)
        self.total = 0.0
        self.count = 0
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, value: float) -> None:
        v = float(value)
        self.counts[bisect_left(self.bounds, v)] += 1
        self.total += v
        self.count += 1
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def percentile(self, q: float) -> Optional[float]:
        """q-th percentile (0-100) estimated from bucket counts."""
        if self.count == 0:
            return None
        rank = q / 100.0 * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if c and cum >= rank:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) else self.vmax
                frac = (rank - (cum - c)) / c
                val = lo + frac * (hi - lo)
                return min(max(val, self.vmin), self.vmax)
        return self.vmax

    def summary(self, include_buckets: bool = True) -> Dict:
        out = {
            "count": self.count,
            "sum": self.total,
            "min": self.vmin if self.count else None,
            "max": self.vmax if self.count else None,
            "p50": self.percentile(50),
            "p90": self.percentile(90),
            "p99": self.percentile(99),
        }
        if include_buckets:
            out["bounds"] = list(self.bounds)
            out["buckets"] = list(self.counts)
        return out

    @classmethod
    def from_dict(cls, d: Dict) -> "Histogram":
        """Rebuild from a ``summary(include_buckets=True)`` dict (the
        heartbeat wire format), so aggregation can merge bucket counts.
        Every field is coerced eagerly: garbage raises TypeError /
        ValueError HERE, where wire-facing callers catch it, instead of
        being stored and crashing a later summary()/merge()."""
        bounds = d.get("bounds")
        if bounds is not None:
            bounds = tuple(float(b) for b in bounds)
        h = cls(bounds)
        buckets = d.get("buckets")
        if buckets is not None and len(buckets) == len(h.counts):
            h.counts = [int(c) for c in buckets]
        h.count = int(d.get("count", 0))
        h.total = float(d.get("sum", 0.0))
        h.vmin = float(d["min"]) if d.get("min") is not None else math.inf
        h.vmax = float(d["max"]) if d.get("max") is not None else -math.inf
        return h

    def merge(self, other: "Histogram") -> None:
        """Accumulate ``other`` into self (cluster-wide aggregation).
        Bucket counts merge only for identical bounds; count/sum/min/max
        always merge."""
        if other.bounds == self.bounds:
            for i, c in enumerate(other.counts):
                self.counts[i] += c
        self.count += other.count
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)


# ---------------------------------------------------------------------------
# process-global registry
# ---------------------------------------------------------------------------

_lock = make_lock("telemetry_core._lock")
_counters: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
_gauges: Dict[str, Dict[str, float]] = defaultdict(dict)
_hists: Dict[str, Dict[str, Histogram]] = defaultdict(dict)
_spans: deque = deque(maxlen=_MAX_SPANS)
_span_seq = 0  # monotone id per recorded span (incremental trace shipping)
_span_ids = itertools.count(1)  # id per OPENED span (next() is GIL-atomic)
_T0 = time.perf_counter()  # session-relative span clock (µs in exports)
# wall-clock moment of _T0: span ts + _T0_EPOCH places a span on this
# process's wall clock, which the tracker's per-rank clock offset then
# maps onto ONE cluster timeline (telemetry.clock / telemetry.flight)
_T0_EPOCH = time.time()
_tls = threading.local()
# tid -> (thread, open-span stack); lets the postmortem dumper see the
# spans every thread is INSIDE at crash time, not just finished ones
_open_stacks: Dict[int, tuple] = {}


def inc(stage: str, name: str, value: float = 1.0) -> None:
    """Add ``value`` to counter ``name`` of ``stage``."""
    with _lock:
        _counters[stage][name] += value


def set_gauge(stage: str, name: str, value: float) -> None:
    """Set gauge ``name`` of ``stage`` to ``value`` (last write wins)."""
    with _lock:
        _gauges[stage][name] = float(value)


def observe(stage: str, name: str, value: float, bounds=None) -> None:
    """Record ``value`` into the histogram ``name`` of ``stage``.  The
    first observation fixes the bucket bounds."""
    with _lock:
        h = _hists[stage].get(name)
        if h is None:
            h = _hists[stage][name] = Histogram(bounds)
        h.observe(value)


def _observe_duration_locked(stage: str, name: str, secs: float) -> None:
    key = name + "_secs"
    _counters[stage][key] += secs
    h = _hists[stage].get(key)
    if h is None:
        h = _hists[stage][key] = Histogram()
    h.observe(secs)


def observe_duration(stage: str, name: str, secs: float) -> None:
    """Duration convention: counter ``<name>_secs`` += secs (the flat
    total old call sites read) plus a histogram observation under the
    same key (the distribution new consumers read)."""
    with _lock:
        _observe_duration_locked(stage, name, secs)


@contextlib.contextmanager
def timed(stage: str, name: str):
    """Time a block into counter + histogram ``<name>_secs`` of ``stage``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        observe_duration(stage, name, time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def _span_stack() -> List[Dict]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
        th = threading.current_thread()
        with _lock:
            _open_stacks[th.ident] = (th, stack)
    return stack


_ANNOTATION = None  # jax.profiler.TraceAnnotation once resolved; False = none


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once JAX is loaded in this
    process (a TraceMe is inert without a profiler session, and a
    session needs JAX loaded: a host-only process pays no import)."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:  # pragma: no cover - jax present in tests
            _ANNOTATION = False
    return _ANNOTATION or None


def span_counter_suffix(name: str, stage: str) -> str:
    """A span's counter family: its name without the ``<stage>.``
    prefix (the whole name where it has none), dots to ``_``."""
    if name.startswith(stage + "."):
        name = name[len(stage) + 1:]
    return name.replace(".", "_")


@contextlib.contextmanager
def span(name: str, stage: str = "dmlc", args: Optional[Dict] = None):
    """The one way to time a block: a nested, thread-aware span.

    The record in the bounded ring carries ``id`` (assigned when the
    span opens), ``parent`` (the ``id`` of the span open around it on
    the same thread, None at the top), ``depth`` (enclosing count) and
    ``seq`` (close order: the ``spans_since`` cursor).  The block also
    runs inside a ``jax.profiler.TraceAnnotation(name)``, so it is a
    host event on the device trace's clock, and closing it adds
    ``<suffix>_secs`` (counter + histogram) and ``<suffix>_count`` to
    ``stage``'s counters: a ratio of counters gives time per unit
    where the work happens.  Yields its ``args`` dict (a copy of the
    caller's), so the block can add fields it learns inside."""
    global _span_seq
    stack = _span_stack()
    a = dict(args) if args else {}
    ann = _trace_annotation()
    rec = {"name": name, "cat": stage, "id": next(_span_ids),
           "parent": stack[-1]["id"] if stack else None,
           "depth": len(stack), "args": a}
    t0 = time.perf_counter()
    rec["ts"] = (t0 - _T0) * 1e6
    stack.append(rec)
    try:
        if ann is None:
            yield a
        else:
            with ann(name):
                yield a
    finally:
        t1 = time.perf_counter()
        # the top of the stack, unless closed out of order (the step
        # ledger abandoning a step): the spans opened after it stay
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is rec:
                del stack[i]
                break
        th = threading.current_thread()
        rec["dur"] = (t1 - t0) * 1e6
        rec["tid"] = th.ident
        rec["thread"] = th.name
        if not a:
            del rec["args"]
        suffix = span_counter_suffix(name, stage)
        with _lock:
            _span_seq += 1
            rec["seq"] = _span_seq
            _spans.append(rec)
            _observe_duration_locked(stage, suffix, t1 - t0)
            _counters[stage][suffix + "_count"] += 1


def current_span() -> Optional[Dict]:
    """The record of the innermost span open on this thread (None
    outside any).  Closing the span adds ``dur`` (µs) to it: a block
    that keeps the record reads its own span's length afterwards, and
    needs no second clock for a sum of its own."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


#: a named span under stage ``annotate`` (the pre-bridge name of the
#: profiler-visible span; every span is one now)
annotate = functools.partial(span, stage="annotate")


def record_span(name: str, stage: str = "dmlc", *, t0: float, t1: float,
                tid=None, thread: Optional[str] = None,
                args: Optional[Dict] = None) -> Dict:
    """Record an ALREADY-COMPLETED span into the ring.

    ``t0``/``t1`` are ``time.perf_counter()`` stamps (the span clock's
    timebase).  Unlike :func:`span`, the caller may assign a synthetic
    ``tid``/``thread`` — the request ledger (telemetry.requests) draws
    each request's lifecycle (queue → prefill → decode slices) on its
    own per-request row of the Chrome trace this way, and because the
    record lands in the ordinary ring it ships through the heartbeat
    ``trace`` path onto the tracker's merged ``/trace`` with no extra
    plumbing.  Synthetic spans do not touch the per-thread open-span
    stacks (they are closed by construction: ``parent`` is None), feed
    no counters and open no profiler annotation."""
    global _span_seq
    th = threading.current_thread()
    rec: Dict = {
        "name": name,
        "cat": stage,
        "id": next(_span_ids),
        "parent": None,
        "ts": (t0 - _T0) * 1e6,
        "dur": max(t1 - t0, 0.0) * 1e6,
        "tid": th.ident if tid is None else tid,
        "thread": th.name if thread is None else str(thread),
        "depth": 0,
    }
    if args:
        rec["args"] = dict(args)
    with _lock:
        _span_seq += 1
        rec["seq"] = _span_seq
        _spans.append(rec)
    return rec


def spans() -> List[Dict]:
    """Copy of the span ring, oldest first."""
    with _lock:
        return list(_spans)


def spans_since(after_seq: int, limit: Optional[int] = None) -> tuple:
    """(new_spans, last_seq): spans recorded after ``after_seq``, oldest
    first, capped at the OLDEST ``limit`` — a shipper that falls behind
    catches up over subsequent calls instead of losing the middle.  The
    incremental-shipping primitive behind HeartbeatSender's trace push:
    resume from the returned ``last_seq``.  When nothing was truncated,
    ``last_seq`` is the high-water mark INCLUDING ring-evicted spans,
    so a slow shipper skips the evicted gap (gone from the ring, not
    recoverable) rather than resending the whole ring forever; when
    ``limit`` truncated, it is the last RETURNED span's seq, so the
    still-retained remainder ships next call."""
    out = []
    with _lock:
        # seq grows along the ring: stop at the first span already seen
        for r in reversed(_spans):
            if r["seq"] <= after_seq:
                break
            out.append(r)
        last = _span_seq
    out.reverse()
    if limit is not None and len(out) > limit:
        out = out[:limit]
        last = out[-1]["seq"]
    return out, last


def span_seq() -> int:
    """Current span high-water mark (the seq of the newest recorded
    span, including ring-evicted ones).  A cheap cursor for interval
    consumers — the step ledger stamps it at ``step_begin`` and asks
    :func:`spans_since` for everything the step enclosed."""
    with _lock:
        return _span_seq


def now_ts() -> float:
    """Current time in the span timebase (µs since the process span
    epoch) — lets interval consumers clip span [ts, ts+dur] extents
    against their own window (the step ledger's overlapped-collective
    accounting)."""
    return (time.perf_counter() - _T0) * 1e6


def counter_value(stage: str, name: str, default: float = 0.0) -> float:
    """One counter's current value without copying the whole registry —
    the step ledger reads per-step deltas (bytes fed, flash FLOPs) on
    the hot path, where a full ``counters_snapshot()`` per step would
    be a dict-copy tax proportional to total metric count."""
    with _lock:
        vals = _counters.get(stage)
        return vals.get(name, default) if vals else default


def open_spans() -> List[Dict]:
    """Spans currently OPEN on any thread (innermost last per thread) —
    what every thread was doing right now; the postmortem dumper's view
    of a crashing process."""
    now_ts = (time.perf_counter() - _T0) * 1e6
    with _lock:
        stacks = [(th, list(stack)) for th, stack in _open_stacks.values()]
    out = []
    for th, stack in stacks:
        for depth, rec in enumerate(stack):
            if not isinstance(rec, dict):  # torn mid-append: skip
                continue
            out.append({
                "name": rec["name"], "cat": rec["cat"], "ts": rec["ts"],
                "id": rec["id"], "parent": rec["parent"],
                "open_us": now_ts - rec["ts"], "tid": th.ident,
                "thread": th.name, "depth": depth,
                # the block may still be adding fields: copy
                **({"args": dict(rec["args"])} if rec.get("args") else {}),
            })
    return out


def anchor_epoch() -> float:
    """Wall-clock time (time.time) corresponding to span ts == 0."""
    return _T0_EPOCH


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------

def counters_snapshot() -> Dict[str, Dict[str, float]]:
    """Flat stage → name → value counter copy (the legacy
    ``metrics.snapshot()`` shape)."""
    with _lock:
        return {stage: dict(vals) for stage, vals in _counters.items()}


def snapshot(include_buckets: bool = True) -> Dict:
    """Full structured snapshot: counters, gauges, and histogram
    summaries with p50/p90/p99 (plus raw buckets for merging unless
    ``include_buckets`` is False)."""
    with _lock:
        return {
            "counters": {s: dict(v) for s, v in _counters.items()},
            "gauges": {s: dict(v) for s, v in _gauges.items()},
            "histograms": {
                s: {n: h.summary(include_buckets) for n, h in hs.items()}
                for s, hs in _hists.items()
            },
        }


def reset() -> None:
    """Clear every counter, gauge, histogram, and recorded span
    (test isolation).  Open-span stacks of LIVE threads are left alone
    (they own their list objects mid-span); dead threads' are pruned."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _hists.clear()
        _spans.clear()
        for tid in [t for t, (th, _s) in _open_stacks.items()
                    if not th.is_alive() and th is not threading.main_thread()]:
            del _open_stacks[tid]
