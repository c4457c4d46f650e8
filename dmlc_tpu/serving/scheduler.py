"""Continuous-batching scheduler: iteration-level admit / evict.

Orca-style scheduling over the paged cache (serving.kv_cache): the unit
of scheduling is one engine *iteration*, not one request.  Every
iteration the engine (a) admits at most one waiting request whose
context fits the free list — its prefill runs this iteration and it
joins the decode batch the next — and (b) decodes every active request
one token.  Requests therefore enter and leave the batch mid-flight;
a long generation never convoys short ones behind it.

Memory pressure is resolved by *preemption with recompute* (the vLLM
trade): when a decode step cannot extend some sequence's cache, an
active request is evicted — its blocks return to the free list and the
request re-enters the FRONT of the wait queue carrying the tokens it
already generated, so its eventual re-prefill recomputes
prompt+generated in one pass and generation resumes where it stopped.
The victim is the LOWEST-priority active request, youngest within the
class: priority encodes who pays for KV pressure (a background batch
request is recomputed before an interactive one is ever touched), and
youngest-within-class minimizes wasted recompute and cannot starve —
the oldest request of the highest class only ever gains blocks.
Where sequences also carry recurrent state (a slot of the cache
manager beside their blocks), eviction frees the slot with the blocks
and the re-prefill rebuilds the state from the context the same way;
admission (``cache.can_reserve``) then needs a free slot as well.

Priority also orders admission: ``next_prefill`` serves the
highest-priority waiting request first (FIFO within a class, preserved
by the same ``(-priority, seq)`` max-heap idiom as
``concurrency.ConcurrentBlockingQueue(priority=True)``), so a spike of
background work cannot queue ahead of interactive traffic.  With every
request at the default priority both policies reduce exactly to the
original FIFO / youngest-first behavior — output parity is a
regression-tested invariant.

The scheduler is pure policy + bookkeeping (no jax): the engine owns
the compute.  All methods are lock-protected; the engine's single step
thread is the only caller of the mutating paths, but /healthz and the
admission path read concurrently.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .. import telemetry
from ..base import DMLCError
from .kv_cache import PagedKVCache
from ..concurrency import make_lock

__all__ = ["AlreadyFinished", "Request", "ContinuousBatchScheduler",
           "WAITING", "ACTIVE", "DONE", "FAILED",
           "PRIORITY_CLASSES", "coerce_priority"]

#: named priority classes accepted anywhere a numeric priority is
#: (``/generate`` bodies, loadgen tenant specs); higher = evicted later
PRIORITY_CLASSES = {"batch": 0, "standard": 1, "interactive": 2}


def coerce_priority(value, levels: int, default: int) -> int:
    """Validate a client-supplied priority class: None → ``default``,
    a name from :data:`PRIORITY_CLASSES` or an integer in
    ``[0, levels)`` → its numeric level.  Raises ``ValueError`` (the
    HTTP edge's 400) on anything else — an unvalidated priority would
    let one bad client outrank the whole fleet."""
    if value is None:
        return int(default)
    if isinstance(value, str):
        if value in PRIORITY_CLASSES:
            value = PRIORITY_CLASSES[value]
        else:
            raise ValueError(
                f"priority must be one of {sorted(PRIORITY_CLASSES)} "
                f"or an int in [0, {levels})")
    if not isinstance(value, int) or isinstance(value, bool):
        raise ValueError("priority must be an int or a named class")
    if not 0 <= value < levels:
        raise ValueError(f"priority {value} out of range [0, {levels})")
    return value

class AlreadyFinished(DMLCError):
    """Raised by :meth:`ContinuousBatchScheduler.finish` when the
    request already reached a terminal state — the exactly-once
    transition's race signal.  A dedicated type so sweep paths that
    legitimately race a terminal transition (engine shutdown/crash
    cleanup) can swallow exactly this and nothing broader: a generic
    ``except DMLCError`` there would also eat cache double-free
    errors or :class:`serving.engine.EngineDraining`."""


WAITING = "waiting"
ACTIVE = "active"
DONE = "done"
FAILED = "failed"

_req_ids = itertools.count(1)


class Request:
    """One generation request's lifetime record.

    ``generated`` persists across preemptions (the output so far is
    never discarded — only its cached K/V is, and the re-prefill
    recomputes that from ``context_ids()``).  ``wait()`` is the client
    blocking primitive; the engine signals completion exactly once.
    """

    def __init__(self, prompt_ids: List[int], max_new_tokens: int,
                 eos_id: Optional[int] = None, priority: int = 1,
                 tenant: str = "default"):
        if not prompt_ids:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        self.id = next(_req_ids)
        self.prompt_ids = [int(t) for t in prompt_ids]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.priority = int(priority)
        self.tenant = str(tenant)
        self.submit_t = time.monotonic()
        # state/error/finish_t transition under the owning scheduler's
        # lock (a cross-object guard the race pass cannot see); the
        # terminal transition publishes them before the _done Event is
        # set, and readers (result(), duplicate waiters) wait() first
        # dmlc-check: unguarded(scheduler-lock guarded; terminal write fenced by _done)
        self.state = WAITING
        self.generated: List[int] = []
        self.ttft_s: Optional[float] = None
        # dmlc-check: unguarded(scheduler-lock guarded; terminal write fenced by _done)
        self.finish_t: Optional[float] = None
        # dmlc-check: unguarded(scheduler-lock guarded; terminal write fenced by _done)
        self.error: Optional[str] = None
        self.preemptions = 0
        self.crash_requeues = 0  # engine-iteration crashes survived
        self.slot = None  # admission token (engine's BufferPool buffer)
        self.client_id: Optional[str] = None  # idempotency key, if any
        self.trace_id: Optional[str] = None  # fleet trace (X-DMLC-Trace)
        self._done = threading.Event()

    # ---- views ----------------------------------------------------------
    @property
    def n_prompt(self) -> int:
        return len(self.prompt_ids)

    @property
    def n_generated(self) -> int:
        return len(self.generated)

    def context_ids(self) -> List[int]:
        """Tokens a (re-)prefill must consume: prompt plus everything
        generated before a preemption, minus the last generated token —
        that one has not been consumed yet (it is the next decode
        input), so caching its K/V would double-count it."""
        if self.generated:
            return self.prompt_ids + self.generated[:-1]
        return list(self.prompt_ids)

    @property
    def latency_s(self) -> Optional[float]:
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t

    @property
    def decode_tokens_per_s(self) -> Optional[float]:
        """Per-user decode throughput: generated tokens over the time
        AFTER the first token (the steady-state rate a streaming user
        experiences; None until finished or when only one token)."""
        if self.finish_t is None or self.ttft_s is None:
            return None
        decode_s = (self.finish_t - self.submit_t) - self.ttft_s
        if self.n_generated <= 1 or decode_s <= 0:
            return None
        return (self.n_generated - 1) / decode_s

    def is_finished_by(self, token: int) -> bool:
        return (self.n_generated >= self.max_new_tokens
                or (self.eos_id is not None and token == self.eos_id))

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request completes (True) or times out."""
        return self._done.wait(timeout)

    def reject(self, error: str) -> None:
        """Terminal transition for a request that was never enqueued
        (its admission failed AFTER a dedupe claim published it): mark
        FAILED and wake any duplicate waiters, without touching
        scheduler or cache state — there is none to release."""
        self.state = FAILED
        self.error = error
        self.finish_t = time.monotonic()
        self._done.set()

    def result(self) -> Dict:
        """JSON-able completion document (the server's response body)."""
        out = {
            "id": self.id,
            "state": self.state,
            "error": self.error,
            "n_prompt": self.n_prompt,
            "n_generated": self.n_generated,
            "output_ids": list(self.generated),
            "ttft_s": self.ttft_s,
            "latency_s": self.latency_s,
            "decode_tokens_per_s": self.decode_tokens_per_s,
            "preemptions": self.preemptions,
            "priority": self.priority,
            "tenant": self.tenant,
        }
        if self.client_id is not None:
            out["request_id"] = self.client_id
        if self.trace_id is not None:
            out["trace_id"] = self.trace_id
        return out


class ContinuousBatchScheduler:
    """Admission queue + active set over a shared :class:`PagedKVCache`."""

    def __init__(self, cache: PagedKVCache, max_active: int = 8):
        if max_active < 1:
            raise ValueError(f"max_active must be >= 1, got {max_active}")
        self.cache = cache
        self.max_active = int(max_active)
        self._waiting: deque = deque()
        self._active: List[Request] = []
        # out of the batch with their last decode step dispatched and
        # not yet read (:meth:`retire`); still running to every view
        # but the batch's
        self._retiring: List[Request] = []
        self._lock = make_lock("ContinuousBatchScheduler._lock")

    # ---- queue views ----------------------------------------------------
    @property
    def n_waiting(self) -> int:
        with self._lock:
            return len(self._waiting)

    @property
    def n_active(self) -> int:
        with self._lock:
            return len(self._active) + len(self._retiring)

    def active_requests(self) -> List[Request]:
        """The decode batch: what the next step runs."""
        with self._lock:
            return list(self._active)

    def running_requests(self) -> List[Request]:
        """The decode batch and the retiring requests: everything that
        is neither waiting nor finished (the crash requeue's set)."""
        with self._lock:
            return self._active + self._retiring

    def counts(self) -> tuple:
        """``(n_active, n_waiting)`` under ONE lock hold: composed
        views (``/healthz``, the router's load signal) get a consistent
        pair instead of two reads an iteration can interleave.  A
        retiring request counts as active until it is finished."""
        with self._lock:
            return (len(self._active) + len(self._retiring),
                    len(self._waiting))

    # ---- admission ------------------------------------------------------
    def enqueue(self, req: Request) -> None:
        with self._lock:
            req.state = WAITING
            self._waiting.append(req)
            telemetry.set_gauge("serving", "queue_depth",
                                len(self._waiting))

    def next_prefill(self) -> Optional[Request]:
        """Pop the next admissible request: there is an active slot and
        the free list covers its context plus one decode slot (the
        iteration-level admission test — checked against the cache NOW,
        so a freed block is reusable on the very next iteration).

        Selection is highest-priority-first, FIFO within a class
        (``max`` returns the FIRST maximal element, i.e. the class's
        front-most queue entry — so a preempted request, re-queued at
        the front, still resumes before fresh peers of its class).
        The head-of-line-blocking contract is per-POLICY, not
        per-deque: when the selected request does not fit, nothing is
        admitted this iteration — skipping past it to a smaller,
        lower-priority request would starve exactly the request the
        priority says to serve first."""
        with self._lock:
            if len(self._active) >= self.max_active or not self._waiting:
                return None
            req = max(self._waiting, key=lambda r: r.priority)
            if not self.cache.can_reserve(len(req.context_ids()) + 1):
                return None
            self._waiting.remove(req)
            telemetry.set_gauge("serving", "queue_depth",
                                len(self._waiting))
            return req

    def requeue_front(self, req: Request) -> None:
        """Put a popped-but-not-started request back at the head (the
        admission check raced a same-iteration cache change)."""
        with self._lock:
            req.state = WAITING
            self._waiting.appendleft(req)
            telemetry.set_gauge("serving", "queue_depth",
                                len(self._waiting))

    def all_pending(self) -> List[Request]:
        """Every request not yet in a terminal state (shutdown sweep)."""
        with self._lock:
            return self._active + self._retiring + list(self._waiting)

    def activate(self, req: Request) -> None:
        with self._lock:
            req.state = ACTIVE
            self._active.append(req)
            telemetry.set_gauge("serving", "active_requests",
                                len(self._active))

    def retire(self, req: Request) -> None:
        """Take an active request out of the batch before it is
        finished: the engine has dispatched the step that generates its
        last token (it ends by count, which needs no token to know) and
        reads that step only after the next one is on the device.  Its
        blocks and state slot go back now, so the next prefill gets the
        place in the batch no later than it would from a step that was
        read at once; every program dispatched from here on runs after
        that step on the device, so none can see the blocks while the
        step still writes them.  The request stays ACTIVE, counted by
        :meth:`counts` and swept by :meth:`all_pending`, until
        :meth:`finish`."""
        with self._lock:
            self._active.remove(req)
            self._retiring.append(req)
            telemetry.set_gauge("serving", "active_requests",
                                len(self._active))
        self.cache.free(req.id)

    def requeue_active(self, req: Request) -> bool:
        """Crash requeue: pull a SPECIFIC active (or retiring) request
        back to the front of the wait queue (its cache state after a crashed
        iteration is unknowable, so its blocks are freed and the
        re-prefill recomputes from ``context_ids()`` — identical
        recompute-resume mechanics to preemption, but counted on the
        request's ``crash_requeues`` budget instead of preemptions).
        Returns False when the request is not active (it finished or
        was swept concurrently)."""
        with self._lock:
            held = next((q for q in (self._active, self._retiring)
                         if req in q), None)
            if held is None:
                return False
            held.remove(req)
            req.state = WAITING
            req.crash_requeues += 1
            self._waiting.appendleft(req)
            telemetry.set_gauge("serving", "active_requests",
                                len(self._active))
            telemetry.set_gauge("serving", "queue_depth",
                                len(self._waiting))
        self.cache.free(req.id)
        return True

    # ---- eviction -------------------------------------------------------
    def preempt_youngest(self) -> Optional[Request]:
        """Evict the lowest-priority active request — youngest within
        the class — (free its blocks, requeue it at the FRONT of the
        wait queue for prompt resumption).  Returns it, or None when
        nothing is active to evict.  A higher-priority request is NEVER
        evicted while any lower-priority one holds blocks; with uniform
        priorities this is exactly the original youngest-first policy
        (and the name keeps that lineage)."""
        with self._lock:
            if not self._active:
                return None
            req = max(self._active,
                      key=lambda r: (-r.priority, r.submit_t, r.id))
            self._active.remove(req)
            req.state = WAITING
            req.preemptions += 1
            self._waiting.appendleft(req)
            telemetry.set_gauge("serving", "active_requests",
                                len(self._active))
            telemetry.set_gauge("serving", "queue_depth",
                                len(self._waiting))
        self.cache.free(req.id)
        telemetry.inc("serving", "preemptions")
        return req

    # ---- completion -----------------------------------------------------
    def finish(self, req: Request, error: Optional[str] = None) -> None:
        """Terminal transition (exactly once per request): release the
        request's cache blocks, mark DONE/FAILED, and wake waiters."""
        with self._lock:
            if req.state in (DONE, FAILED):
                raise AlreadyFinished(f"request {req.id} finished twice")
            for held in (self._active, self._retiring, self._waiting):
                if req in held:
                    held.remove(req)
                    break
            req.state = FAILED if error else DONE
            req.error = error
            req.finish_t = time.monotonic()
            telemetry.set_gauge("serving", "active_requests",
                                len(self._active))
            telemetry.set_gauge("serving", "queue_depth",
                                len(self._waiting))
        self.cache.free(req.id)
        if error:
            telemetry.inc("serving", "failed")
        else:
            telemetry.inc("serving", "completed")
        req._done.set()
