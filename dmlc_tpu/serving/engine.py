"""Inference engine: the per-iteration prefill/decode loop.

The engine owns the compute half of serving: the jitted prefill and
decode programs of ``models.transformer`` (each ends in the greedy
pick, so a program returns token ids and never logits), the paged
cache's data plane, and the instrumentation
contract — every decode iteration is a **step** on the PR 5
:class:`telemetry.StepLedger` (``step_begin``/``step_end`` with the
batch's token count and the exact forward FLOPs given each sequence's
context length), so a serving process surfaces p50/p99 decode-step
time, goodput tokens/s, and decode MFU on ``/metrics`` and in
``dmlc top`` through the machinery training already built.

Admission backpressure is a ``concurrency.BufferPool`` of request
slots: ``submit`` must acquire one within ``admit_timeout_s`` or the
request is rejected (the HTTP layer maps that to 429) — the pool's
kill-wakes semantics double as clean shutdown for blocked submitters.

Request-scoped observability: every admitted request is tracked by a
:class:`telemetry.RequestLedger` (``engine.requests``) through submit →
queue wait → prefill → first token → per-token decode → preempt/resume
→ finish/fail-with-reason, so server-side TTFT decomposes exactly into
``queue_s + prefill_s`` and TBT p50/p99 is measurable; each request
draws its own row on the Chrome ``/trace``.  The decode loop also
records a per-iteration batch/KV-pressure record (the fleet router's
load signal) and streams TTFT/TBT/outcomes into the
:class:`telemetry.SLOMonitor` (``engine.slo``, the ``DMLC_SLO_*``
burn-rate objectives behind ``/slo``).

Where an iteration's time goes is measured from inside, with
``telemetry.span``: ``serving.iteration`` and under it disjoint
children (``serving.schedule``, ``serving.prefill`` with ``.run``
and, inside it, ``.dispatch`` and ``.fetch``, ``serving.kv_write``,
``serving.first_token``, ``serving.decode`` with ``.dispatch`` /
``.fetch`` / ``.commit`` / ``.deliver`` / ``.bookkeeping``), each
carrying ``args.iter``; an idle episode of the loop is one
``serving.starved`` span, and the engine's construction one
``serving.engine_init`` (over ``.weights`` and ``.cache``) on the
thread that builds it.  Every span is a host event in a profiler
capture and a ``<suffix>_secs`` / ``<suffix>_count`` counter
pair; the bytes that cross the host link are counted at the same
boundaries (README "Serving").

The device pools are the cache: the prefill program scatters the
prompt's K/V into the sequence's blocks and the decode program the
window's, the engine adopts the pools they return, and only the picked
ids, their finiteness and the routing counts cross the link
(``serving.kv_write`` is the adoption and the length bookkeeping).

The loop keeps one decode step in flight.  An iteration of ``_loop``
dispatches step n+1 and only then reads step n: the ids step n picked
stay on the device and step n+1 takes each row's token from them (or
from the host, for a row fresh from its prefill), while positions,
lengths, block tables and state slots are host facts, because plain
decode commits exactly one token a live row.  So
``serving.decode.fetch`` is the wait for step n with step n+1 queued
behind it, and the commit, delivery, bookkeeping, the next schedule and
the next dispatch run under a busy chip.  A row that ends by count with
step n is known before step n is read: it is out of step n+1 and the
scheduler gives its place away at once (``retire``).  A row that ends
by ``eos_id``, or fails the finiteness guard, at step n is found out
after step n+1 went with it: that one token is discarded
(``serving.lookahead_discarded_tokens``).  Whatever needs every
request's ``generated`` current reads the step in flight first
(``_settle``): eviction under KV pressure, the crash requeue, a loop
that stops (``close`` / ``drain`` join it), and ``step()`` called
without ``lookahead`` (the tests' single-stepping).  Under a
speculative window the committed count depends on the ids, so nothing
stays in flight there: the same loop at depth 0.  A prefill's read
still blocks, behind the step in flight.

Shape discipline (XLA recompiles per shape, so both are bucketed):
prefill pads prompts up to a whole number of KV blocks (safe under
causal attention), and decode always runs the full ``max_active``-row
batch with dead rows masked by length 0, its block tables growing a
block at a time.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import List, Optional

import numpy as np

from .. import concurrency, telemetry
from ..base import DMLCError, get_env
from ..concurrency import BufferPool, make_lock
from ..models import transformer as tfm
from .kv_cache import PagedKVCache
from .scheduler import (ACTIVE, WAITING, AlreadyFinished,
                        ContinuousBatchScheduler, Request,
                        coerce_priority)

__all__ = ["InferenceEngine", "AdmissionFull", "EngineDraining"]

logger = logging.getLogger("dmlc_tpu.serving")


class AdmissionFull(DMLCError):
    """The admission queue stayed full past the timeout (HTTP 429)."""


class RequestTooLarge(DMLCError):
    """The request could never fit the KV pool, even alone (HTTP 413)."""


class EngineDraining(DMLCError):
    """The engine stopped admitting (SIGTERM drain); HTTP 503 +
    Retry-After — in-flight generations keep decoding to completion."""


_JIT_CACHE: dict = {}

#: every counter the serving spans and byte counts feed: start() sets
#: them to 0, so a window in which a phase never ran reads 0 and not
#: "nothing to read".  No code opens ``prefill_kv_to_host`` or
#: ``kv_upload`` or adds to ``kv_upload_bytes`` (the device pools are
#: the cache); three of BENCHMARK.json's per-layer metrics sum them with
#: ``kv_write`` by name and read null without a term, so they stay, at 0
_SPAN_FAMILIES = (
    "iteration", "schedule", "prefill", "prefill_run",
    "prefill_dispatch", "prefill_fetch", "prefill_kv_to_host",
    "kv_write", "kv_upload", "first_token",
    "decode", "decode_dispatch", "decode_fetch", "decode_commit",
    "decode_deliver", "decode_bookkeeping", "starved", "http")
_ZEROED_COUNTERS = tuple(
    f + kind for f in _SPAN_FAMILIES for kind in ("_secs", "_count")
) + ("decode_d2h_bytes", "prefill_d2h_bytes", "kv_upload_bytes",
     "queue_wait_secs", "queue_wait_count", "latency_secs",
     "decode_steps_overlapped", "lookahead_discarded_tokens")


class _DedupeTable:
    """Idempotency-key table: client ``request_id`` → :class:`Request`.

    The primitive router retry/hedging stands on: a duplicate
    submission while the original is live returns the SAME request (the
    second waiter parks on it), and a duplicate after a successful
    finish returns the finished request from a bounded ring
    (``DMLC_SERVE_DEDUPE_MAX``) instead of generating again.  FAILED
    requests are deliberately dropped from the table — a retry of a
    failed id is a fresh attempt, which is exactly what a router
    failover wants.
    """

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._lock = make_lock("_DedupeTable._lock")
        self._live: dict = {}
        self._done: dict = {}
        self._order: "deque" = deque()

    def get(self, key: str) -> Optional[Request]:
        with self._lock:
            return self._live.get(key) or self._done.get(key)

    def claim(self, key: str, req: Request) -> Request:
        """Publish ``req`` under ``key`` unless a concurrent submit got
        there first; returns whichever request owns the key."""
        with self._lock:
            prior = self._live.get(key) or self._done.get(key)
            if prior is not None:
                return prior
            self._live[key] = req
            return req

    def drop(self, key: str, req: Request) -> None:
        """Un-publish after a failed admission/finish — only if the
        mapping is still ours (a fresh retry may have re-claimed)."""
        with self._lock:
            if self._live.get(key) is req:
                del self._live[key]

    def finish(self, key: str, req: Request) -> None:
        """Move a successfully finished request into the bounded ring."""
        with self._lock:
            if self._live.get(key) is not req:
                return
            del self._live[key]
            self._done[key] = req
            self._order.append(key)
            while len(self._order) > self.capacity:
                self._done.pop(self._order.popleft(), None)


#: growth of the expert layers' routing counts (models.transformer
#: ``_moe_held_ffn``), fed when a prefill or decode span closes
_MOE_COUNTERS = ("moe_pairs_total", "moe_pairs_held",
                 "moe_expert_load_max", "moe_expert_load_mean")


#: what a model with recurrent layers adds: slots handed out (the cache
#: counts them), the live slots summed over decode steps, and the state
#: bytes those steps read and wrote, under the name of the state's kind
_STATE_COUNTERS = ("state_slot_allocs", "state_slot_steps")
_STATE_BYTES_COUNTER = {"kda_mla": "kda_state_rw_bytes",
                        "nemotron_h": "ssm_state_rw_bytes"}

#: what a model with sliding-window layers adds.  The cache counts the
#: ring blocks that were taken over; the rest grow when a decode span
#: closes: the keys the step's live rows attended in ONE full layer
#: (their lengths) and in ONE sliding layer (at most the window), and
#: the cache's own counts at that moment summed over steps (blocks in
#: use in either pool, tokens cached), whose quotients by
#: ``paged_decode_steps`` are the window's means
_SLIDING_COUNTERS = ("kv_sliding_blocks_released", "attn_full_ctx_tokens",
                     "attn_sliding_ctx_tokens", "kv_block_steps",
                     "kv_sliding_block_steps", "kv_cached_token_steps")


def _jitted_programs(family: str = "mha"):
    """Process-wide jitted prefill/decode (one jit wrapper per program,
    so every engine instance shares one compile cache — tests and
    smokes build several engines and must not pay XLA again for
    identical shapes).

    Which pair depends on the model family alone, and every one ends
    in the same epilogue (``tfm.picking_prefill`` / ``picking_decode``
    around the family's forward): the greedy pick and its finiteness
    are what a program returns, never the logits, and the decode
    program takes the ids it consumes from the host or from an earlier
    step's pick that is still on the device.  Prefill, site
    ``serving.prefill``: ``forward_prefill_paged`` with the pools
    DONATED — it has this one caller, which replaces its references
    with the returned pools at once, and an undonated scatter would
    copy both pools per prompt.  Decode, site ``serving.decode_paged``:
    ``forward_decode_paged`` — one program serves any verify window,
    the window is a shape; its pools are DONATED like the prefill's, so
    a step scatters into them in place, and whoever calls the jitted
    program loses the arrays it passed in and goes on with the ones
    returned.  A latent-attention model (``family`` "mla") runs their
    twins at the same two sites, ``forward_prefill_paged_mla`` /
    ``forward_decode_paged_mla``, both donated their one pool; a model
    with recurrent layers ("kda_mla") ``forward_prefill_paged_hybrid``
    / ``forward_decode_paged_hybrid``, donated the pool and the two
    state arrays that travel with it; a model under a layer pattern
    ("nemotron_h") ``forward_prefill_paged_pattern`` /
    ``forward_decode_paged_pattern``, donated the K and V pools and the
    two state arrays; an MHA model with sliding-window
    layers ("mha_swa") ``forward_prefill_paged_swa`` /
    ``forward_decode_paged_swa``, donated the full layers' and the
    sliding layers' K and V pools.  All go
    through :func:`telemetry.compute.profiled_jit`, which is plain
    ``jax.jit`` when ``DMLC_COMPUTE_PROFILE=0``; the cache is keyed on
    that mode so toggling the knob between tests cannot hand a plain
    engine a profiled program or vice versa.  The decode site carries
    the ``DMLC_SERVE_MAX_DECODE_SIGS`` signature cap — every distinct
    context depth is a full XLA recompile, so unbounded signature
    growth is a bug worth failing loudly on."""
    compute = telemetry.compute
    mode = "profiled" if compute.enabled() else "plain"
    if family == "kda_mla":
        prefill_key = (mode, "prefill_paged_hybrid")
        prefill_fn, prefill_kw = tfm.forward_prefill_paged_hybrid, {
            "static_argnums": (8,), "donate_argnums": (3, 4, 5)}
        decode_key = (mode, "decode_paged_hybrid")
        decode_fn, decode_kw = tfm.forward_decode_paged_hybrid, {
            "static_argnums": (9,), "donate_argnums": (3, 4, 5)}
    elif family == "nemotron_h":
        prefill_key = (mode, "prefill_paged_pattern")
        prefill_fn, prefill_kw = tfm.forward_prefill_paged_pattern, {
            "static_argnums": (9,), "donate_argnums": (3, 4, 5, 6)}
        decode_key = (mode, "decode_paged_pattern")
        decode_fn, decode_kw = tfm.forward_decode_paged_pattern, {
            "static_argnums": (10,), "donate_argnums": (3, 4, 5, 6)}
    elif family == "mha_swa":
        prefill_key = (mode, "prefill_paged_swa")
        prefill_fn, prefill_kw = tfm.forward_prefill_paged_swa, {
            "static_argnums": (9,), "donate_argnums": (3, 4, 5, 6)}
        decode_key = (mode, "decode_paged_swa")
        decode_fn, decode_kw = tfm.forward_decode_paged_swa, {
            "static_argnums": (10,), "donate_argnums": (3, 4, 5, 6)}
    elif family == "mla":
        prefill_key = (mode, "prefill_paged_mla")
        prefill_fn, prefill_kw = tfm.forward_prefill_paged_mla, {
            "static_argnums": (5,), "donate_argnums": (3,)}
        decode_key = (mode, "decode_paged_mla")
        decode_fn, decode_kw = tfm.forward_decode_paged_mla, {
            "static_argnums": (6,), "donate_argnums": (3,)}
    else:
        prefill_key = (mode, "prefill_paged")
        prefill_fn, prefill_kw = tfm.forward_prefill_paged, {
            "static_argnums": (6,), "donate_argnums": (3, 4)}
        decode_key = (mode, "decode_paged")
        decode_fn, decode_kw = tfm.forward_decode_paged, {
            "static_argnums": (7,), "donate_argnums": (3, 4)}
    progs = (_JIT_CACHE.get(prefill_key), _JIT_CACHE.get(decode_key))
    if progs[0] is None or progs[1] is None:
        # this cache outlives any one engine — if the first engine of
        # the process is built inside an interleaving-explorer scenario
        # (analysis.scenarios builds a real engine as a scheduler test
        # double), the profiled wrappers must NOT capture the
        # scenario's scheduler-owned SchedLocks: a later engine would
        # inherit a lock wired to a finished controller
        prev_hook = concurrency._lock_factory_hook
        concurrency.set_lock_factory_hook(None)
        try:
            if progs[0] is None:
                _JIT_CACHE[prefill_key] = compute.profiled_jit(
                    tfm.picking_prefill(prefill_fn),
                    site="serving.prefill", **prefill_kw)
            if progs[1] is None:
                _JIT_CACHE[decode_key] = compute.profiled_jit(
                    tfm.picking_decode(decode_fn),
                    site="serving.decode_paged", max_signatures=get_env(
                        "DMLC_SERVE_MAX_DECODE_SIGS", 64), **decode_kw)
        finally:
            concurrency.set_lock_factory_hook(prev_hook)
        progs = (_JIT_CACHE[prefill_key], _JIT_CACHE[decode_key])
    for prog in progs:
        rereg = getattr(prog, "reregister", None)
        if rereg is not None:
            rereg()
    return progs


def _start_fetch(*arrays) -> None:
    """Ask for each device array's copy to the host now: the link takes
    them as the program finishes, side by side, and the ``np.asarray``
    that follows finds them there instead of asking one at a time."""
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:  # a test's stand-in may hand numpy back
            start()


class _DecodeStep:
    """A decode step on the device whose picks the host has not read:
    the batch it ran (``rows``, and ``row_of`` from a request's id to
    its row), what the commit needs of its inputs (``drafts``,
    ``base_lens``), and the arrays the program returned."""

    __slots__ = ("rows", "row_of", "drafts", "base_lens", "n_preempted",
                 "overlapped", "ids", "finite", "moe")

    def __init__(self, rows, drafts, base_lens, n_preempted, overlapped,
                 picked, moe):
        self.rows = rows
        self.row_of = {req.id: i for i, req in enumerate(rows)}
        self.drafts = drafts
        self.base_lens = base_lens
        self.n_preempted = n_preempted
        # dispatched while the step before it was unread
        self.overlapped = overlapped
        self.ids, self.finite = picked
        self.moe = moe


class InferenceEngine:
    """Continuous-batching generation over one model replica.

    Defaults come from the ``DMLC_SERVE_*`` knobs (see README
    "Serving") so ``bin/dmlc-serve`` and embedded uses read one
    configuration surface.

    The tree the engine holds (``self.params``) is the one its programs
    read: a stacked MHA tree is turned, once and here, into one array a
    layer and matrix (``tfm.per_layer_params``: ``params["layers"]``,
    no ``"blocks"``), and the engine keeps no reference to the stack,
    so a caller that drops its own holds the weights once.  Every other
    tree is held as the object that came in.
    """

    # the construction is a span, for the set-up it is part of: the
    # weights' re-layout and the cache manager's construction are its
    # children (the pools themselves are made at first use)
    @telemetry.span("serving.engine_init", stage="serving")
    def __init__(self, params, cfg: "tfm.TransformerConfig", *,
                 n_blocks: Optional[int] = None,
                 block_size: Optional[int] = None,
                 max_active: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 admit_timeout_s: Optional[float] = None,
                 max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 slo_monitor=None):
        with telemetry.span("serving.engine_init.weights", stage="serving"):
            self.params = tfm.per_layer_params(params)
        self.cfg = cfg
        self.max_active = (max_active if max_active is not None
                           else get_env("DMLC_SERVE_MAX_ACTIVE", 8))
        self.admit_timeout_s = (
            admit_timeout_s if admit_timeout_s is not None
            else get_env("DMLC_SERVE_ADMIT_TIMEOUT_S", 2.0))
        self.default_max_new_tokens = (
            max_new_tokens if max_new_tokens is not None
            else get_env("DMLC_SERVE_MAX_TOKENS", 64))
        self.eos_id = eos_id
        # priority classes: admission order and KV-pressure eviction
        # both prefer low-priority victims (scheduler policy); the
        # class count and the unlabeled default are knobs so a fleet
        # can widen the ladder without a code change
        self.priority_levels = max(1, get_env(
            "DMLC_SERVE_PRIORITY_LEVELS", 3))
        self.priority_default = min(
            max(0, get_env("DMLC_SERVE_PRIORITY_DEFAULT", 1)),
            self.priority_levels - 1)
        n_blocks = (n_blocks if n_blocks is not None
                    else get_env("DMLC_SERVE_KV_BLOCKS", 256))
        block_size = (block_size if block_size is not None
                      else get_env("DMLC_SERVE_KV_BLOCK_SIZE", 16))
        # a ring for every row that can be live at once: the sliding
        # pool never refuses what the batch has room for
        sliding = cfg.sliding_pool_shapes(self.max_active, block_size)
        with telemetry.span("serving.engine_init.cache", stage="serving"):
            self.cache = PagedKVCache(
                cfg.n_layers, cfg.n_heads, cfg.head_dim,
                n_blocks=n_blocks, block_size=block_size,
                dtype=np.dtype(cfg.dtype),
                pool_shapes=cfg.kv_pool_shapes(n_blocks, block_size),
                state_shapes=cfg.state_slot_shapes(self.max_active),
                sliding_shapes=sliding,
                sliding_window=cfg.sliding_window if sliding else 0)
        self.scheduler = ContinuousBatchScheduler(
            self.cache, max_active=self.max_active)
        depth = (queue_depth if queue_depth is not None
                 else get_env("DMLC_SERVE_QUEUE_DEPTH", 64))
        self._slots: BufferPool = BufferPool(object, capacity=depth)
        # request-scoped observability: per-request lifecycle ledger
        # (+ /requests endpoint) feeding the SLO burn-rate monitor
        # (+ /slo endpoint); the default monitor is process-wide so
        # heartbeats ship ONE slo sub-doc per replica process
        self.slo = (slo_monitor if slo_monitor is not None
                    else telemetry.slo.monitor())
        self.requests = telemetry.RequestLedger(slo=self.slo)
        # availability ledger (telemetry.goodput): the serving twin of
        # the training goodput ledger — serving / draining /
        # crashed_recovering / starved_idle wall fractions + tokens
        # served vs. capacity-tokens, surfaced via stats() → the router
        # /fleet view and the /metrics dmlc_availability_* family.
        # A replica is idle until its loop first does work.
        self.availability = telemetry.AvailabilityLedger()
        self.availability.set_state("starved_idle")
        # idempotency-key dedupe (router retry/hedge primitive) + the
        # per-request crash-requeue budget (requeue-on-crash keeps an
        # engine-iteration crash output-invisible, bounded so a
        # deterministically poisonous request still fails)
        self._dedupe = _DedupeTable(get_env("DMLC_SERVE_DEDUPE_MAX", 512))
        self._crash_requeue_max = get_env(
            "DMLC_SERVE_CRASH_REQUEUE_MAX", 2)
        self.spec_k = max(0, int(get_env("DMLC_SERVE_SPEC_K", 0)))
        self.spec_min_ctx = max(1, int(get_env("DMLC_SERVE_SPEC_MIN_CTX",
                                               4)))
        if self.spec_k and self.cache.n_slots:
            raise ValueError(
                "DMLC_SERVE_SPEC_K > 0 with a model that has recurrent "
                "layers: a rejected draft would need the state rolled "
                "back, which the cache manager cannot do")
        if self.spec_k and self.cache.ring_blocks:
            raise ValueError(
                "DMLC_SERVE_SPEC_K > 0 with a model that has sliding-"
                "window layers: a ring table holds one decode token's "
                "reach, not a verify window's")
        self._spec_window = 1 + self.spec_k
        # bytes one live row's recurrent state costs a decode step:
        # read once and written once in every layer that has one (the
        # first of the state arrays; the convolution's tail is small)
        self._state_rw_bytes = 0
        self._state_bytes_counter = _STATE_BYTES_COUNTER.get(cfg.family)
        if self.cache.n_slots:
            shape, dt = self.cache.state_shapes[0]
            self._state_rw_bytes = (
                2 * int(np.prod(shape)) // shape[1] * dt.itemsize)
        self._prefill, self._decode = _jitted_programs(cfg.family)
        self._stop = threading.Event()
        self._draining = threading.Event()
        # iteration seqlock: odd = an engine iteration is mid-flight
        # (its pop window can hold a request in NEITHER queue), even =
        # quiescent.  Single writer (the engine thread); drain()'s scan
        # reads it around an atomic scheduler.counts() snapshot and
        # retries on any change, so a request in transit can never be
        # mistaken for drained — see drain() for the proof sketch
        # dmlc-check: unguarded(seqlock: single-writer engine thread; GIL-atomic int reads)
        self._step_seq = 0
        # dmlc-check: unguarded(start/close control-thread lifecycle; close joins before the sweep)
        self._thread: Optional[threading.Thread] = None
        # dmlc-check: unguarded(engine-thread-confined)
        self._flops_declared = False
        # dmlc-check: unguarded(engine-thread-confined)
        self._hbm_tick = 0
        # dmlc-check: unguarded(engine-thread-confined)
        self._fpt_cache: dict = {}
        # padded prompt lengths seen so far: a NEW bucket means a fresh
        # XLA prefill compile, worth a log line and a counter
        # dmlc-check: unguarded(engine-thread-confined)
        self._prompt_buckets: set = set()
        # number of the iteration in flight (``args.iter`` of its spans)
        # dmlc-check: unguarded(engine-thread-confined)
        self._iter = 0
        # the decode step that is dispatched and not read (at most one)
        # dmlc-check: unguarded(engine-thread-confined)
        self._inflight: Optional[_DecodeStep] = None
        # the ids the last decode step picked, on the device: what the
        # next step is handed as ``prev_ids`` whether or not a row
        # takes its token from there
        # dmlc-check: unguarded(engine-thread-confined)
        self._last_ids = np.zeros((self.max_active, self._spec_window),
                                  np.int32)

    # ---- client surface -------------------------------------------------
    def submit(self, prompt_ids: List[int],
               max_new_tokens: Optional[int] = None,
               timeout: Optional[float] = None,
               request_id: Optional[str] = None,
               priority=None, tenant: Optional[str] = None,
               trace_id: Optional[str] = None) -> Request:
        """Admit a request or raise: :class:`AdmissionFull` when no
        queue slot frees up within ``timeout`` (default
        ``admit_timeout_s``), ``ValueError`` when the request could
        never be served (bad ids, context beyond total cache, an
        invalid priority class).

        ``priority`` is a validated class (an int in
        ``[0, priority_levels)`` or a name from
        :data:`scheduler.PRIORITY_CLASSES`; None → the configured
        default): the scheduler admits high classes first and evicts
        low classes first under KV pressure.  ``tenant`` rides along
        for per-tenant accounting (the ROUTER enforces tenant
        fairness; the engine only labels).

        ``request_id`` is the client's idempotency key: a duplicate
        submission while the original is live (or successfully finished
        and still in the bounded dedupe ring) returns the ORIGINAL
        request instead of starting a second generation — the
        primitive the fleet router's retry and hedging rely on.  The
        dedupe lookup runs before the drain gate, so a retry of
        already-admitted work resolves even on a draining replica.

        ``trace_id`` is the fleet trace id from the ``X-DMLC-Trace``
        context (DMLC_TRACE_FLEET): stamped onto the request and its
        ledger rows so this replica's queue → prefill → decode story
        joins the router's dispatch spans in one cross-process
        trace."""
        t_submit = time.perf_counter()
        if request_id is not None:
            if (not isinstance(request_id, str) or not request_id
                    or len(request_id) > 128):
                raise ValueError("request_id must be a non-empty string "
                                 "of at most 128 chars")
            prior = self._dedupe.get(request_id)
            if prior is not None:
                telemetry.inc("serving", "dedupe_hits")
                return prior
        if self._draining.is_set():
            raise EngineDraining(
                "engine is draining (shutdown notice); retry against "
                "another replica")
        mnt = (max_new_tokens if max_new_tokens is not None
               else self.default_max_new_tokens)
        prio = coerce_priority(priority, self.priority_levels,
                               self.priority_default)
        if tenant is None:
            tenant = "default"
        elif (not isinstance(tenant, str) or not tenant
                or len(tenant) > 64):
            raise ValueError("tenant must be a non-empty string of at "
                             "most 64 chars")
        req = Request(prompt_ids, mnt, eos_id=self.eos_id,
                      priority=prio, tenant=tenant)
        req.client_id = request_id
        if trace_id is not None:
            req.trace_id = str(trace_id)
        if any(t < 0 or t >= self.cfg.vocab for t in req.prompt_ids):
            raise ValueError(
                f"prompt ids out of range for vocab {self.cfg.vocab}")
        # spec decode reserves a whole verify window ahead of each
        # step, so the worst-case footprint carries spec_k extra slots
        if not self.cache.fits_at_all(req.n_prompt + mnt + self.spec_k):
            raise RequestTooLarge(
                f"request needs up to {req.n_prompt + mnt + self.spec_k} "
                f"cached tokens; "
                f"cache holds {self.cache.n_blocks * self.cache.block_size}")
        if request_id is not None:
            # publish BEFORE the (possibly seconds-long) slot wait so a
            # concurrent duplicate parks on this request instead of
            # racing it into a second generation
            claimed = self._dedupe.claim(request_id, req)
            if claimed is not req:
                telemetry.inc("serving", "dedupe_hits")
                return claimed
        slot = self._slots.acquire(
            timeout=self.admit_timeout_s if timeout is None else timeout)
        if slot is None:
            telemetry.inc("serving", "rejected")
            if request_id is not None:
                # un-publish so a later retry is a fresh attempt, and
                # wake any duplicate that parked during the slot wait
                self._dedupe.drop(request_id, req)
                req.rejected_busy = True
                req.reject("admission queue full; retry later")
            raise AdmissionFull(
                f"admission queue full (depth includes {self.max_active} "
                f"active); retry later")
        req.slot = slot
        telemetry.inc("serving", "requests")
        # ledger entry opens at the submit stamp, so queue_s includes
        # the admission-slot wait a saturated server imposes
        self.requests.on_submit(req.id, req.n_prompt, mnt, t=t_submit,
                                trace_id=req.trace_id)
        self.scheduler.enqueue(req)
        if self._stop.is_set():
            # close() can finish its sweep between our slot acquire and
            # the enqueue above; nobody would ever fail this request,
            # so do it here rather than hang the waiter
            try:
                self._finish(req, error="engine shut down",
                             reason="shutdown")
            except AlreadyFinished:
                pass
            raise DMLCError("engine shut down")
        return req

    def generate(self, prompt_ids: List[int],
                 max_new_tokens: Optional[int] = None,
                 timeout: float = 120.0) -> List[int]:
        """Blocking convenience: submit, wait, return generated ids."""
        req = self.submit(prompt_ids, max_new_tokens)
        if not req.wait(timeout):
            raise DMLCError(f"request {req.id} timed out after {timeout}s")
        if req.error:
            raise DMLCError(f"request {req.id} failed: {req.error}")
        return list(req.generated)

    # ---- engine loop ----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            if self._thread.is_alive():
                return
            raise DMLCError("engine thread wedged by a previous close(); "
                            "build a fresh engine")
        if self._stop.is_set():
            raise DMLCError("engine is closed")
        self._stop.clear()
        for name in _ZEROED_COUNTERS + (
                _MOE_COUNTERS if self.cfg.moe_router == "sigmoid" else ()
                ) + (_STATE_COUNTERS + (self._state_bytes_counter,)
                     if self.cache.n_slots else ()
                     ) + (_SLIDING_COUNTERS if self.cache.ring_blocks
                          else ()):
            telemetry.inc("serving", name, 0)
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="serving-engine")
        self._thread.start()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def begin_drain(self) -> None:
        """Stop admitting; the decode loop keeps running so active (and
        already-queued) generations finish."""
        if not self._draining.is_set():
            self._draining.set()
            self.availability.set_state("draining")
            telemetry.set_gauge("serving", "draining", 1)
            telemetry.record_event("serving_drain_begin",
                                   active=self.scheduler.n_active,
                                   waiting=self.scheduler.n_waiting)

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful preemption shutdown: stop admitting, finish every
        in-flight generation within ``timeout_s``
        (``DMLC_SERVE_DRAIN_S``, default 30), then close.  Returns True
        when the backlog fully drained, False when the deadline cut it
        off (the remaining requests are failed by close())."""
        t = (timeout_s if timeout_s is not None
             else get_env("DMLC_SERVE_DRAIN_S", 30.0))
        self.begin_drain()
        deadline = time.monotonic() + t
        # "Drained" must be judged against a CONSISTENT cut.  Queue
        # membership comes from scheduler.counts() — one lock hold, so
        # the two backward movers (self-preemption, crash requeue) can
        # never hide a request between separate waiting/active reads
        # (the original PR 13 bug).  A request in the POP WINDOW
        # (popped by next_prefill, not yet activated) is in neither
        # queue; the step seqlock covers it: the window runs strictly
        # inside one step()'s odd interval, so either a seq read is
        # odd or the two reads differ — both retry.  (The interleaving
        # explorer found the flag-based predecessor of this scan being
        # fooled by a requeue-then-resume cycle mid-pass: a boolean
        # "stepping" can flip False->True->False between reads;
        # a counter cannot revisit a value.)
        while True:
            s1 = self._step_seq
            active, waiting = self.scheduler.counts()
            s2 = self._step_seq
            if (not active and not waiting and s1 == s2
                    and s1 % 2 == 0):
                break
            if time.monotonic() > deadline:
                logger.warning(
                    "drain deadline (%.1fs) hit with %d active / %d "
                    "waiting; failing the rest", t, active, waiting)
                self.close()
                telemetry.record_event("serving_drain_end", clean=False)
                return False
            time.sleep(0.02)
        self.close()
        telemetry.record_event("serving_drain_end", clean=True)
        return True

    def close(self) -> None:
        """Stop the loop; fail whatever is still queued or active (their
        waiters wake with an error) and wake blocked submitters."""
        self._stop.set()
        self._slots.kill()
        t = self._thread
        if t is not None:
            t.join(timeout=30.0)
            if t.is_alive():
                # a step is still running (giant jit compile, wedged
                # device): sweeping now would race its cache writes —
                # leave the daemon thread to die with the process and
                # let per-request timeouts surface the failure
                logger.error("engine thread still running after 30s; "
                             "skipping the shutdown sweep")
                return
            self._thread = None
        for req in self.scheduler.all_pending():
            try:
                self._finish(req, error="engine shut down",
                             reason="shutdown")
            except AlreadyFinished:
                pass  # racing terminal transition already happened

    def _span(self, name: str, **args):
        """An engine-thread span of the iteration in flight."""
        args["iter"] = self._iter
        return telemetry.span(name, stage="serving", args=args)

    def _loop(self) -> None:
        # the open serving.starved span: one per episode in which the
        # loop finds no work, not one per sleep
        starved = None
        while not self._stop.is_set():
            if starved is not None and any(self.scheduler.counts()):
                starved.__exit__(None, None, None)
                starved = None
            crashed = False
            try:
                did = self.step(lookahead=True)
            except Exception as e:  # noqa: BLE001 - engine must not die
                crashed = True
                # a crashed decode leaves the ACTIVE set's cache state
                # unknown — but the OUTPUT state is perfectly known
                # (req.generated), and recompute-resume is free: each
                # active request is requeued with its blocks freed so
                # the re-prefill rebuilds its context, exactly like a
                # preemption.  The per-request crash budget
                # (DMLC_SERVE_CRASH_REQUEUE_MAX) bounds a
                # deterministically poisonous request: past it, the
                # request fails with reason "crash".  WAITING requests
                # were never touched and keep serving either way.
                # A step still in flight is read first where it can be
                # (its picks are output like any other); where it
                # cannot, the resume recomputes its tokens.
                self._settle_or_drop()
                for req in self.scheduler.running_requests():
                    if (req.crash_requeues < self._crash_requeue_max
                            and self.scheduler.requeue_active(req)):
                        telemetry.inc("serving", "crash_requeues")
                        self.requests.on_preempt(req.id)
                        continue
                    try:
                        self._finish(
                            req, error=f"engine iteration failed: {e!r}",
                            reason="crash")
                    except AlreadyFinished:
                        pass
                logger.error("serving iteration failed: %r", e)
                did = False
            # availability state for this iteration: draining wins
            # (drain is still in progress even while work finishes),
            # then crash recovery, then serving vs. starved-idle;
            # set_state is a no-op when the state is unchanged
            if self._draining.is_set():
                self.availability.set_state("draining")
            elif crashed:
                self.availability.set_state("crashed_recovering")
            elif did:
                self.availability.set_state("serving")
            else:
                self.availability.set_state("starved_idle")
            if not did:
                if starved is None:
                    starved = self._span("serving.starved")
                    starved.__enter__()
                # idle: nothing waiting, nothing active — but the SLO
                # windows keep aging, so evaluation must keep running
                # (a violation flips back when its burst expires even
                # if no request ever arrives again; throttled inside)
                self.slo.maybe_evaluate()
                time.sleep(0.002)
        if starved is not None:
            starved.__exit__(None, None, None)
        # nothing stays unread behind a loop that has stopped: a request
        # whose last token was in flight finishes, and close() fails the
        # rest
        self._settle_or_drop()

    def _settle_or_drop(self) -> None:
        try:
            self._settle()
        except Exception as e:  # noqa: BLE001 - the step is lost, not the engine
            logger.error("the decode step in flight could not be read: "
                         "%r", e)

    # ---- one iteration --------------------------------------------------
    def step(self, lookahead: bool = False) -> bool:
        """One continuous-batching iteration: drain admissible prefills
        (the scheduler's ``next_prefill`` stops at ``max_active``), then
        one decode window for every active request.  With ``lookahead``
        (the loop's way) the step dispatched here stays unread and the
        one before it is read in its place; without (the default) every
        step is read before this returns, so a single-stepped engine
        shows each step's tokens at once.  Prefill-priority
        keeps the decode batch full — an 8-deep queue joins the batch in
        ONE iteration instead of ramping a row per step, which is where
        decode MFU goes to die on short bursts.  Decode still runs every
        iteration, so active rows are never starved; the worst prefill
        stall a streaming user can see is one queue-drain of admissible
        requests, bounded by ``max_active``.  Returns whether any work
        happened (the loop's idle signal).  Public so tests can
        single-step the engine deterministically."""
        self._step_seq += 1
        try:
            if self._inflight is None and not any(self.scheduler.counts()):
                return False  # the loop's serving.starved span has this
            self._iter += 1
            self.requests.iteration = self._iter
            with self._span("serving.iteration"):
                return self._iterate(lookahead)
        finally:
            self._step_seq += 1

    def _iterate(self, lookahead: bool) -> bool:
        did = False
        while True:
            with self._span("serving.schedule"):
                req = self.scheduler.next_prefill()
            if req is None:
                break
            self._run_prefill(req)
            did = True
            if req.state == WAITING:
                # allocate lost a race and requeued the request;
                # bail rather than spin on it inside one iteration
                break
        return self._run_decode(lookahead) or did

    def _finish(self, req: Request, error: Optional[str] = None,
                reason: Optional[str] = None) -> None:
        self.scheduler.finish(req, error=error)
        # scheduler.finish raising AlreadyFinished above is the
        # exactly-once guard for the ledger too: a swept request can
        # never be recorded twice
        self.requests.on_finish(req.id, error=error, reason=reason)
        if req.client_id is not None:
            if error:
                # failed ids leave the table: a retry of a FAILED
                # request is a fresh attempt (router failover semantics)
                self._dedupe.drop(req.client_id, req)
            else:
                self._dedupe.finish(req.client_id, req)
        if req.latency_s is not None:
            telemetry.observe_duration("serving", "latency", req.latency_s)
        tps = req.decode_tokens_per_s
        if tps is not None:
            telemetry.set_gauge("serving", "tokens_per_s_per_user", tps)
        slot, req.slot = req.slot, None
        if slot is not None:
            self._slots.release(slot)

    def _run_prefill(self, req: Request) -> None:
        """Prefill ``req``'s context and cache its K/V inside the device
        program, which scatters them into the request's blocks of the
        pools it is donated and picks the token after the last position
        (the pick and its finiteness are all that come to the host).  A
        fresh request takes its first token here (that IS the
        TTFT moment); a preemption resume must NOT — its context
        already excludes the un-consumed ``generated[-1]``, so the
        last-position logits would deterministically re-derive that very
        token and duplicate it in the output.  The resume's next token
        comes from the decode step that consumes ``generated[-1]``."""
        with self._span("serving.schedule", req=req.id):
            ctx = req.context_ids()
            n = len(ctx)
            bs = self.cache.block_size
            if not self.cache.allocate(req.id, n):
                # admission checked the free list, but a decode in the
                # same iteration window can race it; retry next iteration
                self.scheduler.requeue_front(req)
                return
            resume = bool(req.generated)
        try:
            padded = n + (-n % bs)
            if padded not in self._prompt_buckets:
                self._prompt_buckets.add(padded)
                telemetry.inc("serving", "prompt_bucket_new")
                logger.info(
                    "serving: new prefill padding bucket %d tokens "
                    "(%d seen) — expect one XLA compile", padded,
                    len(self._prompt_buckets))
            ids = np.zeros((1, padded), np.int32)
            ids[0, :n] = ctx
            last = np.array([n - 1], np.int32)
            self.requests.on_prefill_begin(req.id, resume=resume)
            picked = self._prefill_paged(req, ids, last, n)
            telemetry.inc("serving", "prefill_tokens", n)
        except Exception as e:  # noqa: BLE001 - fail THIS request only
            logger.error("prefill of request %d failed: %r", req.id, e)
            self._finish(req, error=f"prefill failed: {e!r}",
                         reason="prefill")
            if self.cache.drop_lost_pools():
                # the program failed AFTER its donated pools were given
                # up (a device fault, not a compile error): every live
                # sequence's K/V went with them.  That is an iteration
                # crash, not one request's failure: _loop requeues the
                # active requests, whose re-prefill fills fresh pools
                raise
            return
        with self._span("serving.first_token", req=req.id):
            self._after_prefill(req, *picked, resume)

    def _prefill_paged(self, req: Request, ids, last, n: int):
        """The device program writes the K/V into the request's blocks
        (prefill pads to whole blocks, so its block table IS the padded
        prompt's); ``(next id, whether its logit is finite)`` and the
        routing counts alone cross the link.  The read waits for the
        program, and for a decode step in flight before it."""
        with self._span("serving.prefill", tokens=n, req=req.id):
            with self._span("serving.prefill.run", req=req.id):
                with self._span("serving.prefill.dispatch", req=req.id):
                    picked, pools, moe = self._on_pools(
                        self._prefill, self.params, ids, last,
                        np.asarray(self.cache.block_table(req.id),
                                   np.int32),
                        *self._prefill_args(req.id), at=3)
                    _start_fetch(*picked, *moe)
                # the wait for the decode step in flight, the prefill,
                # and the runtime's notice of its end
                with self._span("serving.prefill.fetch", req=req.id):
                    picked = [np.asarray(a) for a in picked]
                    moe = [np.asarray(m) for m in moe]
        telemetry.inc("serving", "prefill_d2h_bytes",
                      sum(a.nbytes for a in picked + moe))
        self._count_moe(moe)
        with self._span("serving.kv_write", req=req.id):
            self.cache.adopt_device_pools(*pools)
            self.cache.advance_many([(req.id, n)])
        return int(picked[0][0]), bool(picked[1][0])

    def _slot_args(self, seq_ids, pad_batch=None) -> tuple:
        """What a program of a model with recurrent layers takes after
        the block tables: the sequences' state slots; nothing else."""
        if not self.cache.n_slots:
            return ()
        return (self.cache.slot_ids(seq_ids, pad_batch),)

    def _prefill_args(self, seq_id) -> tuple:
        """What a prefill program takes after the block table: the
        ring entries it writes (sliding layers), the sequence's state
        slot (recurrent layers), or nothing."""
        if self.cache.ring_blocks:
            return (np.asarray(self.cache.sliding_prefill_ids(seq_id),
                               np.int32),)
        return self._slot_args([seq_id])

    def _row_args(self, seq_ids, pad_batch) -> tuple:
        """What a decode program takes after the tables and lengths:
        the rows' ring tables (sliding layers), their state slots
        (recurrent layers), or nothing."""
        if self.cache.ring_blocks:
            return (self.cache.sliding_tables_array(seq_ids, pad_batch),)
        return self._slot_args(seq_ids, pad_batch)

    def _on_pools(self, program, *args, at: int):
        """Call a paged program with the cache's pools spliced in at
        argument ``at`` and the config last; split what it returns into
        ``((ids, finite), pools, rest)``.  The pools are the cache's
        device arrays, recurrent state included; ``rest`` is empty for
        the MHA programs and the routing counts for the other
        families'."""
        pools = self.cache.device_pools()
        out = program(*args[:at], *pools, *args[at:], self.cfg)
        return out[:2], out[2:2 + len(pools)], out[2 + len(pools):]

    def _count_moe(self, moe) -> None:
        """Add one program call's routing counts ``[n_moe_layers,
        n_experts + 1]`` (pairs per held expert, then pairs routed
        anywhere) to the ``serving.moe_*`` counters."""
        for counts in moe:
            held = counts[:, :-1]
            telemetry.inc("serving", "moe_pairs_total",
                          float(counts[:, -1].sum()))
            telemetry.inc("serving", "moe_pairs_held", float(held.sum()))
            telemetry.inc("serving", "moe_expert_load_max",
                          float(held.max(axis=1).sum()))
            telemetry.inc("serving", "moe_expert_load_mean",
                          float(held.mean(axis=1).sum()))

    def _after_prefill(self, req: Request, next_id: int, finite: bool,
                       resume: bool) -> None:
        """Give a fresh request its first token and activate it."""
        if not resume:
            if not finite:
                # same guard at the prefill sample point: the first
                # token must not come from a non-finite row either
                telemetry.inc("serving", "nonfinite_failures")
                self._finish(req, error="non-finite logits during "
                             "prefill (numeric corruption); retry the "
                             "request", reason="nonfinite")
                return
            req.generated.append(next_id)
            telemetry.inc("serving", "tokens_generated")
            req.ttft_s = time.monotonic() - req.submit_t
            telemetry.observe_duration("serving", "ttft", req.ttft_s)
            # the ledger's TTFT moment: stamps ttft_s = queue_s +
            # prefill_s exactly (all from one clock)
            self.requests.on_first_token(req.id)
            if req.is_finished_by(next_id):
                self._finish(req)
                return
        else:
            # resume prefill re-cached context without sampling; decode
            # resumes from generated[-1] next iteration
            self.requests.on_prefill_end(req.id)
        self.scheduler.activate(req)

    def _ensure_decode_capacity(self, active: List[Request],
                                n_tokens: int = 1) -> tuple:
        """Reserve ``n_tokens`` more cache slots per active request
        (one for plain decode, the whole verify window under spec
        decode), preempting youngest-first under pressure; returns
        ``(survivors, n_preempted)`` — the count feeds the iteration
        record."""
        # batch fast path: one allocator visit reserves the whole
        # batch when the pool has room (the overwhelmingly common
        # case); the per-request loop below only runs under pressure,
        # where eviction decisions must be made one victim at a time
        if active and self.cache.extend_many(
                [r.id for r in active], n_tokens):
            return list(active), 0
        alive = []
        n_preempted = 0
        for req in active:
            if req.state != ACTIVE:
                continue  # a preemption below already took it out
            while not self.cache.extend(req.id, n_tokens):
                victim = self.scheduler.preempt_youngest()
                if victim is not None:
                    n_preempted += 1
                    self.requests.on_preempt(victim.id)
                if victim is None:
                    self._finish(req, error="kv cache exhausted with "
                                 "nothing left to evict",
                                 reason="kv_exhausted")
                    break
                if victim is req:
                    break  # preempted itself; resumes via re-prefill
            else:
                alive.append(req)
        # a LATER request's eviction can preempt an EARLIER survivor
        # (activation order is not age order once resumes re-append):
        # only still-active requests may decode
        return [r for r in alive if r.state == ACTIVE], n_preempted

    def _draft_tokens(self, req: Request) -> List[int]:
        """n-gram suffix-lookup drafter: propose up to ``spec_k``
        continuation tokens from the request's OWN context.  The
        longest (3→1) suffix of prompt+generated that recurs earlier in
        the context predicts whatever followed its previous occurrence
        — free to compute, surprisingly effective on looping/structured
        output, and harmless when wrong (the verify step rejects).  No
        proposal below ``spec_min_ctx`` context tokens."""
        ctx = list(req.prompt_ids) + list(req.generated)
        n = len(ctx)
        if n < self.spec_min_ctx:
            return []
        # C-speed suffix search: token ids map 1:1 onto unicode code
        # points, so str.rfind does the rightmost-occurrence scan (the
        # python-loop version was a measurable slice of a ~1 ms decode
        # step at batch 8)
        try:
            text = "".join(map(chr, ctx))
        except ValueError:  # id beyond chr() range: python-loop fallback
            text = None
        for m in (3, 2, 1):
            if n <= m:
                continue
            if text is not None:
                # match must lie fully inside the prefix (end before
                # the terminal suffix itself): search window [0, n-1)
                p = text.rfind(text[n - m:], 0, n - 1)
            else:
                suffix = ctx[-m:]
                p = next((s for s in range(n - m - 1, -1, -1)
                          if ctx[s:s + m] == suffix), -1)
            if p >= 0:
                return ctx[p + m:p + m + self.spec_k]
        return []

    def _run_decode(self, lookahead: bool) -> bool:
        """One decode window for every active request, and the read of
        a step's picks: of this one at once, or (``lookahead``, plain
        decode) of the one before it, while this one runs.  Whether
        there was anything to run or read."""
        s_w = self._spec_window
        # a verify window commits as many tokens as the ids say, so the
        # next step's lengths wait for this one's read: nothing stays
        # unread behind it
        ahead = lookahead and s_w == 1
        did = False
        if not ahead:
            did = self._settle()
        n_preempted = 0
        with self._span("serving.schedule"):
            active = self.scheduler.active_requests()
            tight = bool(active) and not self.cache.extend_many(
                [r.id for r in active], s_w)
            if active and not tight:
                inputs = self._decode_inputs(active)
        if tight:
            # eviction requeues its victims with what they generated:
            # that has to be current
            self._settle()
            with self._span("serving.schedule"):
                active, n_preempted = self._ensure_decode_capacity(
                    self.scheduler.active_requests(), s_w)
                if active:
                    inputs = self._decode_inputs(active)
        if not active:
            did = self._settle() or did or tight
            if n_preempted:
                self.requests.on_iteration(
                    active=0, waiting=self.scheduler.n_waiting,
                    preempted=n_preempted, kv_stats=self.cache.stats())
            return did
        with self._span("serving.decode", rows=len(active)):
            self._decode_step(active, n_preempted, ahead, *inputs)
        return True

    def _decode_inputs(self, active: List[Request]) -> tuple:
        """What the decode program takes from the host: ``(feed,
        positions, drafts, tables, lengths, base_lens, slots)``, the
        last a tuple that is empty without recurrent state.  ``feed``
        is ``(ids, prev_ids, src)``: a row of the step in flight takes
        its token from that step's picks on the device (``src`` is its
        row there), every other row from ``ids``."""
        s_w = self._spec_window
        b = len(active)
        pad_b = self.max_active
        # the decode window: column 0 is the token each row consumes
        # this step; columns 1..k carry the drafter's proposals (zeros
        # when it has none — the verify mask is causal inside the
        # window, so junk columns cannot influence earlier positions)
        ids = np.zeros((pad_b, s_w), np.int32)
        src = np.full(pad_b, -1, np.int32)
        positions = np.zeros((pad_b, s_w), np.int32)
        drafts: List[List[int]] = []
        # ONE cache visit covers the whole batch: the block-table fetch
        # already reports every row's length, the token of a step in
        # flight included (a dispatch advances it), so the per-row
        # length() round-trips (a lock each) are free
        tables, lengths = self.cache.block_tables_array(
            [r.id for r in active], pad_batch=pad_b)
        base_lens = lengths[:b].astype(np.int64)
        unread = self._inflight.row_of if self._inflight else {}
        for i, req in enumerate(active):
            row = unread.get(req.id)
            if row is not None:
                src[i] = row
            else:
                ids[i, 0] = req.generated[-1]
            d = self._draft_tokens(req) if s_w > 1 else []
            if d:
                ids[i, 1:1 + len(d)] = d
            drafts.append(d)
        positions[:b] = base_lens[:, None] + np.arange(s_w)
        return ((ids, self._last_ids, src), positions, drafts, tables,
                lengths, base_lens,
                self._row_args([r.id for r in active], pad_b))

    def _decode_step(self, active: List[Request], n_preempted: int,
                     ahead: bool, feed, positions, drafts, tables,
                     lengths, base_lens, slots=()) -> None:
        """Dispatch a step for ``active``; then read the step before it
        (``ahead``: this one stays in flight) or this one."""
        if not self._flops_declared:
            # per-token FLOPs vary with context; declared once for the
            # ledger's goodput math, exact FLOPs passed per step below
            telemetry.declare_flops_per_token(
                tfm.decode_flops_per_token(self.cfg, self.cache.block_size))
            # the decode roofline needs the dtype's peak FLOPs/HBM BW
            telemetry.declare_dtype(self.cfg.dtype)
            self._flops_declared = True
        prev = self._inflight
        # the ledger's step is one device program + one commit, nothing
        # else: its span encloses the dispatch and the read that follows
        # it (with a step in flight they are two steps' halves, and
        # still one of each) and closes (LIFO) before delivery and
        # bookkeeping open.  The first step of a run of lookahead reads
        # nothing and is no ledger step; the read that ends the run is
        if prev is not None or not ahead:
            telemetry.step_begin()
        with self._span("serving.decode.dispatch"):
            # the program reads and writes the device-resident pools in
            # place through the block tables (a [B, W] int32 array is
            # all that ships) and hands no K/V back
            try:
                picked, pools, moe = self._on_pools(
                    self._decode, self.params, feed, positions, tables,
                    lengths, *slots, at=3)
            except Exception:
                # the donated pools went with a call that failed after
                # dispatch: the loop's requeue re-prefills into fresh
                # ones
                self.cache.drop_lost_pools()
                raise
            self.cache.adopt_device_pools(*pools)
            # the program wrote the window's K/V at each row's length,
            # and a live row commits its first position or leaves: the
            # lengths are host facts already, so that the next step can
            # be built before this one is read
            self.cache.advance_many([(req.id, 1) for req in active])
            _start_fetch(*picked, *moe)
        step = _DecodeStep(active, drafts, base_lens, n_preempted,
                           prev is not None, picked, moe)
        self._last_ids = step.ids
        if not ahead:
            self._read_step(step)
            return
        self._inflight = step
        for req in active:
            # a row that ends by count with this step is known to now:
            # it is out of the next step, and its place is the next
            # prefill's, as if this step had been read
            if (req.n_generated + (req.id in prev.row_of if prev else 0)
                    + 1 >= req.max_new_tokens):
                self.scheduler.retire(req)
        if prev is not None:
            try:
                self._read_step(prev)
            except Exception:
                # tokens after ones that were lost are no output
                self._inflight = None
                raise

    def _settle(self) -> bool:
        """Read the step in flight, if there is one: afterwards every
        request's ``generated`` is current.  Whatever needs that calls
        this first: eviction, the crash requeue, a step without
        lookahead, a loop that stops."""
        step, self._inflight = self._inflight, None
        if step is None:
            return False
        with self._span("serving.decode", rows=len(step.rows)):
            telemetry.step_begin()
            self._read_step(step)
        return True

    def _read_step(self, step: _DecodeStep) -> None:
        """Fetch a dispatched step's picks and commit, deliver and
        account them.  Closes the ledger step the caller opened."""
        s_w = self._spec_window
        active, drafts, base_lens = step.rows, step.drafts, step.base_lens
        b = len(active)
        compute = telemetry.compute
        with self._span("serving.decode.fetch") as crossed:
            # the wait for the step (the next one, if dispatched, runs
            # behind it) and the link: a [B, S] int32, a [B, S] bool
            # and the routing counts
            amax = np.asarray(step.ids)
            fin = np.asarray(step.finite)
            moe = [np.asarray(m) for m in step.moe]
            crossed["bytes"] = sum(a.nbytes for a in [amax, fin] + moe)
        telemetry.inc("serving", "decode_d2h_bytes", crossed["bytes"])
        self._count_moe(moe)
        # per-sequence numeric health: a non-finite logit row (NaN/Inf
        # from a poisoned cache page or an overflowed activation) would
        # serve garbage silently.  The program checks the picked
        # position only, which is sufficient — argmax lands on the
        # first NaN (NaN propagates through maximum) and an all--inf
        # row argmaxes to -inf.  Fail exactly that request with a clear
        # error; the rest of the batch (and the engine) keep serving.
        #
        # Longest-accepted-prefix commit walk: window position s emits
        # the program's pick at s; the walk continues past s only while
        # the drafted token MATCHES that pick, so the committed output
        # is bit-identical to single-token greedy decoding —
        # speculation can change only how many tokens land per step,
        # never which.
        n_tokens = 0
        n_proposed = 0
        n_accepted = 0
        n_discarded = 0
        with self._span("serving.decode.commit"):
            # the walk touches only python ints
            outcomes = []
            for i, req in enumerate(active):
                if req.state != ACTIVE:
                    # it ended (eos, a non-finite row) in the step
                    # before this one, which was read after this
                    # one was dispatched with it: the token is no
                    # output, and its K/V went into blocks that
                    # were the row's until that read freed them
                    n_discarded += 1
                    continue
                draft = drafts[i]
                n_proposed += len(draft)
                n_row = 0
                fail = False
                done = False
                for s in range(1 + len(draft)):
                    if not fin[i, s]:
                        telemetry.inc("serving", "nonfinite_failures")
                        logger.error(
                            "request %d produced non-finite logits "
                            "at decode position %d", req.id,
                            int(base_lens[i]) + s)
                        fail = True
                        break
                    next_id = int(amax[i, s])
                    req.generated.append(next_id)
                    n_row += 1
                    if req.is_finished_by(next_id):
                        done = True
                        break
                    if s < len(draft) and draft[s] == next_id:
                        n_accepted += 1
                        continue
                    break
                outcomes.append((req, i, n_row, fail, done))
                n_tokens += n_row
            # the dispatch advanced every row by its first position; a
            # verify window's accepted drafts are the rest (contiguous
            # by construction, in ONE batched cache visit; a rejected
            # draft's slots stay garbage past the length).  Must land
            # before any _finish below — finishing frees blocks.
            if s_w > 1:
                self.cache.advance_many(
                    [(req.id, n_row - 1)
                     for req, _, n_row, _, _ in outcomes if n_row > 1])
            for req, i, n_row, fail, done in outcomes:
                if n_row:
                    self.requests.on_token(req.id, n=n_row)
        # executed FLOPs: every window position runs the full forward
        # whether or not its token commits (verify is the price of
        # speculation; MFU is accounted on work actually executed).
        # Context depths repeat heavily across rows and steps, so the
        # per-token figure is memoized (engine-thread-confined cache)
        fpt_at = self._fpt_cache
        flops = 0.0
        for i in range(b):
            base = int(base_lens[i])
            for s in range(s_w):
                c = base + s + 1
                f = fpt_at.get(c)
                if f is None:
                    f = fpt_at[c] = tfm.decode_flops_per_token(self.cfg, c)
                flops += f
        stats_fn = getattr(self._decode, "stats", None)
        cost = stats_fn() if stats_fn else None
        telemetry.step_end(
            tokens=float(n_tokens), flops=flops,
            bytes_accessed=(cost["last_cost"] or {}).get("bytes_accessed")
            if cost else None,
            tokens_per_step=n_tokens / b if b else None,
            spec_accept_rate=(n_accepted / n_proposed
                              if n_proposed else None))
        # completion delivery happens AFTER step_end: waking a blocked
        # handler thread (and everything it does with the core next) is
        # response streaming, not decode work — the step ledger's wall
        # must cover the device program + the commit, nothing else
        with self._span("serving.decode.deliver"):
            for req, _i, _n, fail, done in outcomes:
                if fail:
                    self._finish(
                        req, error="non-finite logits during decode "
                        "(numeric corruption); retry the request",
                        reason="nonfinite")
                elif done:
                    self._finish(req)
        with self._span("serving.decode.bookkeeping"):
            # counted where paged_decode_steps is, so that a window's
            # edge cuts both alike
            if step.overlapped:
                telemetry.inc("serving", "decode_steps_overlapped")
            if n_discarded:
                telemetry.inc("serving", "lookahead_discarded_tokens",
                              n_discarded)
            self._decode_bookkeeping(b, n_tokens, n_proposed, n_accepted,
                                     step.n_preempted, cost, base_lens)

    def _decode_bookkeeping(self, b: int, n_tokens: int, n_proposed: int,
                            n_accepted: int, n_preempted: int,
                            cost, base_lens) -> None:
        """Counters, gauges and the ledgers' per-iteration records."""
        s_w = self._spec_window
        compute = telemetry.compute
        kv_stats = self.cache.stats()
        if n_tokens:
            telemetry.inc("serving", "tokens_generated", n_tokens)
        telemetry.inc("serving", "decode_steps")
        telemetry.observe("serving", "decode_batch", b)
        telemetry.inc("serving", "paged_decode_steps")
        if self._state_rw_bytes:
            telemetry.inc("serving", "state_slot_steps", b)
            telemetry.inc("serving", self._state_bytes_counter,
                          self._state_rw_bytes * b)
        if self.cache.ring_blocks:
            # a row at length n attends its n keys and the token itself
            keys = base_lens + 1
            telemetry.inc("serving", "attn_full_ctx_tokens",
                          float(keys.sum()))
            telemetry.inc("serving", "attn_sliding_ctx_tokens", float(
                np.minimum(keys, self.cache.sliding_window).sum()))
            for name, key in (("kv_block_steps", "blocks_in_use"),
                              ("kv_sliding_block_steps",
                               "sliding_blocks_in_use"),
                              ("kv_cached_token_steps", "cached_tokens")):
                telemetry.inc("serving", name, kv_stats[key])
        if s_w > 1:
            telemetry.inc("serving", "spec_proposed", n_proposed)
            telemetry.inc("serving", "spec_accepted", n_accepted)
            if n_proposed:
                telemetry.set_gauge("serving", "spec_accept_rate",
                                    100.0 * n_accepted / n_proposed)
            telemetry.observe("serving", "spec_tokens_per_step",
                              n_tokens / b)
        if cost:
            telemetry.set_gauge("serving", "decode_signatures",
                                cost["signatures"])
        # HBM peak tracking needs only periodic samples; on a ~1 ms
        # fast-path decode step the per-step device memory-stats query
        # was a measurable tax, so sample every 8th iteration (and the
        # first, so short runs still record a peak)
        self._hbm_tick += 1
        if compute.enabled() and (self._hbm_tick - 1) % 8 == 0:
            compute.sample_hbm()
        # the decode ledger's per-iteration record: batch composition +
        # admission queue depth + KV pressure — the /requests load
        # signal a router/autoscaler consumes — then a throttled SLO
        # burn-rate evaluation on fresh evidence.  tokens counts what
        # actually landed (a nonfinite-guarded row produced none; an
        # accepted draft lands several)
        self.requests.on_iteration(
            active=b, waiting=self.scheduler.n_waiting,
            preempted=n_preempted, tokens=n_tokens, kv_stats=kv_stats)
        self.availability.note_tokens(n_tokens)
        self.slo.maybe_evaluate()

    # ---- observability --------------------------------------------------
    def stats(self) -> dict:
        active, waiting = self.scheduler.counts()
        return {
            "active": active,
            "waiting": waiting,
            "max_active": self.max_active,
            "draining": self.draining,
            "kv": self.cache.stats(),
            "ledger": telemetry.ledger().summary(),
            "requests": self.requests.summary(),
            "slo_active": self.slo.active(),
            "availability": self.availability.report(),
        }
