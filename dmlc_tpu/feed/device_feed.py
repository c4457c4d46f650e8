"""Sharded device feeds.

Design (TPU-first):
  * each data-bearing mesh coordinate (dp, sp) maps to one InputSplit
    partition: part_index = dp * sp_size + sp (the same
    part_index/num_parts contract as the reference's InputSplit,
    src/io/input_split_base.cc:30-64, lifted onto the mesh);
  * batches are packed into STATIC shapes (pad/truncate) so XLA compiles
    one program — no data-dependent shapes;
  * DMLC_FEED_WORKERS parser threads each write their partitions' batches
    straight into their slice of a pooled staging buffer
    (concurrency.BufferPool), so global-batch assembly allocates nothing
    and never concatenates;
  * each host shard is placed on its own addressable device
    (jax.device_put per device + make_array_from_single_device_arrays
    against the mesh NamedSharding) instead of round-tripping through one
    global host array, and DMLC_FEED_DEPTH staging buffers double-buffer
    the pipeline so step N's parse overlaps step N-1's transfer;
  * throughput is logged every 10 MB like the reference's iterators
    (src/data/basic_row_iter.h:68-75).

Batch-borrowing contract: a partition iterator's yielded dict is only
read BETWEEN the yield and the next ``next()`` call on that same
iterator — the feed copies it into the staging buffer immediately — so
iterators may reuse one output buffer per step (the in-repo feeds do;
see recordio_packed_feed) instead of allocating fresh arrays on the hot
path.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from queue import Empty, Queue
from typing import Dict, Iterator, List, Optional

import numpy as np

from ..base import check, get_env
from ..concurrency import BufferPool, make_rlock
from ..parallel.mesh import AXIS_DP, AXIS_SP, addressable_shards, \
    mesh_config


class _ProducerError:
    """Wraps a producer-thread exception for re-raise on the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def pack_rowblock(blk, batch_size: int, max_nnz: int, num_col: int = 0,
                  out: Optional[Dict[str, np.ndarray]] = None):
    """RowBlock (CSR) → fixed-shape dense-index batch dict.

    Returns {label [B], value [B,K], index [B,K], mask [B,K]} float32/int32,
    rows padded (mask 0) or truncated to K = max_nnz.  Static shapes keep
    XLA from recompiling per batch.  When num_col > 0, feature indices are
    clamped to [0, num_col) so downstream gathers into a [num_col] weight
    vector are always in bounds.

    ``out`` (same keys/shapes/dtypes as the return value) is filled in
    place and returned, so a hot loop that copies batches onward anyway
    — the DeviceFeed staging pipeline — reuses one output buffer per
    iterator instead of allocating four arrays per batch.

    Hot path: the whole pad-pack runs in ONE native call
    (``dmlc_pad_pack_csr``, cpp/dmlc_native.cc) writing the four arrays
    in place; the numpy broadcast-gather below is the bit-identical
    fallback (``DMLC_TPU_DISABLE_NATIVE=1``).
    """
    if out is None:
        out = {"label": np.empty(batch_size, np.float32),
               "value": np.empty((batch_size, max_nnz), np.float32),
               "index": np.empty((batch_size, max_nnz), np.int32),
               "mask": np.empty((batch_size, max_nnz), np.float32)}
    label, value = out["label"], out["value"]
    index, mask = out["index"], out["mask"]
    b = min(batch_size, blk.size)
    _expect = (("label", np.float32, (batch_size,)),
               ("value", np.float32, (batch_size, max_nnz)),
               ("index", np.int32, (batch_size, max_nnz)),
               ("mask", np.float32, (batch_size, max_nnz)))
    if all(out[k].flags["C_CONTIGUOUS"] and out[k].dtype == dt
           and out[k].shape == shp for k, dt, shp in _expect):
        from .. import native

        if native.pad_pack_csr(blk.label[:b], blk.offset[: b + 1],
                               blk.index, blk.value, b, batch_size,
                               max_nnz, num_col, out):
            return out
    label[b:] = 0
    label[:b] = blk.label[:b]
    src_val = np.asarray(blk.value)
    src_idx = np.asarray(blk.index)
    if b == 0 or src_val.size == 0:
        value[:] = 0
        index[:] = 0
        mask[:] = 0
        return out
    # vectorized CSR -> padded batch via a broadcast GATHER (each cell
    # reads offset[row] + column, masked past the row length) — no
    # per-row Python loop, no fancy scatter
    offsets = np.asarray(blk.offset[: b + 1], np.int64)
    lens = np.diff(offsets)
    ar = np.arange(max_nnz, dtype=np.int64)
    sel = ar[None, :] < lens[:, None]                        # [b, K]
    src = np.minimum(offsets[:-1, None] + ar[None, :], src_val.size - 1)
    value[b:] = 0
    # masked cells are WRITTEN zero, not multiplied to zero: the clamped
    # gather reads neighboring rows' values, and NaN/Inf * 0 = NaN would
    # leak garbage into padding (and diverge from the native path)
    value[:b] = np.where(sel, src_val[src], np.float32(0))
    index[b:] = 0
    index[:b] = src_idx[src]
    index[:b] *= sel
    mask[b:] = 0
    mask[:b] = sel
    if num_col > 0:
        np.minimum(index, num_col - 1, out=index)
    return out


class _StagingBuf:
    """One pooled global host batch: per-key arrays of shape
    ``(n_parts * per_part_dim0, *rest)``.  A drained partition's slice
    is simply left stale — placement substitutes a cached device-resident
    zero shard, so nothing ever reads it."""

    __slots__ = ("bufs",)

    def __init__(self, template: Dict[str, np.ndarray], n_parts: int):
        self.bufs = {
            k: np.empty((n_parts * v.shape[0],) + v.shape[1:], v.dtype)
            for k, v in template.items()
        }


class _Slot:
    """A staging buffer bound to one pipeline step: complete (ready to
    place) once every parser worker has checked its partitions in."""

    __slots__ = ("step", "sbuf", "alive", "workers_left", "done")

    def __init__(self, step: int, sbuf: _StagingBuf, n_parts: int,
                 n_workers: int):
        self.step = step
        self.sbuf = sbuf
        self.alive = np.zeros(n_parts, bool)
        self.workers_left = n_workers
        self.done = False


class DeviceFeed:
    """Assemble per-partition host batches into one sharded global array.

    ``part_sources``: list of iterator FACTORIES (one per data partition,
    in mesh part_index order), each returning a fresh host-side iterator
    of dicts of equal-shaped np arrays.  Fresh iterators are created at
    the start of every epoch, so one feed serves multi-epoch training
    (iterate it again after exhaustion).  Plain iterators are accepted
    for single-epoch use.  Batches are stacked on the leading axis and
    placed with a NamedSharding over the data axes, so the leading dim
    of the global batch is n_parts * per_part_batch.

    Pipeline: ``num_workers`` (DMLC_FEED_WORKERS) threads parse
    partitions — worker w owns partitions ``p ≡ w (mod W)``, so each
    partition's batch order is preserved — writing every batch directly
    into its slice of a pooled staging buffer; a placer thread ships
    completed buffers shard-by-shard to their addressable devices and
    recycles them through a ``queue_depth`` (DMLC_FEED_DEPTH) deep
    BufferPool, overlapping parse with transfer.

    Every yielded batch carries a ``parts_alive`` float32 host array of
    shape ``[n_parts]``: 1.0 where the partition contributed real rows,
    0.0 where a drained partition was padded with (cached, pre-placed)
    zero shards — consumers down-weight epoch-tail padding with it.

    Elasticity: instead of explicit ``part_sources``, pass a
    ``source_builder(part_index, num_parts) -> factory`` plus
    ``world=(rank, world_size)`` — this process then reads global
    partitions ``rank*n_local + lp`` of ``world_size*n_local`` (the
    InputSplit byte-range contract makes that deterministic for any
    world size), and :meth:`resize` re-partitions the feed in place
    when the world changes under a run.
    """

    def __init__(self, mesh, part_sources=None, *,
                 queue_depth: Optional[int] = None,
                 axes=(AXIS_DP, AXIS_SP), log_every_mb: int = 10,
                 num_workers: int = 0, source_builder=None,
                 world=None):
        import jax

        if queue_depth is not None:
            # the staging pool must be bounded; the pre-pipeline
            # queue_depth=0 "unbounded queue" spelling is gone
            check(queue_depth >= 1,
                  f"queue_depth must be >= 1, got {queue_depth}")

        self.mesh = mesh
        cfg = mesh_config(mesh)
        n_parts = 1
        for a in axes:
            n_parts *= cfg.axis_size(a)
        self._n_parts = n_parts
        self._source_builder = source_builder
        # dmlc-check: unguarded(consumer-thread epoch/resize state; close() joins first)
        self._world = self._check_world(world) if world is not None \
            else (0, 1)
        if part_sources is None:
            check(source_builder is not None,
                  "DeviceFeed needs part_sources or a source_builder")
            part_sources = self._build_sources()
        check(len(part_sources) == n_parts,
              f"need {n_parts} partition sources, got {len(part_sources)}")
        # dmlc-check: unguarded(consumer-thread epoch/resize state; close() joins first)
        self._multi_epoch = all(callable(s) for s in part_sources)
        # dmlc-check: unguarded(consumer-thread epoch/resize state; close() joins first)
        self._sources = part_sources
        # dmlc-check: unguarded(consumer-thread epoch state)
        self._epochs_started = 0
        self.sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(axes)
        )
        # dmlc-check: unguarded(autotuned between epochs before Thread.start publishes)
        self._depth = (queue_depth if queue_depth is not None
                       else max(1, get_env("DMLC_FEED_DEPTH", 2)))
        # dmlc-check: unguarded(autotuned between epochs before Thread.start publishes)
        self._workers = max(1, min(n_parts, num_workers
                            or get_env("DMLC_FEED_WORKERS",
                                       min(4, os.cpu_count() or 2))))
        # post-placement batch hook (producer side): recordio_feed's
        # packed-transport mode installs its on-device expander here
        # (it needs the constructed feed's sharding, so it cannot be a
        # constructor argument)
        self._transform = None
        # pinned staging-pool footprint (feed_staging_pool_bytes gauge)
        # dmlc-check: unguarded(advisory gauge; reset precedes parser threads)
        self._staging_bytes = 0
        # ledger-driven auto-tuning: when DMLC_FEED_AUTOTUNE=1, the
        # controller watches the step ledger's feed-wait fraction and
        # re-sizes workers/depth within bounds at every epoch boundary
        # (worker→partition assignment is w mod W, so W may only change
        # between epochs without breaking per-partition batch order).
        # The ledger's feed-wait is a property of the TRAINING STEP, so
        # the signal assumes this is the one feed the ledgered loop
        # consumes — with several concurrently-autotuned feeds, each
        # would adapt to wait the others caused (enable the knob for
        # the training feed only)
        self._autotuner = None
        if get_env("DMLC_FEED_AUTOTUNE", False):
            from .autotune import FeedAutotuner

            wmax = get_env("DMLC_FEED_WORKERS_MAX", 0) or \
                (os.cpu_count() or 2)
            self._autotuner = FeedAutotuner(
                workers=self._workers, depth=self._depth,
                min_workers=max(1, get_env("DMLC_FEED_WORKERS_MIN", 1)),
                max_workers=max(1, min(n_parts, wmax)),
                max_depth=max(self._depth,
                              get_env("DMLC_FEED_DEPTH_MAX", 4)))
            # dmlc-check: unguarded(consumer-thread epoch-boundary cursor)
            self._ledger_seen_seq = 0
        # dmlc-check: unguarded(thread-safe Queue; rebound between epochs pre-start)
        self._queue: Queue = Queue(maxsize=self._depth)
        # dmlc-check: unguarded(rebuilt between epochs; each iterator read by its one owning worker)
        self.part_iters: list = []
        # dmlc-check: unguarded(per-cell owner-worker reads; mutated under _cv)
        self._part_done = [False] * n_parts
        # dmlc-check: unguarded(mutation under _cv; epoch reset pre-start)
        self._n_dead = 0
        # dmlc-check: unguarded(write-once under _cv; read only after _checkin_slot saw it locked)
        self._template: Optional[Dict[str, np.ndarray]] = None
        # dmlc-check: unguarded(thread-safe BufferPool; rebound between epochs pre-start)
        self._pool: Optional[BufferPool] = None
        # dmlc-check: unguarded(accesses under _cv; rebound between epochs pre-start)
        self._pending: Dict[int, _Slot] = {}
        self._cv = threading.Condition(make_rlock("DeviceFeed._cv"))
        # dmlc-check: unguarded(under _cv; cancel polls are stale-tolerant)
        self._error: Optional[BaseException] = None
        # dmlc-check: unguarded(set/read under _cv; epoch reset pre-start)
        self._empty_epoch = False
        # dmlc-check: unguarded(consumer-thread lifecycle; joined before rebinding)
        self._thread: Optional[threading.Thread] = None  # placer
        # dmlc-check: unguarded(consumer-thread lifecycle; joined before rebinding)
        self._parsers: List[threading.Thread] = []
        self._stop = threading.Event()
        # dmlc-check: unguarded(placer-thread-confined cache)
        self._shard_maps: Dict[str, list] = {}
        # dmlc-check: unguarded(placer-thread-confined cache)
        self._zero_shards: Dict[tuple, object] = {}
        # dmlc-check: unguarded(placer-thread-confined lazy probe)
        self._host_aliasing: Optional[bool] = None
        self._log_every = log_every_mb << 20
        # dmlc-check: unguarded(placer-thread writes; bytes_fed is a stale-tolerant monitor read)
        self._bytes = 0
        # dmlc-check: unguarded(placer-thread-confined)
        self._last_log = 0
        # dmlc-check: unguarded(placer-thread-confined)
        self._t0 = None

    # ---- parser workers ------------------------------------------------
    def _fail(self, exc: BaseException) -> None:
        with self._cv:
            if self._error is None:
                self._error = exc
            self._cv.notify_all()
        self._stop.set()
        if self._pool is not None:
            self._pool.kill()

    def _parse_part(self, p: int):
        """Next batch of partition ``p`` (None once drained).  Sets the
        feed-wide template from the first batch ever seen."""
        from .. import telemetry

        if self._part_done[p]:
            return None
        with telemetry.span("feed.parse", stage="feed", args={"part": p}):
            batch = next(self.part_iters[p], None)
        if batch is None:
            with self._cv:
                self._part_done[p] = True
                self._n_dead += 1
                if self._n_dead == self._n_parts:
                    self._cv.notify_all()
            return None
        if self._template is None:
            with self._cv:
                if self._template is None:
                    self._template = {
                        k: np.zeros_like(v) for k, v in batch.items()
                    }
                    self._cv.notify_all()
        return batch

    def _checkin_slot(self, step: int) -> Optional[_Slot]:
        """The staging slot for ``step``, creating it from the pool if
        this worker arrives first.  None on stop/error/empty epoch."""
        from .. import telemetry

        with self._cv:
            while self._template is None:
                # nothing parsed yet anywhere: either another worker is
                # about to set the template, or the whole epoch is empty
                if self._error is not None or self._stop.is_set():
                    return None
                if self._n_dead == self._n_parts:
                    self._empty_epoch = True
                    self._cv.notify_all()
                    return None
                self._cv.wait(0.1)
            slot = self._pending.get(step)
        if slot is not None:
            return slot
        # stage stall: parsing ran ahead of the transfer pipeline and is
        # waiting for a staging buffer to come back from the placer.
        # The acquire must stay a poll loop: while this worker waits,
        # another worker may create this very step's slot with the last
        # free buffer — blocking without re-checking _pending deadlocks.
        t0 = time.perf_counter()
        try:
            while True:
                sbuf = self._pool.acquire(timeout=0.05)
                if sbuf is not None:
                    break
                if self._stop.is_set() or self._error is not None:
                    return None
                with self._cv:
                    slot = self._pending.get(step)
                if slot is not None:
                    return slot
        finally:
            telemetry.observe_duration("feed", "stage_stall",
                                       time.perf_counter() - t0)
        with self._cv:
            slot = self._pending.get(step)
            if slot is not None:  # another worker won the race
                self._pool.release(sbuf)
                return slot
            slot = _Slot(step, sbuf, self._n_parts, self._workers)
            self._pending[step] = slot
            return slot

    def _write_part(self, slot: _Slot, p: int, batch) -> None:
        from .. import telemetry

        sbuf = slot.sbuf
        if batch is None:
            return  # drained: placement serves a cached zero shard
        with telemetry.span("feed.stage", stage="feed", args={"part": p}):
            for k, t in self._template.items():
                d0 = t.shape[0]
                dst = sbuf.bufs[k][p * d0:(p + 1) * d0]
                src = batch[k]
                check(dst.shape == src.shape and dst.dtype == src.dtype,
                      f"partition {p} batch key '{k}' is "
                      f"{src.shape}/{src.dtype}, expected "
                      f"{dst.shape}/{dst.dtype}")
                np.copyto(dst, src)
        slot.alive[p] = True

    # ---- placer --------------------------------------------------------
    def _shard_map(self, key: str) -> list:
        m = self._shard_maps.get(key)
        if m is None:
            shape = self._staging_shape(key)
            m = addressable_shards(self.sharding, shape)
            self._shard_maps[key] = m
        return m

    def _staging_shape(self, key: str) -> tuple:
        t = self._template[key]
        return (self._n_parts * t.shape[0],) + t.shape[1:]

    def _place(self, slot: _Slot) -> Dict[str, "object"]:
        """Per-shard placement: each partition's slice goes straight to
        its addressable device(s); drained partitions reuse a cached,
        already-placed zero shard (no bytes shipped for padding)."""
        import jax

        if self._host_aliasing is None:
            # jax's CPU backend zero-copies device_put of an aligned
            # host array: the "device" buffer IS the staging memory, so
            # recycling the staging buffer would mutate already-yielded
            # batches.  Accelerator backends DMA a real copy and keep
            # the zero-copy hand-off.
            self._host_aliasing = jax.devices()[0].platform == "cpu"
        out = {}
        for k, t in self._template.items():
            d0 = t.shape[0]
            buf = slot.sbuf.bufs[k]
            arrs = []
            for pos, (dev, idx) in enumerate(self._shard_map(k)):
                p = (idx[0].start or 0) // d0
                if slot.alive[p]:
                    src = buf[idx]
                    if self._host_aliasing:
                        src = src.copy()
                    arrs.append(jax.device_put(src, dev))
                else:
                    z = self._zero_shards.get((k, pos))
                    if z is None:
                        z = jax.device_put(np.zeros_like(buf[idx]), dev)
                        self._zero_shards[(k, pos)] = z
                    arrs.append(z)
            out[k] = jax.make_array_from_single_device_arrays(
                buf.shape, self.sharding, arrs)
        return out

    def _place_loop(self) -> None:
        import jax

        from .. import telemetry

        self._t0 = time.perf_counter()
        step = 0
        try:
            while True:
                with telemetry.span("feed.assemble", stage="feed"), \
                        self._cv:
                    # "assembly" = waiting for the parser workers to
                    # complete this step's staging buffer
                    while not (self._error is not None
                               or self._empty_epoch
                               or (step in self._pending
                                   and self._pending[step].done)):
                        if self._stop.is_set():
                            return
                        self._cv.wait(0.1)
                    if self._error is not None:
                        raise self._error
                    if self._empty_epoch:
                        slot = None
                    else:
                        slot = self._pending.pop(step)
                if slot is None or not slot.alive.any():
                    # every partition drained: end of epoch
                    self._stop.set()
                    if self._pool is not None:
                        self._pool.kill()  # wake workers parked ahead
                    self._queue.put(None)
                    return
                with telemetry.span("feed.place", stage="feed"), \
                        telemetry.annotate("dmlc_feed_batch"), \
                        telemetry.timed("feed", "device_put"):
                    dev = self._place(slot)
                dev["parts_alive"] = slot.alive.astype(np.float32)
                if self._transform is not None:
                    # e.g. the padded feed's on-device expansion: runs
                    # on this placer thread so it overlaps the
                    # consumer's step, and the staging recycle below
                    # still waits on the PRE-transform arrays it fed
                    staged = dev
                    dev = self._transform(staged)
                else:
                    staged = dev
                # count bytes actually shipped: drained partitions ride
                # cached device-resident zero shards, not the link
                nbytes = (sum(v.nbytes // self._n_parts
                              for v in slot.sbuf.bufs.values())
                          * int(slot.alive.sum()))
                self._bytes += nbytes
                telemetry.inc("feed", "batches")
                telemetry.inc("feed", "bytes_to_device", nbytes)
                if self._bytes - self._last_log >= self._log_every:
                    dt = time.perf_counter() - self._t0
                    from ..logging import info

                    info(
                        f"feed: {self._bytes / 1e6:.0f} MB to device, "
                        f"{self._bytes / 1e6 / dt:.2f} MB/sec"
                    )
                    self._last_log = self._bytes
                # a full queue means the consumer is the bottleneck
                with telemetry.timed("feed", "producer_stall"):
                    self._queue.put(dev)
                # the transfers must land before the staging buffer is
                # recycled for a later step (device arrays never alias
                # host staging memory after this point)
                jax.block_until_ready(
                    [staged[k] for k in self._template.keys()])
                self._pool.release(slot.sbuf)
                step += 1
        except BaseException as e:  # surface on the consumer side
            self._fail(e)
            self._queue.put(_ProducerError(e))

    # ---- consumer ------------------------------------------------------
    def __iter__(self) -> Iterator[Dict[str, "object"]]:
        threads = ([self._thread] if self._thread else []) + self._parsers
        for t in threads:
            # A pipeline that already delivered its None sentinel is done
            # but may not have exited yet; give it a moment rather than
            # spuriously refusing an immediate epoch restart.
            t.join(timeout=2.0)
            if t.is_alive():
                raise RuntimeError(
                    "previous DeviceFeed epoch still in flight: exhaust "
                    "the iterator or close() before starting a new epoch"
                )
        self._thread = None
        self._parsers = []
        if self._epochs_started > 0 and not self._multi_epoch:
            raise RuntimeError(
                "DeviceFeed built from plain iterators is single-epoch: "
                "pass iterator factories (callables) for multi-epoch use"
            )
        self._epochs_started += 1
        self._apply_autotune()
        self.part_iters = [s() if callable(s) else s for s in self._sources]
        self._part_done = [False] * self._n_parts
        self._n_dead = 0
        self._pending = {}
        self._error = None
        self._empty_epoch = False
        self._queue = Queue(maxsize=self._depth)
        self._stop.clear()
        # dmlc-check: unguarded(advisory gauge; reset precedes parser threads)
        self._staging_bytes = 0
        self._pool = BufferPool(
            functools.partial(self._make_staging), capacity=self._depth)
        self._parsers = [
            threading.Thread(target=self._parser_worker, args=(w,),
                             daemon=True)
            for w in range(self._workers)
        ]
        for t in self._parsers:
            t.start()
        self._thread = threading.Thread(target=self._place_loop,
                                        daemon=True)
        self._thread.start()
        from .. import telemetry

        while True:
            # an empty queue means the producer is the bottleneck.  The
            # feed.wait span is the CONSUMER-thread record of this wait:
            # it is what the step ledger (telemetry.steps) bills as a
            # step's feed-wait share, since the producer-side
            # parse/stage/place spans run overlapped on other threads
            # and do not cost the step anything
            with telemetry.span("feed.wait", stage="feed"), \
                    telemetry.timed("feed", "consumer_stall"):
                item = self._queue.get()
            if item is None:
                return
            if isinstance(item, _ProducerError):
                raise item.exc
            yield item

    def _make_staging(self) -> _StagingBuf:
        from .. import telemetry

        sbuf = _StagingBuf(self._template, self._n_parts)
        # host-side half of the memory ledger: the compute HBM gauges
        # cover device memory, this covers the pinned staging pool
        # dmlc-check: unguarded(advisory gauge; GIL-atomic int accumulate)
        self._staging_bytes += sum(a.nbytes for a in sbuf.bufs.values())
        telemetry.set_gauge("feed", "staging_pool_bytes",
                            self._staging_bytes)
        return sbuf

    # ---- ledger-driven auto-tuning -------------------------------------
    def _apply_autotune(self) -> None:
        """Epoch-boundary controller step: feed the StepLedger's recent
        feed-wait fraction to the FeedAutotuner and apply its
        (workers, depth) decision before the pipeline threads spawn.
        Worker count changes re-map partitions (w mod W) for the FRESH
        epoch only; depth changes re-size the staging pool, which is
        rebuilt per epoch anyway."""
        if self._autotuner is None:
            return
        from .. import telemetry

        recs, last = telemetry.ledger().records_since(
            self._ledger_seen_seq)
        walls = sum(r["wall_s"] for r in recs)
        if len(recs) < self._autotuner.window or walls <= 0:
            # too thin to decide — do NOT advance the seen-seq, so
            # short epochs (fewer steps than the window) accumulate
            # evidence across boundaries instead of discarding it
            telemetry.set_gauge("feed", "autotune_workers", self._workers)
            telemetry.set_gauge("feed", "autotune_depth", self._depth)
            return
        self._ledger_seen_seq = last
        fw = sum(r["feed_wait_s"] for r in recs) / walls
        workers, depth = self._autotuner.observe(fw)
        workers = max(1, min(self._n_parts, workers))
        if workers != self._workers or depth != self._depth:
            from ..logging import info

            info(f"feed autotune: feed-wait {fw:.2f} over {len(recs)} "
                 f"steps -> workers {self._workers}->{workers}, "
                 f"depth {self._depth}->{depth}")
            telemetry.inc("feed", "autotune_adjustments")
            self._workers = workers
            self._depth = depth
        telemetry.set_gauge("feed", "autotune_workers", self._workers)
        telemetry.set_gauge("feed", "autotune_depth", self._depth)

    # ---- elastic repartition -------------------------------------------
    @staticmethod
    def _check_world(world) -> tuple:
        rank, wsize = world
        check(wsize >= 1 and 0 <= rank < wsize,
              f"world must be (rank, world_size) with 0 <= rank < "
              f"world_size, got {world}")
        return (int(rank), int(wsize))

    def _build_sources(self) -> list:
        rank, wsize = self._world
        total = wsize * self._n_parts
        return [self._source_builder(rank * self._n_parts + lp, total)
                for lp in range(self._n_parts)]

    @property
    def world(self) -> tuple:
        return self._world

    def resize(self, world) -> None:
        """Elastic repartition: rebuild the per-partition iterators for
        a new ``(rank, world_size)`` in place.

        The in-flight epoch is abandoned (its partial coverage is
        superseded — on a resize the trainer restores from the last
        checkpoint anyway); the next iteration starts a FRESH epoch
        whose partitions tile the dataset exactly once under the new
        byte-range split.  The local mesh (and so per-batch shapes,
        staging pools, shard maps, cached zero shards) is untouched —
        only the global partition ids change."""
        from .. import telemetry

        check(self._source_builder is not None,
              "this feed was built from explicit part_sources; elastic "
              "resize needs a source_builder (the recordio_/libsvm_ "
              "feed factories provide one)")
        world = self._check_world(world)
        old = self._world
        self.close()
        self._world = world
        self._sources = self._build_sources()
        self._multi_epoch = True
        telemetry.inc("feed", "resizes")
        telemetry.record_event("feed_resized", old_world=list(old),
                               world=list(world),
                               local_parts=self._n_parts)

    def _parser_worker(self, w: int) -> None:
        my_parts = list(range(w, self._n_parts, self._workers))
        step = 0
        try:
            while not self._stop.is_set():
                # parse first, then stage: the slot (and the staging
                # shapes) only exist once SOME batch defined the template
                produced = {p: self._parse_part(p) for p in my_parts}
                slot = self._checkin_slot(step)
                if slot is None:
                    return
                for p in my_parts:
                    self._write_part(slot, p, produced[p])
                with self._cv:
                    slot.workers_left -= 1
                    if slot.workers_left == 0:
                        slot.done = True
                        self._cv.notify_all()
                step += 1
        except BaseException as e:  # noqa: BLE001 - surfaced to consumer
            self._fail(e)

    def close(self):
        self._stop.set()
        if self._pool is not None:
            self._pool.kill()
        with self._cv:
            self._cv.notify_all()
        # drain so a placer blocked on a full queue can observe the stop
        # flag, then actually join it — close() must leave no live thread
        threads = ([self._thread] if self._thread else []) + self._parsers
        deadline = time.monotonic() + 5.0
        while (any(t.is_alive() for t in threads)
               and time.monotonic() < deadline):
            while not self._queue.empty():
                try:
                    self._queue.get_nowait()
                except Empty:
                    break  # racing consumer drained it first
            for t in threads:
                t.join(timeout=0.05)
        if not any(t.is_alive() for t in threads):
            self._thread = None
            self._parsers = []
        else:
            # keep _thread set so __iter__'s in-flight guard still
            # refuses to start a second pipeline over live shared state
            from ..logging import warning

            warning(
                "DeviceFeed.close(): pipeline thread still alive after "
                "5s (likely a hung device_put); leaking a daemon thread")

    @property
    def bytes_fed(self) -> int:
        return self._bytes


def libsvm_feed(uri: str, mesh, *, batch_size: int, max_nnz: int,
                fmt: str = "libsvm", queue_depth: Optional[int] = None,
                world=None) -> DeviceFeed:
    """Sparse text formats (libsvm/csv/libfm) → sharded padded-CSR batches.

    ``batch_size`` is per partition; the global leading dim is
    batch_size * dp_size * sp_size.  ``world=(rank, world_size)``
    partitions across an elastic multi-process world (resizable via
    :meth:`DeviceFeed.resize`).

    LibSVM URIs without a ``#cachefile`` take the fused native path:
    one ``dmlc_parse_libsvm_into`` call per (chunk window, batch)
    tokenizes the text AND writes the padded batch arrays in place —
    no intermediate CSR, no per-token Python ``float()`` loop, GIL
    released so DMLC_FEED_WORKERS partition threads genuinely overlap.
    The classic parser path below is the bit-identical fallback (and
    serves csv/libfm and cached URIs)."""
    from ..data import create_row_iter
    from ..io.uri import URISpec

    def part_iter_classic(part: int, n_parts: int):
        it = create_row_iter(uri, part, n_parts, fmt)
        ncol = it.num_col()
        out = None
        for blk in it:
            # re-slice parser blocks into fixed batches; the yielded
            # dict is BORROWED (overwritten on the next batch) per the
            # DeviceFeed batch-borrowing contract
            for lo in range(0, blk.size, batch_size):
                sub = blk.slice(lo, min(lo + batch_size, blk.size))
                out = pack_rowblock(sub, batch_size, max_nnz, ncol,
                                    out=out)
                yield out

    def part_iter_fused(part: int, n_parts: int):
        from .. import native, telemetry
        from ..io import input_split as isplit

        if not native.available():  # e.g. disabled since construction
            yield from part_iter_classic(part, n_parts)
            return
        split = isplit.create(uri, part, n_parts, "text")
        try:
            # ONE borrowed batch dict per iterator, rows written in
            # place by the fused native tokenizer; num_col clamping is
            # a no-op here by construction (the classic path clamps to
            # the partition's own max index + 1, which no parsed index
            # can exceed), so batches stay bit-identical
            out = {"label": np.zeros(batch_size, np.float32),
                   "value": np.zeros((batch_size, max_nnz), np.float32),
                   "index": np.zeros((batch_size, max_nnz), np.int32),
                   "mask": np.zeros((batch_size, max_nnz), np.float32)}
            r = 0
            while True:
                chunk = split.next_chunk()
                if chunk is None:
                    break
                start, n = 0, len(chunk)
                while start < n:
                    with telemetry.span("feed.parse_native",
                                        stage="feed"):
                        r, start = native.parse_libsvm_into(
                            chunk, start, r, max_nnz, 0, out)
                    if r == batch_size:
                        yield out
                        r = 0
            if r:  # epoch-tail short batch: zero-pad like pack_rowblock
                out["label"][r:] = 0
                out["value"][r:] = 0
                out["index"][r:] = 0
                out["mask"][r:] = 0
                yield out
        finally:
            split.close()

    spec = URISpec(uri, 0, 1)
    fused = (fmt == "libsvm" and spec.cache_file is None
             and spec.args.get("format", "libsvm") == "libsvm")
    part_iter = part_iter_fused if fused else part_iter_classic
    # factories, not iterators: each epoch re-creates the row iters (which
    # hit the DiskRowIter/#cachefile cache when the URI requests one)
    builder = lambda p, n: functools.partial(part_iter, p, n)  # noqa: E731
    return DeviceFeed(mesh, queue_depth=queue_depth,
                      source_builder=builder, world=world)


#: reject kinds emitted by the fused scanners (flag >= 8), rendered as
#: the same message strings the pre-fused walkers reported
_REJECT_WHAT = {
    8: "bad magic",
    9: "truncated payload",
    10: "torn multi-segment record",
    11: "missing end segment",
    13: "crc32c mismatch",
    14: "torn tail (sub-word remainder)",
}  # kind 12 renders with the offending cflag read back from the chunk


def _py_chunk_spans(mv: memoryview, verify: bool = True):
    """Pure fused single-pass walker — the Python twin of the native
    ``dmlc_recordio_spans_verify`` scanner (ABI 6), held to byte-
    identical triple tables by the differential test matrix.  Produces
    (offset, len, flag) triples: flags 0/1 plain, 2/3 checksummed
    (CRC32C-verified inline when ``verify``), and TYPED REJECTS with
    flag >= 8 covering [begin, resync point) for every corruption —
    bad magic, truncated/torn structure, crc mismatch, stray sub-word
    tail (the tail reject is suppressed when the chunk already
    reported; the other report covers those bytes).  No integrity
    policy is applied here: :func:`_verify_spans` routes rejects."""
    from ..io import integrity
    from ..io.recordio import CRC_BIT, HEAD_CFLAGS, _MAGIC_BYTES, _U32, \
        decode_flag, decode_length, find_next_record_head, stored_crc

    triples, pos, n = [], 0, len(mv)
    any_reject = False

    def resync(p):
        nxt = min(n, p + 4)
        nxt += (-nxt) % 4
        end = n - n % 4
        return find_next_record_head(mv, nxt, end) if nxt < end else end

    def region_crc_ok(off, ln):
        p2, end2 = off, off + ln
        while p2 + 12 <= end2:
            lrec2 = _U32.unpack_from(mv, p2 + 4)[0]
            want = _U32.unpack_from(mv, p2 + 8)[0]
            m = decode_length(lrec2)
            if stored_crc(integrity.crc32c(
                    mv[p2 + 12: p2 + 12 + m])) != want:
                return False
            p2 += 12 + ((m + 3) & ~3)
        return True

    while pos + 8 <= n:
        if mv[pos:pos + 4] != _MAGIC_BYTES:
            r = resync(pos)
            triples.append((pos, r - pos, 8))
            any_reject = True
            pos = r
            continue
        lrec = _U32.unpack_from(mv, pos + 4)[0]
        cflag, ln = decode_flag(lrec), decode_length(lrec)
        ck = cflag >= CRC_BIT
        hdr = 12 if ck else 8
        if cflag & 3 == 0 and cflag in HEAD_CFLAGS:
            nxt = pos + hdr + ((ln + 3) & ~3)
            if nxt > n:
                r = resync(pos)
                triples.append((pos, r - pos, 9))
                any_reject = True
                pos = r
                continue
            if ck and verify:
                want = _U32.unpack_from(mv, pos + 8)[0]
                if stored_crc(integrity.crc32c(
                        mv[pos + hdr: pos + hdr + ln])) != want:
                    # span = [head, payload end): the quarantine key
                    triples.append((pos, hdr + ln, 13))
                    any_reject = True
                    pos = nxt
                    continue
            triples.append((pos + hdr, ln, 2 if ck else 0))
            pos = nxt
        elif cflag & 3 == 1 and cflag in HEAD_CFLAGS:
            start = pos
            p = pos + hdr + ((ln + 3) & ~3)
            kind = 0  # 0 = structurally sound
            while True:
                if p + hdr > n or mv[p:p + 4] != _MAGIC_BYTES:
                    kind = 10
                    break
                lrec = _U32.unpack_from(mv, p + 4)[0]
                cf, l2 = decode_flag(lrec), decode_length(lrec)
                if cf & 3 not in (2, 3) or (cf >= CRC_BIT) != ck:
                    kind = 11
                    break
                p += hdr + ((l2 + 3) & ~3)
                if p > n:
                    kind = 9
                    break
                if cf & 3 == 3:
                    break
            if kind:
                r = resync(start)
                triples.append((start, r - start, kind))
                any_reject = True
                pos = r
                continue
            if ck and verify and not region_crc_ok(start, p - start):
                triples.append((start, p - start, 13))
                any_reject = True
            else:
                triples.append((start, p - start, 3 if ck else 1))
            pos = p
        else:
            r = resync(pos)
            triples.append((pos, r - pos, 12))
            any_reject = True
            pos = r
    if pos < n and not any_reject:
        triples.append((pos, n - pos, 14))
    return np.asarray(triples, np.uint64).reshape(-1, 3)


def _chunk_spans(mv: memoryview, source=None, base=None):
    """Span triples (offset, len, flag) for one record-aligned RecordIO
    chunk via the fused single-pass scan: structure walk + inline
    CRC32C verification in ONE native call (Python twin as fallback),
    typed rejects routed through DMLC_INTEGRITY_POLICY, quarantined
    spans dropped on replay.  ``source``/``base`` key quarantined spans
    as (uri, global byte offset of the record head).  Since PR 11 the
    crc never costs a second pass over the chunk — the ``feed.crc``
    stage below times only the residual reject/skip-list routing."""
    from .. import native, telemetry
    from ..io.recordio import KMAGIC

    with telemetry.span("feed.parse_native", stage="feed"):
        sp = native.recordio_spans(mv, KMAGIC, verify=True)
        if sp is None:  # no native library: fused Python walk
            sp = _py_chunk_spans(mv)
    with telemetry.timed("feed", "crc"):
        return _verify_spans(mv, sp, source, base)


def _verify_spans(mv: memoryview, sp, source, base):
    """Route a fused scan's span table through the integrity layer:
    typed rejects (flag >= 8) are reported under the active
    DMLC_INTEGRITY_POLICY (raise / skip / quarantine) and dropped;
    skip-listed (quarantined) spans are dropped on replay.  Verification
    itself already happened inside the scan — the common clean-chunk
    path is one vectorized compare and no byte is re-read."""
    from ..io import integrity
    from ..io.recordio import _U32, decode_flag

    if sp.shape[0] == 0:
        return sp
    flags = sp[:, 2]
    rejects = flags >= 8
    listed = integrity.has_quarantine(source)
    if not rejects.any() and not listed:
        return sp
    keep = np.ones(sp.shape[0], bool)
    for i in np.nonzero(rejects)[0]:
        keep[i] = False
        off, ln, kind = int(sp[i, 0]), int(sp[i, 1]), int(sp[i, 2])
        gbegin = None if base is None else base + off
        if kind == 13 and integrity.should_drop(source, gbegin):
            # quarantined on a previous (poisoned) pass: the replay
            # contract counts a skip-list drop, not a fresh report
            continue
        if kind == 12:
            cf = decode_flag(_U32.unpack_from(mv, off + 4)[0])
            what = f"cflag {cf} at record head"
        else:
            what = _REJECT_WHAT[kind]
        integrity.handle_corrupt(  # raises under policy 'raise'
            what, source=source, begin=gbegin,
            end=None if base is None else base + off + ln)
    if listed and base is not None:
        for i in np.nonzero(~rejects)[0]:
            off, flag = int(sp[i, 0]), int(sp[i, 2])
            head = off - 12 if flag == 2 else off - 8 if flag == 0 else off
            if integrity.should_drop(source, base + head):
                keep[i] = False
    return sp if keep.all() else sp[keep]


def _reassemble_region(mv: memoryview, off: int, ln: int) -> bytes:
    """Reassemble one escaped-magic (multi-segment) record region —
    plain (8-byte headers) or checksummed (12-byte headers; the crc was
    verified by the span scan)."""
    from ..io.recordio import CRC_BIT, _MAGIC_BYTES, _U32, decode_flag, \
        decode_length

    region = mv[off: off + ln]
    parts, pos = [], 0
    first = True
    while pos + 8 <= len(region):
        lrec = _U32.unpack_from(region, pos + 4)[0]
        cf, n = decode_flag(lrec), decode_length(lrec)
        hdr = 12 if cf >= CRC_BIT else 8
        if not first:
            parts.append(_MAGIC_BYTES)
        parts.append(bytes(region[pos + hdr: pos + hdr + n]))
        first = False
        pos += hdr + ((n + 3) & ~3)
        if cf & 3 in (0, 3):
            break
    return b"".join(parts)


def _chunk_record_views(mv: memoryview, sp=None):
    """Per-record uint8 numpy views over one chunk (zero-copy for
    direct-payload records — flags 0/2; multi-segment regions — flags
    1/3 — reassembled as owned arrays)."""
    if sp is None:
        sp = _chunk_spans(mv)
    arr = np.frombuffer(mv, np.uint8)
    out = []
    for off, ln, flag in sp.tolist():
        if flag % 2 == 0:
            out.append(arr[off: off + ln])
        else:
            out.append(np.frombuffer(
                _reassemble_region(mv, int(off), int(ln)), np.uint8))
    return out


def _gather_rows_into(mv: memoryview, sp, lo: int, hi: int,
                      max_bytes: int, out_rows: np.ndarray,
                      out_lens: np.ndarray) -> None:
    """Gather span records ``[lo, hi)`` of one RecordIO chunk into the
    caller-provided ``out_rows [hi-lo, max_bytes]`` / ``out_lens`` —
    a single broadcast numpy gather straight into the batch buffer (no
    per-record Python loop, no intermediate row array).

    The span scan yields (offset, len, flag) per logical record; the
    hot path is ONE native call (``dmlc_pad_pack_rows``: memcpy +
    zero-fill per row, escaped-magic reassembly in place) writing
    straight into the batch buffer.  The numpy broadcast gather below
    is the bit-identical fallback (``DMLC_TPU_DISABLE_NATIVE=1``)."""
    from .. import native
    from ..io.recordio import KMAGIC

    g = hi - lo
    rows_out = out_rows[:g]
    lens_out = out_lens[:g]
    if (rows_out.flags["C_CONTIGUOUS"] and lens_out.flags["C_CONTIGUOUS"]
            and lens_out.dtype == np.int32
            and native.pad_pack_rows(mv, sp[lo:hi], KMAGIC, max_bytes,
                                     rows_out, lens_out)):
        return
    arr = np.frombuffer(mv, np.uint8)
    offs = sp[lo:hi, 0].astype(np.int32)   # chunk-local: always < 2^31
    lens = np.minimum(sp[lo:hi, 1].astype(np.int64), max_bytes)
    g = hi - lo
    idx = offs[:, None] + np.arange(max_bytes, dtype=np.int32)[None, :]
    np.minimum(idx, arr.size - 1, out=idx)
    np.take(arr, idx, out=out_rows[:g])
    out_rows[:g] *= (np.arange(max_bytes, dtype=np.int64)[None, :]
                     < lens[:, None])
    for i in np.nonzero(sp[lo:hi, 2] % 2 == 1)[0]:  # escaped magic
        payload = _reassemble_region(mv, int(offs[i]), int(sp[lo + i, 1]))
        n = min(len(payload), max_bytes)
        out_rows[i, :n] = np.frombuffer(payload, np.uint8, n)
        out_rows[i, n:] = 0
        lens[i] = n
    out_lens[:g] = lens


def _packed_part_iter(uri: str, part: int, n_parts: int, buf_bytes: int,
                      max_records: int, guard_bytes: int = 0):
    """One partition of RecordIO shards as packed batches:
    {data [buf_bytes + guard_bytes] uint8, offsets [max_records+1]
    int32, count [1]} with record payloads packed back-to-back in
    ``data[:buf_bytes]`` (``guard_bytes`` stays zero — the padded
    transform's dynamic-slice guard region).

    Batches assemble IN PLACE: record payloads go straight from the
    mapped chunk into the static batch buffer via one native pack call
    per (chunk, batch) pair (cpp/dmlc_native.cc dmlc_pack_spans) — no
    intermediate pending-payload array, no concat chain, no second
    copy.  The batch dict is BORROWED (DeviceFeed copies it into the
    staging buffer before resuming this generator), so ONE
    data/offsets/count buffer serves the whole epoch — zero
    steady-state allocation."""
    from .. import native, telemetry
    from ..io import input_split

    split = input_split.create(uri, part, n_parts, "recordio")
    try:
        data = np.empty(buf_bytes + guard_bytes, np.uint8)
        pack_dst = data[:buf_bytes]
        offsets = np.empty(max_records + 1, np.int32)
        count_arr = np.empty(1, np.int32)
        ends = np.empty(max_records, np.int64)
        count = 0
        pos = 0

        def emit():
            nonlocal count, pos
            data[pos:] = 0  # zero tail (and guard) only, not the buffer
            np.minimum(ends[:count], buf_bytes, out=ends[:count])
            offsets[0] = 0
            offsets[1: count + 1] = ends[:count]
            offsets[count + 1:] = offsets[count]
            count_arr[0] = count
            count = 0
            pos = 0
            return {"data": data, "offsets": offsets,
                    "count": count_arr}

        while True:
            mv = split.next_chunk()
            if mv is None:
                break
            sp = _chunk_spans(
                mv, source=uri,
                base=getattr(split, "last_chunk_begin", None))
            if (sp[:, 2] % 2 == 0).all():
                # direct-payload spans (plain or verified
                # checksummed): pack straight from the chunk
                src = mv
                offs = sp[:, 0].astype(np.int64)
                lens = sp[:, 1].astype(np.int64)
            else:  # rare escaped-magic chunk: flatten, then pack
                views = _chunk_record_views(mv, sp)
                lens = np.fromiter((v.size for v in views),
                                   np.int64, count=len(views))
                src = (np.concatenate(views) if views
                       else np.empty(0, np.uint8))
                offs = np.zeros(len(views), np.int64)
                if len(views) > 1:
                    np.cumsum(lens[:-1], out=offs[1:])
            i = 0
            n_spans = len(lens)
            while i < n_spans:
                with telemetry.timed("feed", "pack"):
                    consumed, pos, full = native.pack_spans(
                        src, offs[i:], lens[i:], pack_dst, pos,
                        max_records - count, count == 0, ends[count:])
                count += consumed
                i += consumed
                if full:
                    yield emit()
        if count:
            yield emit()
    finally:
        split.close()


def recordio_packed_feed(uri: str, mesh, *, buf_bytes: int,
                         max_records: int = 4096,
                         queue_depth: Optional[int] = None,
                         world=None) -> DeviceFeed:
    """RecordIO shards → packed batches with NO per-record padding:
    {data [buf_bytes] uint8, offsets [max_records+1] int32, count [1]}.

    Padding a [B, max_bytes] batch wastes host→HBM bandwidth on the gap
    between mean and max record size; the packed layout ships payload
    bytes back-to-back (static buf_bytes, zero tail) with record offsets
    for on-device slicing.  Records larger than buf_bytes are truncated.
    ``world=(rank, world_size)`` partitions across an elastic
    multi-process world (resizable via :meth:`DeviceFeed.resize`).
    """
    def part_iter(part: int, n_parts: int):
        return _packed_part_iter(uri, part, n_parts, buf_bytes,
                                 max_records)

    builder = lambda p, n: functools.partial(part_iter, p, n)  # noqa: E731
    return DeviceFeed(mesh, queue_depth=queue_depth,
                      source_builder=builder, world=world)


def _make_padded_expander(feed: DeviceFeed, batch_records: int,
                          max_bytes: int, stride: int):
    """On-device expansion for the packed-transport padded feed: one
    jitted gather per batch turns the packed staging layout
    ({data, offsets}) into the padded {data [n_parts*B, max_bytes],
    length} contract AFTER the bytes crossed the host→device link —
    the link ships payload, the accelerator materializes the padding.
    Runs on the placer thread, so expansion overlaps the consumer's
    step like any other producer work."""
    import jax
    import jax.numpy as jnp

    n_parts = feed._n_parts
    B = batch_records
    sharding = feed.sharding

    from ..telemetry import compute

    @functools.partial(compute.profiled_jit, site="feed.expand",
                       out_shardings=(sharding, sharding))
    def expand(data, offsets):
        offs = offsets.reshape(n_parts, B + 1)
        base = (jnp.arange(n_parts, dtype=jnp.int32) * stride)[:, None]
        starts = (offs[:, :-1] + base).reshape(-1)
        lens = jnp.minimum((offs[:, 1:] - offs[:, :-1]).reshape(-1),
                           max_bytes).astype(jnp.int32)
        # per-row dynamic_slice under vmap lowers to ONE gather with
        # row-level (not cell-level) indices; the guard region appended
        # to each partition's staging block keeps every slice in bounds
        # so no clamp can shift a window
        rows = jax.vmap(
            lambda s: jax.lax.dynamic_slice(data, (s,), (max_bytes,))
        )(starts)
        mask = (jnp.arange(max_bytes, dtype=jnp.int32)[None, :]
                < lens[:, None])
        return jnp.where(mask, rows, jnp.uint8(0)), lens

    def transform(batch):
        data, length = expand(batch["data"], batch["offsets"])
        return {"data": data, "length": length,
                "parts_alive": batch["parts_alive"]}

    return transform


def recordio_feed(uri: str, mesh, *, batch_records: int, max_bytes: int,
                  queue_depth: Optional[int] = None,
                  world=None,
                  pack_bytes: Optional[int] = None) -> DeviceFeed:
    """RecordIO shards → {data [B, max_bytes] uint8, length [B] int32}.

    Payload decode (e.g. images) happens on device or downstream; this
    feed moves raw record bytes into HBM at full InputSplit throughput.
    Batch assembly is chunk-at-a-time: the fused native span scan
    (+inline CRC32C) and one native pad-pack per span group
    (cpp/dmlc_native.cc), not a per-record copy loop.
    ``world=(rank, world_size)`` partitions across an elastic
    multi-process world (resizable via :meth:`DeviceFeed.resize`).

    ``pack_bytes`` selects the **packed-transport** variant: the host
    stages records back-to-back in a ``pack_bytes``-sized buffer per
    partition (plus offsets) and a jitted on-device gather expands each
    batch to the same padded ``{data, length}`` contract AFTER the
    link — so the padded feed ships payload bytes, not padding, and
    tracks the device_put ceiling like the packed layout.  The trade:
    a batch then holds UP TO ``batch_records`` rows (whatever fills
    ``pack_bytes``; trailing rows have length 0), so consumers must
    honor ``length``/``parts_alive`` — which the epoch-tail contract
    already requires.  Default (None) keeps the classic fully-padded
    host staging."""
    from ..io import input_split

    if pack_bytes is not None:
        # the packed staging buffer must hold any record the padded
        # contract would deliver: with pack_bytes < max_bytes, an
        # oversized record would be truncated at pack_bytes (the
        # pack_spans allow-truncate path) and silently lose bytes the
        # default padded path delivers
        check(pack_bytes >= max_bytes,
              f"pack_bytes ({pack_bytes}) must be >= max_bytes "
              f"({max_bytes}) so no record is truncated below the "
              f"padded contract")

        def part_iter_packed(part: int, n_parts: int):
            return _packed_part_iter(uri, part, n_parts, pack_bytes,
                                     batch_records,
                                     guard_bytes=max_bytes)

        builder = lambda p, n: functools.partial(  # noqa: E731
            part_iter_packed, p, n)
        feed = DeviceFeed(mesh, queue_depth=queue_depth,
                          source_builder=builder, world=world)
        feed._transform = _make_padded_expander(
            feed, batch_records, max_bytes, pack_bytes + max_bytes)
        return feed

    def part_iter(part: int, n_parts: int):
        from .. import telemetry

        split = input_split.create(uri, part, n_parts, "recordio")
        try:
            # ONE batch buffer per iterator, filled in place chunk by
            # chunk and yielded BORROWED (the DeviceFeed staging copy
            # happens before this generator resumes) — no pending-row
            # concat chain, no per-group row allocation.
            data = np.empty((batch_records, max_bytes), np.uint8)
            length = np.empty(batch_records, np.int32)
            batch = {"data": data, "length": length}
            # bound the transient gather index ≲16 MB even for MB-sized
            # records by splitting a chunk's spans into groups (the
            # native pad-pack has no such transient; the cap only
            # matters for the numpy fallback)
            group_cap = max(1, (16 << 20) // max(max_bytes, 1))
            r = 0
            while True:
                mv = split.next_chunk()
                if mv is None:
                    break
                sp = _chunk_spans(
                    mv, source=uri,
                    base=getattr(split, "last_chunk_begin", None))
                i, n_spans = 0, sp.shape[0]
                while i < n_spans:
                    g = min(n_spans - i, batch_records - r, group_cap)
                    with telemetry.timed("feed", "pack"):
                        _gather_rows_into(mv, sp, i, i + g, max_bytes,
                                          data[r:], length[r:])
                    i += g
                    r += g
                    if r == batch_records:
                        yield batch
                        r = 0
            if r:
                # zero-pad the epoch's final short batch
                data[r:] = 0
                length[r:] = 0
                yield batch
        finally:
            split.close()

    builder = lambda p, n: functools.partial(part_iter, p, n)  # noqa: E731
    return DeviceFeed(mesh, queue_depth=queue_depth,
                      source_builder=builder, world=world)
