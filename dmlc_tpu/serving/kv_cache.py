"""Paged KV cache: block-granular virtual memory for decode contexts.

vLLM-style PagedAttention bookkeeping adapted to this substrate: the
cache is a fixed pool of fixed-size blocks (``block_size`` tokens each)
handed out by a free-list :class:`BlockAllocator`, and every sequence
owns a *block table* mapping its logical token positions to physical
blocks (two of them where the model has sliding-window layers: see
below).  Continuous batching lives or dies on this layout — sequences
of wildly different lengths share one arena with zero fragmentation
beyond the final partial block, and a finished (or preempted) request
returns its blocks to the free list for immediate reuse.

Pool layout (layer-major, mirroring the paged-attention kernel shapes):

    k_pool / v_pool : [n_layers, n_blocks, block_size, n_kv_heads, head_dim]

(a page holds the K/V heads, which grouped-query attention has fewer of
than query heads; where keys and values have widths of their own a page
is lane-flat, ``[block_size, n_kv_heads x width]``, and each kind of
layer has its own K/V heads).  What the pools are is the model's to say (``pool_shapes``, from
``TransformerConfig.kv_pool_shapes``): K and V heads as above, or under
latent attention ONE pool of rows ``[rms(c_kv) | rope(k_pe)]`` with no
head axis and no separate V, ``[n_layers, n_blocks, 576, block_size]``.
The allocator, block tables, lengths and ``stats()`` do not care.

A model with sliding-window layers has a block table per layer TYPE.
Its full layers keep the growing table above over the pool of
``n_blocks``.  Its sliding layers, which never look further back than
``sliding_window`` keys, have a pool of their own (``sliding_shapes``,
from ``TransformerConfig.sliding_pool_shapes``: sized for the rows that
can be live at once, not by ``n_blocks``) and a second table a sequence,
a *ring* of ``R = ceil(window / block_size) + 1`` entries: logical block
``j`` lives at entry ``j mod R``.  A prompt's prefill writes only the
last ``R`` blocks (:meth:`PagedKVCache.sliding_prefill_ids`), and when
decode grows into logical block ``j >= R`` it takes over the physical
block of ``j - R``, every key of which is out of every later query's
reach: a sequence never holds more than ``R`` sliding blocks however
long it grows.  ``allocate`` / ``extend`` / ``extend_many`` / ``free``
cover both pools atomically; ``n_blocks``, ``blocks_in_use`` and
``occupancy`` go on meaning the full layers' pool, and the sliding
pool's counts stand beside them (``sliding_*``).

A model with recurrent layers keeps another kind of state, fixed in
size per sequence (``state_shapes``, from
``TransformerConfig.state_slot_shapes``): arrays ``[layers, n_slots,
...]`` of which a sequence owns ONE slot, taken with its blocks at
:meth:`PagedKVCache.allocate` and returned with them at
:meth:`PagedKVCache.free`, so admission is bounded by free slots as
well as free blocks.  The arrays travel with the pools: the prefill
program writes a sequence's slot, the decode program updates the live
rows' slots in place (:meth:`PagedKVCache.slot_ids` beside the block
tables), both donated and adopted alike.

The bytes live in ONE place: the pools are device arrays and they ARE
the cache.  Device programs write them in place
(``models.transformer.prefill_paged`` scatters a prompt's K/V into its
blocks, ``decode_paged`` the decode window's; the engine
DONATES the pools to both, so the arrays handed in are deleted by the
call) and the engine swaps in what they return (:meth:`device_pools` /
:meth:`adopt_device_pools`, and :meth:`drop_lost_pools` after a call
that failed with the pools in its hands); the host only advances
lengths (:meth:`advance_many`) and ships the tiny int32
:meth:`block_tables_array` per step.  No K/V ever crosses the host
link.

Thread-safety: all bookkeeping is lock-protected, but the data plane
assumes the engine's single step thread — the same contract as the
training feed.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import DMLCError
from .. import telemetry
from ..concurrency import make_lock

__all__ = ["BlockAllocator", "PagedKVCache"]


class BlockAllocator:
    """Free-list allocator over ``n_blocks`` fixed-size blocks.

    All-or-nothing ``alloc_many`` keeps admission atomic: a request
    either gets its whole reservation or leaves the free list untouched
    (no partial grabs to roll back under concurrent admits).  Double
    free raises — an aliased block silently corrupting another
    sequence's context is the worst failure mode a KV cache has.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        self.n_blocks = int(n_blocks)
        # pop() from the tail → ascending ids first; order is cosmetic
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self._in_use: set = set()
        self._lock = make_lock("BlockAllocator._lock")

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def n_in_use(self) -> int:
        with self._lock:
            return len(self._in_use)

    def alloc(self) -> Optional[int]:
        got = self.alloc_many(1)
        return got[0] if got else None

    def alloc_many(self, n: int) -> Optional[List[int]]:
        """``n`` block ids, or None (and no state change) if fewer than
        ``n`` are free."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        with self._lock:
            if n > len(self._free):
                return None
            got = [self._free.pop() for _ in range(n)]
            self._in_use.update(got)
            return got

    def free(self, blocks: Sequence[int]) -> None:
        """All-or-nothing like ``alloc_many``: the whole list is
        validated before any block moves, so a bad id raises with the
        allocator unchanged (a partial free would desync the caller's
        block table from ``in_use``)."""
        blocks = list(blocks)
        with self._lock:
            bad = [b for b in blocks if b not in self._in_use]
            if bad:
                raise DMLCError(
                    f"double free / foreign blocks {bad} "
                    f"(in_use={len(self._in_use)})")
            for b in blocks:
                self._in_use.discard(b)
                self._free.append(b)


class _SeqEntry:
    __slots__ = ("blocks", "length", "slot", "ring", "ring_top")

    def __init__(self) -> None:
        self.blocks: List[int] = []
        self.length = 0
        self.slot: Optional[int] = None  # recurrent-state slot, if any
        # sliding layers: the ring's physical blocks by entry, and the
        # newest logical block it has room for
        self.ring: List[int] = []
        self.ring_top = -1


class PagedKVCache:
    """Block-paged K/V storage for a set of live sequences.

    ``n_layers/n_heads/head_dim`` come from the model config, or
    ``pool_shapes`` does (one shape per pool; the default is K and V
    pools of ``[n_layers, n_blocks, block_size, n_heads, head_dim]``).
    ``n_blocks × block_size`` is the total token capacity shared by all
    concurrent requests.  ``state_shapes`` is ``((shape, dtype), ...)``
    of the recurrent-state arrays, axis 1 the slots (default none).
    ``sliding_shapes`` are the sliding layers' pools (axis 1 their own
    blocks) and ``sliding_window`` their reach in keys (default: no
    such layers).  The bytes are device arrays that device programs
    write (module docstring).
    """

    def __init__(self, n_layers: int, n_heads: int, head_dim: int, *,
                 n_blocks: int = 256, block_size: int = 16,
                 dtype=np.float32, pool_shapes: Optional[tuple] = None,
                 state_shapes: tuple = (), sliding_shapes: tuple = (),
                 sliding_window: int = 0):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.dtype = np.dtype(dtype)
        self.pool_shape = (self.n_layers, self.n_blocks, self.block_size,
                           self.n_heads, self.head_dim)
        self.pool_shapes = (self.pool_shape,) * 2 if pool_shapes is None \
            else tuple(tuple(int(d) for d in shape) for shape in pool_shapes)
        self.state_shapes = tuple(
            (tuple(int(d) for d in shape), np.dtype(dt))
            for shape, dt in state_shapes)
        self.n_slots = self.state_shapes[0][0][1] if self.state_shapes else 0
        self.sliding_shapes = tuple(
            tuple(int(d) for d in shape) for shape in sliding_shapes)
        if bool(self.sliding_shapes) != bool(sliding_window):
            raise ValueError("sliding_shapes and sliding_window go together")
        self.sliding_window = int(sliding_window)
        #: entries of a sequence's ring table (0: no sliding layers)
        self.ring_blocks = (self.blocks_for(self.sliding_window) + 1
                            if self.sliding_shapes else 0)
        self.n_sliding_blocks = (self.sliding_shapes[0][1]
                                 if self.sliding_shapes else 0)
        self._ring_alloc = (BlockAllocator(self.n_sliding_blocks)
                            if self.sliding_shapes else None)
        # pop() from the tail: ascending slots first, as the blocks
        self._free_slots: List[int] = list(range(self.n_slots - 1, -1, -1))
        # the pools, made as zeros ON the device by the first
        # device_pools() and replaced by whatever the last prefill /
        # decode program returned
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._dev: Optional[tuple] = None
        # block-table memo: the tables themselves change only when some
        # sequence gains or loses blocks (every ~block_size committed
        # tokens), not every decode step — the version counter lets
        # block_tables_array reuse the previous [B, W] array instead of
        # rebuilding it per step (a measurable slice of a ~1 ms step)
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._tables_version = 0
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._tables_cache: Optional[tuple] = None
        self._alloc = BlockAllocator(self.n_blocks)
        self._seqs: Dict[int, _SeqEntry] = {}
        # running Σ length over live sequences: occupancy/waste gauges
        # and stats() stay O(1) on the decode hot path (extend runs
        # once per active request per iteration — re-summing all live
        # sequences there measurably taxes the decode step)
        self._cached_tokens = 0
        self._lock = make_lock("PagedKVCache._lock")
        telemetry.set_gauge("serving", "kv_blocks_total", self.n_blocks)
        if self.ring_blocks:
            telemetry.set_gauge("serving", "kv_sliding_blocks_total",
                                self.n_sliding_blocks)
        if self.n_slots:
            telemetry.set_gauge("serving", "state_slots_total", self.n_slots)
        self._publish_usage()

    # ---- capacity arithmetic -------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` (ceil; 0 tokens → 0)."""
        return -(-max(int(n_tokens), 0) // self.block_size)

    @property
    def n_free_blocks(self) -> int:
        return self._alloc.n_free

    @property
    def n_blocks_in_use(self) -> int:
        return self._alloc.n_in_use

    @property
    def n_free_slots(self) -> int:
        with self._lock:
            return len(self._free_slots)

    def ring_blocks_for(self, n_tokens: int) -> int:
        """Sliding blocks a sequence of ``n_tokens`` holds: its blocks
        up to the ring's size (0 without sliding layers)."""
        return min(self.blocks_for(n_tokens), self.ring_blocks)

    @property
    def n_free_sliding_blocks(self) -> int:
        return self._ring_alloc.n_free if self._ring_alloc else 0

    @property
    def n_sliding_blocks_in_use(self) -> int:
        return self._ring_alloc.n_in_use if self._ring_alloc else 0

    def can_reserve(self, n_tokens: int) -> bool:
        """Whether a NEW sequence of ``n_tokens`` fits now: its blocks
        in the full layers' pool, its ring's in the sliding layers' and,
        where sequences carry recurrent state, a slot."""
        if self.n_slots and not self.n_free_slots:
            return False
        if self.ring_blocks_for(n_tokens) > self.n_free_sliding_blocks:
            return False
        return self.blocks_for(n_tokens) <= self._alloc.n_free

    def fits_at_all(self, n_tokens: int) -> bool:
        """Whether ``n_tokens`` could EVER be cached, even with both
        pools free — the admission-time sanity bound."""
        return (self.blocks_for(n_tokens) <= self.n_blocks
                and self.ring_blocks_for(n_tokens) <= self.n_sliding_blocks)

    # ---- sequence lifecycle --------------------------------------------
    def allocate(self, seq_id: int, n_tokens: int) -> bool:
        """Register ``seq_id`` with capacity for ``n_tokens``; False
        (and no state change) when the free list cannot cover it."""
        with self._lock:
            if seq_id in self._seqs:
                raise DMLCError(f"sequence {seq_id} already allocated")
            got = None
            n_ring = self.ring_blocks_for(n_tokens)
            if (not self.n_slots or self._free_slots) \
                    and n_ring <= self.n_free_sliding_blocks:
                got = self._alloc.alloc_many(self.blocks_for(n_tokens))
            if got is None:
                telemetry.inc("serving", "kv_alloc_failures")
                return False
            ent = _SeqEntry()
            ent.blocks = got
            if self.ring_blocks:
                # guarded above: one lock holds both allocators' users
                ent.ring = self._ring_alloc.alloc_many(n_ring)
                ent.ring_top = self.blocks_for(n_tokens) - 1
            if self.n_slots:
                ent.slot = self._free_slots.pop()
                telemetry.inc("serving", "state_slot_allocs")
            self._seqs[seq_id] = ent
            self._tables_version += 1
        self._publish_usage()
        return True

    def extend(self, seq_id: int, n_tokens: int = 1) -> bool:
        """Ensure capacity for ``n_tokens`` more tokens; False when the
        pool is exhausted (caller evicts and retries)."""
        with self._lock:
            ent = self._seq(seq_id)
            need = self.blocks_for(ent.length + n_tokens) - len(ent.blocks)
            ring = self._ring_need(ent, ent.length + n_tokens)
            if need <= 0 and ring is None:
                return True
            if (max(need, 0) > self._alloc.n_free or
                    (ring or 0) > self.n_free_sliding_blocks):
                telemetry.inc("serving", "kv_alloc_failures")
                return False
            self._grow(ent, need, ring, ent.length + n_tokens)
            self._tables_version += 1
        self._publish_usage()
        return True

    def _ring_need(self, ent: _SeqEntry, end: int) -> Optional[int]:
        """Lock held.  Sliding blocks to allocate so that the ring has
        room for tokens up to ``end`` (0: it only turns, taking over
        blocks whose keys are out of reach), or None when it has."""
        if not self.ring_blocks or self.blocks_for(end) - 1 <= ent.ring_top:
            return None
        return self.ring_blocks_for(end) - len(ent.ring)

    def _grow(self, ent: _SeqEntry, need: int, ring: Optional[int],
              end: int) -> None:
        """Lock held, room checked: give ``ent`` ``need`` blocks and
        turn its ring up to ``end``."""
        if need > 0:
            ent.blocks.extend(self._alloc.alloc_many(need))
        if ring is not None:
            if ring:
                ent.ring.extend(self._ring_alloc.alloc_many(ring))
            top = self.blocks_for(end) - 1
            # every logical block at or past the ring's size took over
            # the physical block of the one a ring before it
            released = max(top, self.ring_blocks - 1) - max(
                ent.ring_top, self.ring_blocks - 1)
            if released:
                telemetry.inc("serving", "kv_sliding_blocks_released",
                              released)
            ent.ring_top = top

    def extend_many(self, seq_ids: Sequence[int],
                    n_tokens: int = 1) -> bool:
        """Reserve ``n_tokens`` more per sequence for a whole decode
        batch under ONE lock acquisition — all or nothing.  False means
        the free list cannot cover the batch and NO state changed; the
        caller falls back to the per-sequence extend + evict loop.  The
        common steady-state case (every row already has block headroom)
        touches no allocator state at all."""
        with self._lock:
            ents = [self._seq(s) for s in seq_ids]
            needs = [self.blocks_for(e.length + n_tokens) - len(e.blocks)
                     for e in ents]
            rings = [self._ring_need(e, e.length + n_tokens) for e in ents]
            total = sum(n for n in needs if n > 0)
            if total == 0 and all(r is None for r in rings):
                return True
            if total > self._alloc.n_free or sum(
                    r or 0 for r in rings) > self.n_free_sliding_blocks:
                return False
            for ent, need, ring in zip(ents, needs, rings):
                if need > 0 or ring is not None:
                    self._grow(ent, need, ring, ent.length + n_tokens)
            self._tables_version += 1
        self._publish_usage()
        return True

    def free(self, seq_id: int) -> None:
        """Return the sequence's blocks to the free list (idempotent:
        freeing an unknown seq is a no-op so finish/preempt paths never
        double-free)."""
        with self._lock:
            ent = self._seqs.pop(seq_id, None)
            if ent is None:
                return
            self._cached_tokens -= ent.length
            self._alloc.free(ent.blocks)
            if ent.ring:
                self._ring_alloc.free(ent.ring)
            if ent.slot is not None:
                self._free_slots.append(ent.slot)
            self._tables_version += 1
        self._publish_usage()

    def length(self, seq_id: int) -> int:
        with self._lock:
            return self._seq(seq_id).length

    def block_table(self, seq_id: int) -> List[int]:
        with self._lock:
            return list(self._seq(seq_id).blocks)

    def sliding_prefill_ids(self, seq_id: int) -> List[int]:
        """The physical sliding blocks a prefill writes, in logical
        order: the sequence's last ``len(ring)`` logical blocks, which
        are all a later query's window reaches."""
        with self._lock:
            ent = self._seq(seq_id)
            n, r = len(ent.ring), self.ring_blocks
            return [ent.ring[j % r]
                    for j in range(ent.ring_top + 1 - n, ent.ring_top + 1)]

    def sliding_tables_array(self, seq_ids: Sequence[int],
                             pad_batch: Optional[int] = None) -> np.ndarray:
        """The sequences' ring tables as int32 ``[B, ring_blocks]``,
        unused entries and rows past ``seq_ids`` 0.  The width is fixed
        by the model's window, so it is no part of a decode program's
        signature."""
        out = np.zeros((max(pad_batch or 0, len(seq_ids)),
                        self.ring_blocks), np.int32)
        with self._lock:
            for i, s in enumerate(seq_ids):
                ring = self._seq(s).ring
                out[i, :len(ring)] = ring
        return out

    def slot_ids(self, seq_ids: Sequence[int],
                 pad_batch: Optional[int] = None) -> np.ndarray:
        """Each sequence's recurrent-state slot as int32 ``[B]``, rows
        past ``seq_ids`` padded with 0 (dead rows: their length 0 keeps
        the decode program from touching the slot)."""
        out = np.zeros(max(pad_batch or 0, len(seq_ids)), np.int32)
        with self._lock:
            out[:len(seq_ids)] = [self._seq(s).slot for s in seq_ids]
        return out

    def live_sequences(self) -> List[int]:
        with self._lock:
            return list(self._seqs)

    def _seq(self, seq_id: int) -> _SeqEntry:
        ent = self._seqs.get(seq_id)
        if ent is None:
            raise DMLCError(f"unknown sequence {seq_id}")
        return ent

    # ---- lengths --------------------------------------------------------
    def _grow_to(self, seq_id: int, ent: _SeqEntry, end: int) -> None:
        """Lock held: the sequence now holds tokens up to ``end``.
        Capacity must already be reserved (allocate/extend); growing
        past it raises rather than silently allocating, keeping the
        eviction policy in the scheduler where it belongs."""
        if self.blocks_for(end) > len(ent.blocks):
            raise DMLCError(
                f"write past reservation: seq {seq_id} end={end} "
                f"blocks={len(ent.blocks)}×{self.block_size}")
        if end > ent.length:
            self._cached_tokens += end - ent.length
            ent.length = end

    # ---- data plane ------------------------------------------------------
    def device_pools(self) -> tuple:
        """The device arrays that are the cache itself, one per entry
        of ``pool_shapes``: ``(k_pool, v_pool)``, or the one latent pool,
        then one per entry of ``sliding_shapes`` and of ``state_shapes``.  Made as zeros on the
        device at first use (nothing is uploaded); afterwards whatever
        :meth:`adopt_device_pools` installed last."""
        if self._dev is None:
            import jax.numpy as jnp

            self._dev = tuple(
                [jnp.zeros(shape, self.dtype)
                 for shape in self.pool_shapes + self.sliding_shapes]
                + [jnp.zeros(shape, dt) for shape, dt in self.state_shapes])
        return self._dev

    def adopt_device_pools(self, *pools) -> None:
        """Install the pools a prefill or decode program returned (its
        in-program scatter made them the cache)."""
        assert len(pools) == len(self.pool_shapes) + len(
            self.sliding_shapes) + len(self.state_shapes), len(pools)
        self._dev = tuple(pools)

    def drop_lost_pools(self) -> bool:
        """After a failed device call: whether the pools were lost with
        it.  A program the pools are DONATED to (prefill and decode
        alike) owns their buffers from dispatch on; if it then raises,
        the arrays this cache still references are deleted and every
        live sequence's K/V is gone.  Forgets them (the next
        :meth:`device_pools` starts from zeros) and returns True so the
        caller can recompute the live sequences; False when the pools
        are intact."""
        if self._dev is None or not any(p.is_deleted() for p in self._dev):
            return False
        self._dev = None
        return True

    def advance_many(self, updates) -> None:
        """``updates`` is ``[(seq_id, n_tokens), ...]``: tokens whose K/V
        a device program already wrote at each sequence's current
        length, under ONE lock acquisition for the batch."""
        with self._lock:
            for seq_id, n in updates:
                ent = self._seq(seq_id)
                self._grow_to(seq_id, ent, ent.length + int(n))

    def block_tables_array(self, seq_ids: Sequence[int], *,
                           pad_width: Optional[int] = None,
                           pad_batch: Optional[int] = None
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-sequence block tables as one dense int32 array — all the
        paged decode program needs from the host besides the lengths.

        Returns ``(tables [B, W], lengths [B])``; ``W`` = ``pad_width``
        or the max owned-block count (min 1), ``B`` = ``pad_batch`` or
        ``len(seq_ids)``.  Rows are padded with block 0 — the attention
        mask keeps padded entries unreachable (positions past
        ``lengths``), and dead rows carry length 0.  An insufficient
        explicit ``pad_width`` is loud: it pins the jit shape.

        The tables array is memoized on (seq_ids, padding, allocator
        version): block OWNERSHIP changes only every ~block_size
        committed tokens, so most decode steps get the previous array
        back verbatim (callers must treat it as read-only — the engine
        only ever ships it into jit).  Lengths change every step and
        are always rebuilt."""
        key = (tuple(seq_ids), pad_width, pad_batch)
        with self._lock:
            cached = self._tables_cache
            if cached is not None and cached[0] == key \
                    and cached[1] == self._tables_version:
                ents = [self._seq(s) for s in seq_ids]
                lengths = np.zeros(cached[2].shape[0], np.int32)
                lengths[:len(ents)] = [e.length for e in ents]
                return cached[2], lengths
            version = self._tables_version
            ents = [self._seq(s) for s in seq_ids]
            tables = [list(e.blocks) for e in ents]
            lens = [e.length for e in ents]
        w = max((len(t) for t in tables), default=0) or 1
        if pad_width is not None:
            if pad_width < w:
                raise ValueError(f"pad_width {pad_width} < required {w}")
            w = pad_width
        b = max(pad_batch or 0, len(seq_ids))
        out = np.zeros((b, w), np.int32)
        lengths = np.zeros(b, np.int32)
        for i, (t, n) in enumerate(zip(tables, lens)):
            out[i, :len(t)] = t
            lengths[i] = n
        with self._lock:
            if version == self._tables_version:
                self._tables_cache = (key, version, out)
        return out, lengths

    # ---- observability --------------------------------------------------
    def stats(self) -> Dict[str, float]:
        with self._lock:
            live = len(self._seqs)
            tokens = self._cached_tokens
            in_use = self._alloc.n_in_use
            slots_in_use = self.n_slots - len(self._free_slots)
            ring_in_use = self.n_sliding_blocks_in_use
        # occupancy: pool pressure the admission test acts on; waste:
        # allocated-but-unfilled token slots (final partial blocks +
        # reserve-ahead) — the paged layout's only fragmentation, so a
        # drifting waste gauge means the block size is wrong for the
        # workload
        return {
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
            "blocks_in_use": in_use,
            "blocks_free": self.n_blocks - in_use,
            "live_sequences": live,
            "cached_tokens": tokens,
            "occupancy": in_use / self.n_blocks,
            "waste_tokens": in_use * self.block_size - tokens,
            "state_slots": self.n_slots,
            "state_slots_in_use": slots_in_use,
            "sliding_blocks": self.n_sliding_blocks,
            "sliding_blocks_in_use": ring_in_use,
            "sliding_occupancy": (ring_in_use / self.n_sliding_blocks
                                  if self.n_sliding_blocks else 0.0),
        }

    def _publish_usage(self) -> None:
        with self._lock:
            in_use = self._alloc.n_in_use
            tokens = self._cached_tokens
            slots_in_use = self.n_slots - len(self._free_slots)
            ring_in_use = self.n_sliding_blocks_in_use
        if self.n_slots:
            telemetry.set_gauge("serving", "state_slots_in_use",
                                slots_in_use)
        if self.ring_blocks:
            telemetry.set_gauge("serving", "kv_sliding_blocks_in_use",
                                ring_in_use)
        telemetry.set_gauge("serving", "kv_blocks_in_use", in_use)
        telemetry.set_gauge("serving", "kv_occupancy_pct",
                            100.0 * in_use / self.n_blocks)
        telemetry.set_gauge("serving", "kv_waste_tokens",
                            in_use * self.block_size - tokens)
