"""Paged KV cache: block-granular virtual memory for decode contexts.

vLLM-style PagedAttention bookkeeping adapted to this substrate: the
cache is a fixed pool of fixed-size blocks (``block_size`` tokens each)
handed out by a free-list :class:`BlockAllocator`, and every sequence
owns a *block table* mapping its logical token positions to physical
blocks.  Continuous batching lives or dies on this layout — sequences
of wildly different lengths share one arena with zero fragmentation
beyond the final partial block, and a finished (or preempted) request
returns its blocks to the free list for immediate reuse.

Pool layout (layer-major, mirroring the paged-attention kernel shapes):

    k_pool / v_pool : [n_layers, n_blocks, block_size, n_heads, head_dim]

Two decode data paths share this bookkeeping.  The paged fast path
(``DMLC_SERVE_PAGED_ATTN``) keeps device-resident pool twins
(:meth:`device_pools` / :meth:`adopt_device_pools`) and ships only the
tiny int32 :meth:`block_tables_array` per step — the model attends the
pool in place (ops/paged_attention) and no dense view is ever built.
The gather path remains the oracle twin and the sharded-mesh route:
:meth:`PagedKVCache.gather` materializes a dense padded
``[L, B, T, H, D]`` view for a decode batch (whole blocks are copied;
slots past a sequence's length carry garbage the attention mask
ignores), and :meth:`shard_gathered` places that view over a
``parallel.mesh`` — batch over ``dp``, heads over ``tp`` — so the
decode matmuls run sharded under jit.  Prefill attention goes through
the model layer's existing dispatch (Pallas flash on TPU, the
materialized oracle elsewhere); an sp-sharded ring/Ulysses prefill for
very long prompts is future work — the cache is layout-ready for it
(it only ever stores the resulting per-layer K/V).

Thread-safety: all bookkeeping is lock-protected, but the data plane
(write/gather) assumes the engine's single step thread — the same
contract as the training feed.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import DMLCError
from .. import telemetry
from ..concurrency import make_lock

__all__ = ["BlockAllocator", "PagedKVCache", "kv_partition_spec"]


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
    __slots__ = ("blocks", "length")

    def __init__(self) -> None:
        self.blocks: List[int] = []
        self.length = 0


def kv_partition_spec(mesh) -> Optional[tuple]:
    """PartitionSpec for a gathered ``[L, B, T, H, D]`` view over
    ``mesh``: batch over dp, heads over tp, everything else replicated.
    None when the mesh offers no divisible sharding (single device)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_DP, AXIS_TP

    dp = mesh.shape.get(AXIS_DP, 1)
    tp = mesh.shape.get(AXIS_TP, 1)
    if dp <= 1 and tp <= 1:
        return None
    return P(None, AXIS_DP if dp > 1 else None, None,
             AXIS_TP if tp > 1 else None, None)


class PagedKVCache:
    """Block-paged K/V storage for a set of live sequences.

    ``n_layers/n_heads/head_dim`` come from the model config;
    ``n_blocks × block_size`` is the total token capacity shared by all
    concurrent requests.  ``mesh`` (optional) enables
    :meth:`shard_gathered` device placement.
    """

    def __init__(self, n_layers: int, n_heads: int, head_dim: int, *,
                 n_blocks: int = 256, block_size: int = 16,
                 dtype=np.float32, mesh=None):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.mesh = mesh
        shape = (self.n_layers, self.n_blocks, self.block_size,
                 self.n_heads, self.head_dim)
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self.k_pool = np.zeros(shape, dtype)
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self.v_pool = np.zeros(shape, dtype)
        # device twins of the pools for the paged-attention fast path:
        # lazily created, kept in sync block-granularly — host writes
        # (prefill) mark their blocks dirty and device_pools() uploads
        # just those; decode-step scatter happens IN the jitted program,
        # whose updated pools the engine hands back via
        # adopt_device_pools (the host mirror gets the same tokens
        # through append_from_device, which skips the dirty mark)
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._dev_k = None
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._dev_v = None
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._dirty_blocks: set = set()
        # dmlc-check: unguarded(data plane is single-step-thread by contract — class docstring)
        self._upload_jit = None
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

    def can_reserve(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self._alloc.n_free

    def fits_at_all(self, n_tokens: int) -> bool:
        """Whether ``n_tokens`` could EVER be cached, even with the
        whole pool free — the admission-time sanity bound."""
        return self.blocks_for(n_tokens) <= self.n_blocks

    # ---- sequence lifecycle --------------------------------------------
    def allocate(self, seq_id: int, n_tokens: int) -> bool:
        """Register ``seq_id`` with capacity for ``n_tokens``; False
        (and no state change) when the free list cannot cover it."""
        with self._lock:
            if seq_id in self._seqs:
                raise DMLCError(f"sequence {seq_id} already allocated")
            got = self._alloc.alloc_many(self.blocks_for(n_tokens))
            if got is None:
                telemetry.inc("serving", "kv_alloc_failures")
                return False
            ent = _SeqEntry()
            ent.blocks = got
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
            if need <= 0:
                return True
            got = self._alloc.alloc_many(need)
            if got is None:
                telemetry.inc("serving", "kv_alloc_failures")
                return False
            ent.blocks.extend(got)
            self._tables_version += 1
        self._publish_usage()
        return True

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
            total = sum(n for n in needs if n > 0)
            if total == 0:
                return True
            if total > self._alloc.n_free:
                return False
            grew = False
            for ent, need in zip(ents, needs):
                if need <= 0:
                    continue
                got = self._alloc.alloc_many(need)
                assert got is not None  # guarded by the total check
                ent.blocks.extend(got)
                grew = True
            if grew:
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
            self._tables_version += 1
        self._publish_usage()

    def length(self, seq_id: int) -> int:
        with self._lock:
            return self._seq(seq_id).length

    def block_table(self, seq_id: int) -> List[int]:
        with self._lock:
            return list(self._seq(seq_id).blocks)

    def live_sequences(self) -> List[int]:
        with self._lock:
            return list(self._seqs)

    def _seq(self, seq_id: int) -> _SeqEntry:
        ent = self._seqs.get(seq_id)
        if ent is None:
            raise DMLCError(f"unknown sequence {seq_id}")
        return ent

    # ---- data plane -----------------------------------------------------
    def write(self, seq_id: int, k, v, start: Optional[int] = None, *,
              device_synced: bool = False) -> None:
        """Write ``k/v [L, T, H, D]`` at token offset ``start`` (default:
        the current length — append semantics).  Capacity must already
        be reserved (allocate/extend); writing past it raises rather
        than silently growing, keeping the eviction policy in the
        scheduler where it belongs.  ``device_synced`` marks a write
        whose bytes the device pools ALREADY hold (a decode-step
        scatter adopted via :meth:`adopt_device_pools`) — it updates
        the host mirror without dirtying the blocks for re-upload."""
        k = np.asarray(k)
        v = np.asarray(v)
        t = k.shape[1]
        with self._lock:
            ent = self._seq(seq_id)
            pos = ent.length if start is None else int(start)
            end = pos + t
            if self.blocks_for(end) > len(ent.blocks):
                raise DMLCError(
                    f"write past reservation: seq {seq_id} end={end} "
                    f"blocks={len(ent.blocks)}×{self.block_size}")
            blocks = list(ent.blocks)
            new_len = max(ent.length, end)
            self._cached_tokens += new_len - ent.length
            ent.length = new_len
        bs = self.block_size
        off = 0
        touched = set()
        while off < t:
            p = pos + off
            blk = blocks[p // bs]
            slot = p % bs
            n = min(bs - slot, t - off)
            self.k_pool[:, blk, slot:slot + n] = k[:, off:off + n]
            self.v_pool[:, blk, slot:slot + n] = v[:, off:off + n]
            if not device_synced:
                touched.add(blk)
            off += n
        if touched:
            if self._dev_k is not None:
                # write-through: upload NOW, once per prefill/resume,
                # so the decode hot loop never pays an upload — before
                # this, every decode step following a prefill re-synced
                # dirty blocks and the eager scatter dispatch was ~half
                # the decode step wall on small models
                self._upload_blocks(touched)
            else:
                self._dirty_blocks.update(touched)

    def write_many(self, updates, *, device_synced: bool = False) -> None:
        """Batched :meth:`write`: ``updates`` is ``[(seq_id, k, v), ...]``
        with each ``k/v [L, T, H, D]`` appended at that sequence's
        current length.

        One lock acquisition covers the whole batch.  The per-row
        ``write`` calls on the decode commit path were dominated not by
        bytes moved but by lock/GIL handoffs — with a pool of HTTP
        handler threads live, every release is a chance to lose the GIL
        for a scheduler quantum, and the commit walk made one such
        crossing per row per step."""
        if not updates:
            return
        plans = []
        with self._lock:
            for seq_id, k, v in updates:
                k = np.asarray(k)
                v = np.asarray(v)
                t = k.shape[1]
                ent = self._seq(seq_id)
                pos = ent.length
                end = pos + t
                if self.blocks_for(end) > len(ent.blocks):
                    raise DMLCError(
                        f"write past reservation: seq {seq_id} end={end} "
                        f"blocks={len(ent.blocks)}×{self.block_size}")
                self._cached_tokens += end - ent.length
                ent.length = end
                plans.append((list(ent.blocks), pos, t, k, v))
        bs = self.block_size
        touched = set()
        for blocks, pos, t, k, v in plans:
            off = 0
            while off < t:
                p = pos + off
                blk = blocks[p // bs]
                slot = p % bs
                n = min(bs - slot, t - off)
                self.k_pool[:, blk, slot:slot + n] = k[:, off:off + n]
                self.v_pool[:, blk, slot:slot + n] = v[:, off:off + n]
                if not device_synced:
                    touched.add(blk)
                off += n
        if touched:
            if self._dev_k is not None:
                self._upload_blocks(touched)
            else:
                self._dirty_blocks.update(touched)

    def append(self, seq_id: int, k, v) -> None:
        """Append ONE token's ``k/v [L, H, D]`` (the per-decode-step
        write path)."""
        self.write(seq_id, np.asarray(k)[:, None], np.asarray(v)[:, None])

    def append_from_device(self, seq_id: int, k, v) -> None:
        """Append ONE token's ``k/v [L, H, D]`` that the device pools
        already hold (the paged decode program scattered it in place):
        host-mirror bookkeeping only, no dirty mark, no re-upload."""
        self.write(seq_id, np.asarray(k)[:, None], np.asarray(v)[:, None],
                   device_synced=True)

    # ---- device twins (paged-attention fast path) ----------------------
    def _upload_blocks(self, blocks) -> None:
        """Block-granular host→device sync of ``blocks`` into the
        existing device twins.

        Runs through a jitted scatter (eager ``.at[].set`` dispatch cost
        roughly tripled prefill wall on small models).  The block count
        is padded to the next power of two by REPEATING the first
        (index, data) pair — duplicate scatter indices carrying
        identical values are deterministic — so the jit sees a handful
        of shapes total instead of one per count."""
        import jax

        if self._upload_jit is None:
            self._upload_jit = jax.jit(
                lambda pool, idx, data: pool.at[:, idx].set(data))
        idx = np.asarray(sorted(blocks), np.int32)
        n = len(idx)
        padded = 1
        while padded < n:
            padded *= 2
        if padded > n:
            idx = np.concatenate([idx, np.full(padded - n, idx[0],
                                               np.int32)])
        with telemetry.span("serving.kv_upload", stage="serving") as crossed:
            k_blk = self.k_pool[:, idx]
            v_blk = self.v_pool[:, idx]
            crossed["bytes"] = k_blk.nbytes + v_blk.nbytes
            self._dev_k = self._upload_jit(self._dev_k, idx, k_blk)
            self._dev_v = self._upload_jit(self._dev_v, idx, v_blk)
        telemetry.inc("serving", "kv_upload_bytes", crossed["bytes"])

    def device_pools(self):
        """The device-resident ``(k_pool, v_pool)`` twins.  First call
        uploads the whole pool once and flips :meth:`write` into
        write-through mode (each prefill/resume uploads its own blocks
        as it lands); any blocks dirtied BEFORE that first call are
        drained here.  Steady-state decode therefore pays no upload at
        all — the program's in-place scatter keeps the device copy
        freshest and :meth:`adopt_device_pools` installs it."""
        import jax.numpy as jnp

        if self._dev_k is None:
            self._dev_k = jnp.asarray(self.k_pool)
            self._dev_v = jnp.asarray(self.v_pool)
            self._dirty_blocks.clear()
        elif self._dirty_blocks:
            self._upload_blocks(self._dirty_blocks)
            self._dirty_blocks.clear()
        return self._dev_k, self._dev_v

    def adopt_device_pools(self, k_pool, v_pool) -> None:
        """Install the pools a paged decode program returned (its
        in-program scatter made them the freshest copy)."""
        self._dev_k = k_pool
        self._dev_v = v_pool

    def block_tables_array(self, seq_ids: Sequence[int], *,
                           pad_width: Optional[int] = None,
                           pad_batch: Optional[int] = None
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-sequence block tables as one dense int32 array — the
        small indirection the paged-attention kernel ships to the
        device INSTEAD of a gathered cache.

        Returns ``(tables [B, W], lengths [B])``; ``W`` = ``pad_width``
        or the max owned-block count (min 1), ``B`` = ``pad_batch`` or
        ``len(seq_ids)``.  Rows are padded with block 0 — the attention
        mask keeps padded entries unreachable (positions past
        ``lengths``), and dead rows carry length 0.  Like gather's
        ``pad_len``, an insufficient explicit ``pad_width`` is loud.

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

    def gather(self, seq_ids: Sequence[int], *, pad_len: Optional[int] = None,
               pad_batch: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Dense padded view for a decode batch.

        Returns ``(k, v, lengths)`` with k/v ``[L, B, T, H, D]`` and
        lengths ``[B] int32``; ``T`` = ``pad_len`` or the max sequence
        length rounded up to a whole block, ``B`` = ``pad_batch`` or
        ``len(seq_ids)`` (extra rows are zero with length 0 — dead rows
        the decode mask ignores, used to pin the jit batch shape).
        Whole blocks are copied, so slots in [length, T) are garbage by
        contract."""
        with self._lock:
            ents = [self._seq(s) for s in seq_ids]
            tables = [list(e.blocks) for e in ents]
            lens = [e.length for e in ents]
        bs = self.block_size
        max_len = max(lens, default=0)
        need = max(self.blocks_for(max_len) * bs, bs)
        if pad_len is not None:
            # an explicit pad_len pins the jit shape; widening it
            # silently would defeat that, so insufficiency is loud
            if pad_len % bs:
                raise ValueError(f"pad_len {pad_len} not a multiple of "
                                 f"block_size {bs}")
            if pad_len < need:
                raise ValueError(f"pad_len {pad_len} < required {need}")
            t = pad_len
        else:
            t = need
        b = max(pad_batch or 0, len(seq_ids))
        shape = (self.n_layers, b, t, self.n_heads, self.head_dim)
        k_out = np.zeros(shape, self.k_pool.dtype)
        v_out = np.zeros(shape, self.v_pool.dtype)
        for i, (table, n) in enumerate(zip(tables, lens)):
            for j in range(self.blocks_for(n)):
                blk = table[j]
                k_out[:, i, j * bs:(j + 1) * bs] = self.k_pool[:, blk]
                v_out[:, i, j * bs:(j + 1) * bs] = self.v_pool[:, blk]
        lengths = np.zeros(b, np.int32)
        lengths[:len(lens)] = lens
        return k_out, v_out, lengths

    def shard_gathered(self, k: np.ndarray, v: np.ndarray):
        """Place a gathered view over the mesh (batch→dp, heads→tp) so
        decode runs as a sharded jit program.  Falls back to plain
        host→default-device arrays when no mesh was given or the shapes
        do not divide the axes."""
        if self.mesh is None:
            return k, v
        import jax

        spec = kv_partition_spec(self.mesh)
        if spec is None:
            return k, v
        from ..parallel.mesh import AXIS_DP, AXIS_TP

        if (k.shape[1] % max(self.mesh.shape.get(AXIS_DP, 1), 1)
                or k.shape[3] % max(self.mesh.shape.get(AXIS_TP, 1), 1)):
            return k, v
        sh = jax.sharding.NamedSharding(self.mesh, spec)
        return jax.device_put(k, sh), jax.device_put(v, sh)

    # ---- observability --------------------------------------------------
    def stats(self) -> Dict[str, float]:
        with self._lock:
            live = len(self._seqs)
            tokens = self._cached_tokens
            in_use = self._alloc.n_in_use
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
        }

    def _publish_usage(self) -> None:
        with self._lock:
            in_use = self._alloc.n_in_use
            tokens = self._cached_tokens
        telemetry.set_gauge("serving", "kv_blocks_in_use", in_use)
        telemetry.set_gauge("serving", "kv_occupancy_pct",
                            100.0 * in_use / self.n_blocks)
        telemetry.set_gauge("serving", "kv_waste_tokens",
                            in_use * self.block_size - tokens)
