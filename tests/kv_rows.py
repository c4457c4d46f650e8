"""K/V rows in and out of a ``PagedKVCache``'s device pools, by hand:
what a prefill or decode program does to the cache, for the tests of
its bookkeeping."""

import numpy as np


def kv_put(cache, seq_id, k, v, start=None):
    """Write ``k/v [L, T, H, D]`` at token offset ``start`` (default: the
    sequence's length) as a device program would: the length first (a
    write past the reservation raises and moves nothing), then a scatter
    into ``device_pools()`` through the block table, adopted."""
    length = cache.length(seq_id)
    pos = (length if start is None else start) + np.arange(k.shape[1])
    cache.advance_many([(seq_id, int(pos[-1]) + 1 - length)])
    table = np.asarray(cache.block_table(seq_id))
    blk, slot = table[pos // cache.block_size], pos % cache.block_size
    k_pool, v_pool = cache.device_pools()
    cache.adopt_device_pools(k_pool.at[:, blk, slot].set(k),
                             v_pool.at[:, blk, slot].set(v))


def kv_get(cache, seq_ids, **pad):
    """``(k, v [L, B, W * block_size, H, D], lengths [B])`` read back
    through ``block_tables_array(seq_ids, **pad)``.  Slots at or past a
    row's length, dead rows included, hold whatever their padded table's
    pages do."""
    tables, lengths = cache.block_tables_array(seq_ids, **pad)

    def rows(pool):
        paged = np.asarray(pool)[:, tables]  # [L, B, W, bs, H, D]
        return paged.reshape(paged.shape[:2] + (-1,) + paged.shape[4:])

    k_pool, v_pool = cache.device_pools()
    return rows(k_pool), rows(v_pool), lengths
