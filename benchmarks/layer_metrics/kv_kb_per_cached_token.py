"""KiB of K/V blocks in use, in the full layers' pool and the sliding
layers' together, per cached token: the cache manager's own counts of
blocks in use and of tokens cached, which the engine sums over decode
steps (``serving.kv_block_steps``, ``serving.kv_sliding_block_steps``,
``serving.kv_cached_token_steps``), a block weighed by the layers of
its kind.  ``ratio`` cannot weigh its terms."""

from benchmarks import costs_gqa_swa


def read(obs, params):
    numbers = obs["numbers"]
    steps = {kind: numbers.get(f"counters.serving.{name}")
             for kind, name in (("full", "kv_block_steps"),
                                ("sliding", "kv_sliding_block_steps"))}
    tokens = numbers.get("counters.serving.kv_cached_token_steps")
    kinds = costs_gqa_swa.layer_kinds(obs["model"])
    if not tokens or not kinds or None in steps.values():
        return None
    block = costs_gqa_swa.kv_bytes_per_token(obs["model"]) \
        * obs["config"]["serve"]["block_size"]
    held = sum(steps[kind] * kinds.count(kind) * block for kind in steps)
    return held / tokens / 1024.0
