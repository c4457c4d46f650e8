"""The one general traffic generator.  A mix is a data file under
``benchmarks/traffic/``; nothing here knows a mix's name.

Standard library only: the load-generating child process imports this
module and must never import jax (the benchmark's own process holds the
chip).  Everything is drawn from ``random.Random`` seeded with a string
made of the run's ``--seed``, the mix's name and the item's own
coordinates, so item i of client c is the same in every process that
asks for it, without generating its predecessors.

Serve mixes (``kind: serve``):
  loop            "closed": ``clients`` callers, each sending its next
                  request when the last one returned.  The only loop
                  there is; an open loop comes with the cell that
                  needs it
  prompt_classes  [{name, weight, length: <dist>}]
  class_deck      n: a deck of n cards holds each class in its weight's
                  share, and client c always sends card c mod n, so the
                  mix in flight is the same at every instant and for
                  every seed
  output          <dist> of max_tokens
  <dist>          {dist: "uniform", min, max} |
                  {dist: "lognormal", median, sigma, min, max}

Train mixes (``kind: train``): ``B``, ``T`` and ``records``
{alphabet, max_stride, n_batches}: ``n_batches * B`` records of T + 1
int32 ids, arithmetic progressions over an alphabet spread across the
vocabulary (learnable within a few steps, as chip_smoke.py's are).
"""

from __future__ import annotations

import array
import math
import random


def _rng(*coords) -> random.Random:
    return random.Random("/".join(str(c) for c in coords))


def draw(dist: dict, rng: random.Random) -> int:
    """One integer from a length distribution."""
    kind = dist["dist"]
    if kind == "uniform":
        return rng.randint(int(dist["min"]), int(dist["max"]))
    if kind == "lognormal":
        x = rng.lognormvariate(math.log(dist["median"]), dist["sigma"])
        return int(min(max(round(x), dist["min"]), dist["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def dist_bounds(dist: dict) -> tuple:
    return int(dist["min"]), int(dist["max"])


def _deck(mix):
    """``class_deck`` n: n cards, each class in its weight's share."""
    n = int(mix["class_deck"])
    total = sum(c["weight"] for c in mix["prompt_classes"])
    deck = [c for c in mix["prompt_classes"]
            for _ in range(round(c["weight"] / total * n))]
    if len(deck) != n:
        raise ValueError(f"class_deck {n} does not hold the weights "
                         f"{[c['weight'] for c in mix['prompt_classes']]}")
    return deck


def request(mix_name: str, mix: dict, seed: int, vocab: int,
            client: int, index: int) -> dict:
    """Request ``index`` of ``client``: prompt ids, max_tokens, class."""
    rng = _rng(seed, mix_name, "request", client, index)
    deck = _deck(mix)
    cls = deck[client % len(deck)]
    n_prompt = draw(cls["length"], rng)
    max_tokens = draw(mix["output"], rng)
    prompt = [rng.randrange(vocab) for _ in range(n_prompt)]
    return {"client": client, "index": index, "class": cls["name"],
            "prompt": prompt, "max_tokens": max_tokens}


def _blocks(n: int, block_size: int) -> int:
    return -(-n // block_size)


def warmup_requests(mix: dict, vocab: int, block_size: int) -> list:
    """The fewest decode steps that, each request sent alone, visit
    every program the mix can reach in an engine that compiles one
    prefill program per ``block_size`` bucket of prompt length and one
    decode program per block-table width.  A request of n prompt tokens
    and m output tokens prefills in bucket blocks(n) and decodes at
    widths blocks(n + 1) .. blocks(n + m - 1).  Candidates are the
    bottom and the top of every bucket of every class; the longest
    prompts go first, because a width costs a longer prompt fewer
    steps, and each candidate decodes just far enough to reach the
    widest width it can that nothing before it has visited."""
    bs = block_size
    _, out_max = dist_bounds(mix["output"])
    candidates = set()
    for cls in mix["prompt_classes"]:
        lo, hi = dist_bounds(cls["length"])
        for k in range(_blocks(lo, bs), _blocks(hi, bs) + 1):
            candidates.add((min(hi, k * bs), cls["name"]))
            candidates.add((max(lo, (k - 1) * bs + 1), cls["name"]))
    todo = decode_widths(mix, bs)
    buckets = set()
    plan = []
    for n, name in sorted(candidates, reverse=True):
        reach = {w for w in todo
                 if _blocks(n + 1, bs) <= w <= _blocks(n + out_max - 1, bs)}
        if reach:
            m = max((max(reach) - 1) * bs + 2 - n, 2)
            todo -= set(range(_blocks(n + 1, bs),
                              _blocks(n + m - 1, bs) + 1))
        elif _blocks(n, bs) not in buckets:
            m = 1  # the prefill program alone
        else:
            continue
        buckets.add(_blocks(n, bs))
        plan.append({"class": name, "max_tokens": m,
                     "prompt": [(7 * i + 3) % vocab for i in range(n)]})
    if todo:
        raise ValueError(f"no request of the mix reaches widths {todo}")
    return plan


def decode_widths(mix: dict, block_size: int) -> set:
    """Every block-table width the mix's requests can decode at."""
    _, out_max = dist_bounds(mix["output"])
    widths = set()
    for cls in mix["prompt_classes"]:
        lo, hi = dist_bounds(cls["length"])
        if out_max > 1:
            widths.update(range(_blocks(lo + 1, block_size),
                                _blocks(hi + out_max - 1, block_size) + 1))
    return widths


def token_records(mix_name: str, mix: dict, seed: int, vocab: int):
    """Train mixes: yields each record's payload, T + 1 int32 ids."""
    rec = mix["records"]
    t = int(mix["T"])
    n_alpha = int(rec["alphabet"])
    alphabet = [(i * (vocab // n_alpha) + 7) % vocab for i in range(n_alpha)]
    rng = _rng(seed, mix_name, "records")
    for _ in range(int(rec["n_batches"]) * int(mix["B"])):
        start = rng.randrange(n_alpha)
        stride = rng.randint(1, int(rec["max_stride"]))
        # the progression repeats every n_alpha steps at the latest
        period = array.array("i", (alphabet[(start + stride * j) % n_alpha]
                                   for j in range(n_alpha)))
        yield (period * (t // n_alpha + 1))[:t + 1].tobytes()
