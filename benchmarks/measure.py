"""The benchmark's own metric arithmetic (pure functions, no jax)."""

from __future__ import annotations

import math
import statistics

SLICE_S = 1.0  # the logged per-second readings, not a metric


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it.  No interpolation, so it is always a
    value that was observed."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def in_window(records, t_open: float, t_close: float):
    """Requests whose answer arrived inside the window."""
    return [r for r in records if t_open <= r["t_done"] <= t_close]


def request_failed(rec) -> bool:
    """A request that failed, was refused, or came back short of what
    it asked for without an end-of-sequence token (none is configured)
    counts as failed."""
    return bool(rec.get("error")) or rec.get("status") != 200 \
        or rec.get("n_generated", 0) < 1


def tokens_served(records, t_open: float, t_close: float) -> float:
    """Prompt + generated tokens served inside the window on the
    client's clock.  A request that straddles an edge of the window
    counts by the share of its own duration that lies inside: whole
    requests only would make the count jump by a request's tokens
    (2-3% of a window of long requests) with where the edge happens to
    fall, and a closed loop always has one request per client across
    each edge.  Failed requests serve nothing."""
    served = 0.0
    for r in records:
        if request_failed(r):
            continue
        start, done = r["t_send"], r["t_done"]
        inside = min(done, t_close) - max(start, t_open)
        if inside > 0:
            served += (r["n_prompt"] + r["n_generated"]) * inside / (
                done - start)
    return served


def slice_rates(records, t_open: float, t_close: float) -> list:
    """Tokens served per second in each whole SLICE_S of the window."""
    n = int((t_close - t_open) / SLICE_S)
    return [tokens_served(records, t_open + i * SLICE_S,
                          t_open + (i + 1) * SLICE_S) / SLICE_S
            for i in range(n)]


def serve_window(records, t_open: float, t_close: float) -> dict:
    """End-to-end facts of one serving window on the client's clock.
    ``records`` also holds the requests in flight when the window
    closed, which the clients let finish."""
    seen = in_window(records, t_open, t_close)
    done = [r for r in seen if not request_failed(r)]
    window_s = t_close - t_open
    norm_lat = [(r["t_done"] - r["t_send"]) / r["n_generated"] for r in done]
    rates = slice_rates(records, t_open, t_close)
    return {
        "window_s": window_s,
        "attempted": len(seen),
        "failed": len(seen) - len(done),
        "completed": len(done),
        "prompt_tokens": sum(r["n_prompt"] for r in done),
        "generated_tokens": sum(r["n_generated"] for r in done),
        # the whole window counts: a stall of any length lowers it
        "serve_tok_s": tokens_served(records, t_open, t_close) / window_s,
        # logged beside it, never reported: where the two part, some
        # seconds of the window ran slow
        "median_second_tok_s": statistics.median(rates) if rates else None,
        "norm_lat_p90": percentile(norm_lat, 90) if norm_lat else None,
        "requests_per_s": len(done) / window_s,
        "preemptions": sum(r.get("preemptions", 0) for r in done),
    }


def flatten(prefix: str, tree: dict, out: dict) -> dict:
    """{"a": {"b": 1}} -> {"<prefix>.a.b": 1}; numbers only."""
    for key, value in tree.items():
        name = f"{prefix}.{key}"
        if isinstance(value, dict):
            flatten(name, value, out)
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            out[name] = float(value)
    return out


def delta(after: dict, before: dict) -> dict:
    """Counter growth over the window, name by name."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}
