"""100 x the least time the chip could take for the traced window's
decode attention calls of a model whose full and sliding layers each
have their own K/V heads and widths, and whose sliding layers have a
sink, over the time they took, at the window's mean attended keys a
decode step (the cost is in costs_mimo_v2.py, where ``kernel_roofline``
does not look).  The keys come from the program's own counters:
``serving.attn_full_ctx_tokens`` grows by the live rows' contexts a
decode step, ``serving.attn_sliding_ctx_tokens`` by the same cut to the
window, each the figure of ONE layer of its kind, and the live rows a
step (whose sinks each cost an exponential a head) are
``serving.tokens_generated`` over the steps, one token a row.  One
kernel serves both kinds, so the calls' time is one sum and the least
time is summed over the layers of a step."""

from benchmarks import costs, costs_mimo_v2, reduce_trace


def read(obs, params):
    red = obs.get("reduction")
    numbers = obs["numbers"]
    steps = numbers.get("counters.serving.paged_decode_steps")
    keys = {"full": numbers.get("counters.serving.attn_full_ctx_tokens"),
            "sliding": numbers.get(
                "counters.serving.attn_sliding_ctx_tokens")}
    rows = numbers.get("counters.serving.tokens_generated", 0.0)
    kinds = costs_mimo_v2.layer_kinds(obs["model"])
    if red is None or not steps or not kinds or None in keys.values():
        return None
    match = reduce_trace.matcher(params["patterns"], "any")
    took = red.seconds(match)
    if not took:
        return None
    least, bounds = 0.0, set()
    for kind in kinds:  # one call a layer and decode step
        seconds, bound = costs.min_seconds(
            costs_mimo_v2.decode_attn_cost(obs["model"], kind,
                                           keys[kind] / steps, rows / steps),
            obs["peaks"])
        least += seconds
        bounds.add(bound)
    obs.setdefault("notes", {})["gqa_sink_decode_attn_cost"] = \
        "-".join(sorted(bounds)) + "-bound"
    return 100.0 * red.count(match) / len(kinds) * least / took
