"""100 x the least time the chip could take for the traced window's
prefill attention calls of a model with grouped heads and sliding and
full layers over the time they took.  ``kernel_roofline`` cannot
express it: the cost is in costs_gqa_swa.py, and a call's T, window
and share of its layer are read from its own name path (the program's
``prefill_attn_t<T>_w<W>_c<chunks>`` scope), since the mix has two
lengths and the model two kinds of layer."""

import re

from benchmarks import costs, costs_gqa_swa, reduce_trace


def read(obs, params):
    red = obs.get("reduction")
    if red is None or not obs["model"].get("sliding_window"):
        return None
    kernel = reduce_trace.matcher(params["patterns"], "any")
    scope = re.compile(params["scope"])
    took, least, bounds = 0.0, 0.0, set()
    for rows in red._rows.values():
        for event, self_s, _ in rows:
            at = scope.search(event.path or "")
            if at is None or not kernel(event):
                continue
            t, window, chunks = (int(g) for g in at.groups())
            cost = costs_gqa_swa.gqa_prefill_attn_cost(obs["model"], t,
                                                       window)
            seconds, bound = costs.min_seconds(cost, obs["peaks"])
            took += self_s / red.n_devices
            least += seconds / chunks / red.n_devices
            bounds.add(bound)
    if not took:
        return None
    obs.setdefault("notes", {})["gqa_prefill_attn_cost"] = \
        "-".join(sorted(bounds)) + "-bound"
    return 100.0 * least / took
