"""100 x the least time the chip could take for the traced window's
prefill attention calls of the latent family over the time they took.
``kernel_roofline`` cannot express it: the cost is in
costs_mla_moe.py, and a call's T is read from its own name path (the
program's ``prefill_attn_t<T>`` scope), since the mix has two."""

import re

from benchmarks import costs, costs_mla_moe, reduce_trace


def read(obs, params):
    red = obs.get("reduction")
    if red is None or "qk_rope_head_dim" not in obs["model"]:
        return None
    kernel = reduce_trace.matcher(params["patterns"], "any")
    scope = re.compile(params["scope"])
    took, least, bounds = 0.0, 0.0, set()
    for rows in red._rows.values():
        for event, self_s, _ in rows:
            at = scope.search(event.path or "")
            if at is None or not kernel(event):
                continue
            cost = costs_mla_moe.mla_prefill_attn_cost(obs["model"],
                                                       int(at.group(1)))
            seconds, bound = costs.min_seconds(cost, obs["peaks"])
            took += self_s / red.n_devices
            least += seconds / red.n_devices
            bounds.add(bound)
    if not took:
        return None
    obs.setdefault("notes", {})["mla_prefill_attn_cost"] = \
        "-".join(sorted(bounds)) + "-bound"
    return 100.0 * least / took
