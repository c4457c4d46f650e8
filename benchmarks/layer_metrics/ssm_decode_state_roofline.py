"""100 x the least time the chip could take for the traced window's
Mamba-2 decode state steps over the time they took, at the window's
mean live rows per decode step (the cost is in costs_mamba2.py, where
``kernel_roofline`` does not look).  The live rows come from the
program's own counter: ``serving.ssm_state_rw_bytes`` grows by 2 x
state bytes x Mamba-2 layers x live rows a decode step."""

from benchmarks import costs, costs_mamba2, reduce_trace


def read(obs, params):
    red = obs.get("reduction")
    numbers = obs["numbers"]
    moved = numbers.get("counters.serving.ssm_state_rw_bytes")
    steps = numbers.get("counters.serving.paged_decode_steps")
    layers = costs_mamba2.mamba_layers(obs["model"])
    if red is None or not moved or not steps or not layers:
        return None
    match = reduce_trace.matcher(params["patterns"], "any")
    took = red.seconds(match)
    if not took:
        return None
    live_rows = moved / steps / layers / (
        2 * costs_mamba2.state_bytes_per_row(obs["model"]))
    least, bound = costs.min_seconds(
        costs_mamba2.ssm_state_step_cost(obs["model"], live_rows),
        obs["peaks"])
    obs.setdefault("notes", {})["ssm_state_step_cost"] = f"{bound}-bound"
    return 100.0 * red.count(match) * least / took
