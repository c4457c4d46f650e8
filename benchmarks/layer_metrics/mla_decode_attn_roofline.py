"""100 x the least time the chip could take for the traced window's
latent decode attention calls over the time they took, at the window's
mean context per decode step (as paged_attn_roofline; the cost is in
costs_mla_moe.py, where ``kernel_roofline`` does not look)."""

from benchmarks import costs, costs_mla_moe, reduce_trace


def read(obs, params):
    red = obs.get("reduction")
    per_step = obs["numbers"].get("facts.ctx_tokens_per_decode_step")
    if red is None or per_step is None \
            or "kv_lora_rank" not in obs["model"]:
        return None
    match = reduce_trace.matcher(params["patterns"], "any")
    took = red.seconds(match)
    if not took:
        return None
    least, bound = costs.min_seconds(
        costs_mla_moe.mla_decode_attn_cost(obs["model"], per_step),
        obs["peaks"])
    obs.setdefault("notes", {})["mla_decode_attn_cost"] = f"{bound}-bound"
    return 100.0 * red.count(match) * least / took
