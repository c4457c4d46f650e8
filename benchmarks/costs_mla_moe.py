"""Operations and bytes of the latent family's attention kernels, from
shapes: what the ALGORITHM needs, whatever implements it (costs.py's
rule).  ``model`` is the ``model`` object of a configuration file."""

from __future__ import annotations

from benchmarks import costs


def mla_prefill_attn_cost(model: dict, t: int) -> dict:
    """One causal prefill attention call (one layer of one prompt of
    ``t`` tokens), up-projected: per head the score product over the
    qk width and the value product over the v width, each over the
    causal half of t x t.  2 x t^2 / 2 x H x (192 + 128) FLOPs for
    A.X-K1.  It reads q and k at qk width and v at v width once and
    writes o: compute-bound from a few hundred tokens on."""
    h = model["n_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    return {"per": "call",
            "flops": 2.0 * (t * t / 2.0) * h * (qk + v),
            "bytes": float(t * h * (2 * qk + 2 * v) * costs.dtype_bytes(
                model))}


def mla_decode_attn_cost(model: dict, ctx_tokens_per_step: float) -> dict:
    """One latent decode attention call (one layer of one decode step),
    absorbed: every cached token of every live row is one row of
    kv_lora_rank + qk_rope_head_dim values, read once for all heads;
    per head the score takes the whole row and the value product its
    first kv_lora_rank entries: 576 x 2 B and 4 x 64 x 544 FLOPs a
    cached token for A.X-K1 (121 FLOPs a byte: under the v5e's ridge
    of 240, so the read bounds it)."""
    row = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    per_head = 2.0 * row + 2.0 * model["kv_lora_rank"]
    return {"per": "call",
            "flops": ctx_tokens_per_step * model["n_heads"] * per_head,
            "bytes": ctx_tokens_per_step * row * costs.dtype_bytes(model)}
