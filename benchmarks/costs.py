"""The benchmark's own arithmetic: operations and bytes from shapes.

Copied from the program (models/transformer.py::train_flops_per_token)
and not imported, so that no later PR changes the yardstick.  ``model``
is the ``model`` object of a configuration file (the TransformerConfig
fields).  Every function returns what the ALGORITHM needs, never what
an implementation happens to execute: masked blocks that a kernel
computes anyway and pages it reads past a sequence's end do not count,
which is what makes the quotient a utilisation.  Recomputation under
remat does not count towards train_flops_per_token; a kernel's own
cost counts every call the memory plan makes, each at what it needs.
"""

from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dtype_bytes(model: dict) -> int:
    return _DTYPE_BYTES[model["dtype"]]


def count_params(model: dict) -> int:
    e, hd = model["d_model"], model["n_heads"] * model["head_dim"]
    f, x = model["d_ff"], model["n_experts"]
    per_layer = 2 * e + 4 * e * hd + e * x + 3 * x * e * f
    return model["n_layers"] * per_layer + 2 * model["vocab"] * e + e


def train_flops_per_token(model: dict, t: int) -> float:
    """Matmul FLOPs one trained token needs at sequence length ``t``:
    forward (q/k/v/o projections, the FFN's three matmuls, the unembed,
    and the two attention matmuls over the causal half only) times
    three for forward plus backward.  Recomputed forward passes are not
    counted.  5.84 GFLOP at t=1024, 8.86 at 16384, 12.08 at 32768 for
    the flagship."""
    e, hd = model["d_model"], model["n_heads"] * model["head_dim"]
    f, x = model["d_ff"], model["n_experts"]
    attention = 2 * t * hd  # 2 matmuls x 2 FLOPs x t x hd, causal half
    per_layer = 2 * 4 * e * hd + attention + 2 * 3 * e * f * x
    forward = model["n_layers"] * per_layer + 2 * e * model["vocab"]
    return 3.0 * forward


def attention_flops_share(model: dict, t: int) -> float:
    """Attention's (score and value matmuls) share of the counted FLOPs."""
    hd = model["n_heads"] * model["head_dim"]
    return 3.0 * model["n_layers"] * 2 * t * hd / train_flops_per_token(
        model, t)


def _attn_block_flops(b, tq, tk, h, d, matmuls, visible):
    """``matmuls`` [tq, d] x [d, tk]-sized products per head over the
    ``visible`` share of the score matrix."""
    return 2.0 * matmuls * b * h * tq * tk * d * visible


def _local_attention_shape(model, traffic, config):
    """Per-device (batch, local sequence, local heads, head_dim, sp)."""
    mesh = config.get("mesh") or {}
    dp, sp, tp = (mesh.get(a, 1) for a in ("dp", "sp", "tp"))
    return (traffic["B"] // dp, traffic["T"] // sp,
            model["n_heads"] // tp, model["head_dim"], sp)


def flash_fwd_cost(model: dict, traffic: dict, config: dict) -> dict:
    """Per train step and device, the work the ``flash_fwd`` kernel
    needs.  One chip: one causal call per layer (the save_flash policy
    keeps its residuals, so the backward does not call it again).
    Under sp > 1 the same kernel is the ring step: per layer, ring
    rank r needs its diagonal block (half visible) and the r earlier
    blocks (whole), so a device needs 1/2 + (sp - 1)/2 blocks of FLOPs
    on average and touches 1 + (sp - 1)/2 blocks of data; later blocks
    are wholly masked and need nothing.  The ring path has no saved
    residuals, so under remat the forward runs twice, and both runs
    belong to that memory plan and are counted."""
    b, t, h, d, sp = _local_attention_shape(model, traffic, config)
    nbytes = dtype_bytes(model)
    layers = model["n_layers"]
    if sp == 1:
        flops = layers * _attn_block_flops(b, t, t, h, d, 2, 0.5)
        # q, k, v read and o written once, plus the f32 log-sum-exp
        moved = layers * (4 * b * t * h * d * nbytes + b * h * t * 4)
        return {"per": "step", "flops": flops, "bytes": moved}
    passes = 2 if model.get("remat") else 1
    earlier = (sp - 1) / 2.0  # whole blocks before the diagonal, mean
    flops = passes * layers * _attn_block_flops(
        b, t, t, h, d, 2, 0.5 + earlier)
    # per block touched: q, k, v read; pv (f32) and m, l (f32) written
    per_block = (3 * b * t * h * d * nbytes + b * t * h * d * 4
                 + 2 * b * h * t * 4)
    moved = passes * layers * (1 + earlier) * per_block
    return {"per": "step", "flops": flops, "bytes": moved}


def flash_dkv_cost(model: dict, traffic: dict, config: dict) -> dict:
    """``flash_dkv``: per (q block, k block) pair it needs s = q k^T
    again, dp = do v^T, dv += p^T do and dk += ds^T q: four matmuls over
    the causal half."""
    b, t, h, d, _ = _local_attention_shape(model, traffic, config)
    nbytes = dtype_bytes(model)
    layers = model["n_layers"]
    flops = layers * _attn_block_flops(b, t, t, h, d, 4, 0.5)
    # q, k, v, do read, dk, dv written, lse and delta (f32) read
    moved = layers * (6 * b * t * h * d * nbytes + 2 * b * h * t * 4)
    return {"per": "step", "flops": flops, "bytes": moved}


def flash_dq_cost(model: dict, traffic: dict, config: dict) -> dict:
    """``flash_dq``: s again, dp again, dq += ds k: three matmuls over
    the causal half (the split into two backward kernels is what makes
    s and dp needed twice)."""
    b, t, h, d, _ = _local_attention_shape(model, traffic, config)
    nbytes = dtype_bytes(model)
    layers = model["n_layers"]
    flops = layers * _attn_block_flops(b, t, t, h, d, 3, 0.5)
    moved = layers * (5 * b * t * h * d * nbytes + 2 * b * h * t * 4)
    return {"per": "step", "flops": flops, "bytes": moved}


def context_tokens_read(n_prompt: int, n_generated: int) -> int:
    """Cached tokens one request's decode steps attend, summed over its
    steps: the first token comes from prefill, and decode step j of
    the g - 1 that follow reads the n + j tokens cached by then."""
    g = n_generated
    return (g - 1) * n_prompt + g * (g - 1) // 2


def paged_attn_cost(model: dict, ctx_tokens_per_step: float) -> dict:
    """One ``paged_attn`` call (one layer of one decode step): it has
    to read the K and the V of every cached token of every live row
    once.  Memory-bound by construction: 4 FLOPs per K/V element pair
    against 2 x 2 bytes."""
    h, d = model["n_heads"], model["head_dim"]
    elems = ctx_tokens_per_step * h * d
    return {"per": "call", "flops": 4.0 * elems,
            "bytes": 2.0 * elems * dtype_bytes(model)}


def min_seconds(cost: dict, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak sets it."""
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "memory"))
