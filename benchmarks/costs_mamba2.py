"""Operations and bytes of the Mamba-2 state-space kernels, from
shapes: what the ALGORITHM needs, whatever implements it (costs.py's
rule).  ``model`` is the ``model`` object of a configuration file."""

from __future__ import annotations

STATE_BYTES = 4  # the state is float32 whatever the activations are


def mamba_layers(model: dict) -> int:
    """Layers of the model that carry an SSM state: the letters "M" of
    its layer pattern, cut to its depth."""
    return model.get("layer_pattern", "")[:model["n_layers"]].count("M")


def state_elements(model: dict) -> int:
    """One sequence's state in one Mamba-2 layer: H x P x N values."""
    return (model["mamba_n_heads"] * model["mamba_head_dim"]
            * model["mamba_state"])


def state_bytes_per_row(model: dict) -> int:
    """128 x 64 x 128 x 4 B = 4.19 MB for Nemotron 3 Super."""
    return state_elements(model) * STATE_BYTES


def ssm_state_step_cost(model: dict, live_rows: float) -> dict:
    """One decode state step (one Mamba-2 layer of one decode step):
    every live row's state is read once and written once, 2 x H x P x N
    x 4 B a row, and takes 5 operations an element (the decay's
    product, the outer product and its sum, the read-out's product and
    its sum): 0.625 FLOPs a byte, far under the v5e's ridge of 240, so
    the traffic bounds it.  A dead row costs nothing."""
    elements = live_rows * state_elements(model)
    return {"per": "call", "flops": 5.0 * elements,
            "bytes": 2.0 * elements * STATE_BYTES}


def ssd_chunk_scan_cost(model: dict, t: int, chunk: int = 128) -> dict:
    """The chunked prefill scan of one Mamba-2 layer over one prompt of
    ``t`` tokens.  Inside a chunk of C: C B^T a group (2 C^2 N), the
    decay mask on it a head (C^2) and the masked product with dt x
    (2 C^2 P a head); across chunks the state's read-out, its write
    (2 C P N a head each) and its decay (P N a head).  It reads x, B,
    C and dt and writes y, float32 as the program holds them."""
    h, p = model["mamba_n_heads"], model["mamba_head_dim"]
    g, n = model["mamba_n_groups"], model["mamba_state"]
    n_chunks = -(-t // chunk)
    per_chunk = (g * 2.0 * chunk * chunk * n
                 + h * (chunk * chunk + 2.0 * chunk * chunk * p
                        + 4.0 * chunk * p * n + p * n))
    return {"per": "call", "flops": n_chunks * per_chunk,
            "bytes": float(t * (2 * h * p + 2 * g * n + h) * 4)}
