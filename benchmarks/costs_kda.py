"""Operations and bytes of the delta-rule (KDA) kernels, from shapes:
what the ALGORITHM needs, whatever implements it (costs.py's rule).
``model`` is the ``model`` object of a configuration file."""

from __future__ import annotations

STATE_BYTES = 4  # the state is float32 whatever the activations are


def kda_layers(model: dict) -> int:
    """Layers of the model that carry a delta-rule state: layer i is
    published layer layer_offset + i, MLA where (that + 1) is a
    multiple of layer_group_size, else KDA."""
    if model.get("attention") != "kda_mla":
        return 0
    group, first = model["layer_group_size"], model.get("layer_offset", 0)
    return sum((first + i + 1) % group != 0
               for i in range(model["n_layers"]))


def state_bytes_per_row(model: dict) -> int:
    """One sequence's state in one KDA layer: H x d_k x d_v float32
    (32 x 128 x 128 x 4 B = 2.1 MB for Ling-3.0-flash)."""
    return model["n_heads"] * model["head_dim"] ** 2 * STATE_BYTES


def kda_state_step_cost(model: dict, live_rows: float) -> dict:
    """One decode state step (one KDA layer of one decode step): every
    live row's state is read once and written once, 2 x H x d x d x 4 B
    a row, and takes about 6 operations an element (the decay, the
    correction's product and sum, the write's product and sum, the
    read-out's product; its sum is the 7th on every other): 0.75 FLOPs
    a byte, far under the v5e's ridge of 240, so the traffic bounds it.
    A dead row costs nothing."""
    elements = live_rows * model["n_heads"] * model["head_dim"] ** 2
    return {"per": "call", "flops": 6.0 * elements,
            "bytes": 2.0 * elements * STATE_BYTES}


def kda_chunk_scan_cost(model: dict, t: int, chunk: int = 64) -> dict:
    """The chunked prefill scan of one KDA layer over one prompt of
    ``t`` tokens, per head: inside a chunk of C the two decayed
    C x C products over d_k (q k^T and k k^T, 2 x 2 C^2 d), the
    triangular solve against d_v (C^2 d) and the intra-chunk output
    (2 C^2 d / 2 x 2, lower half); across chunks the state's three
    C x d x d products (read-out, correction, write: 3 x 2 C d^2).  It
    reads q, k, v, the decay (float32, as the program holds them) and
    writes o."""
    h, d = model["n_heads"], model["head_dim"]
    n_chunks = -(-t // chunk)
    per_chunk = (4.0 + 1.0 + 1.0) * chunk * chunk * d + 6.0 * chunk * d * d
    return {"per": "call", "flops": h * n_chunks * per_chunk,
            "bytes": float(t * h * d * 5 * 4)}
