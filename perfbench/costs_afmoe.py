"""Operations and bytes the afmoe kernels NEED, from shapes and from
what the program counted (``perfbench/costs.py``'s rules: what the
mathematics requires, so an implementation that does more reads a lower
share, never a higher one; a share above 100% means a function here
counts too much).

Each function takes the configuration and the counts a reader summed
over the window's spans, and returns ``{"flops": f, "bytes": b}``.
"""
from perfbench.costs import _BYTES


def moe_experts(cfg, counts):
    """The held experts' grouped SwiGLU. Every (token, expert) pair on a
    held expert needs three products with a (hidden, expert width)
    matrix, 2 FLOPs a weight; every held expert that a tick (or a
    prefill) touched needs its three matrices read once, and every pair
    its row read and written around each product. ``pairs`` /
    ``touched`` are the decode ticks' counts, summed over the sparse
    layers, ``prefill_*`` the prefills'. HBM-bound in decode (under one
    row an expert), MXU-bound in a long prefill."""
    d, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = _BYTES[cfg["torch_dtype"]]
    pairs = counts.get("pairs", 0) + counts.get("prefill_pairs", 0)
    touched = counts.get("touched", 0) + counts.get("prefill_touched", 0)
    return {"flops": 6 * d * i * pairs,
            "bytes": item * (3 * d * i * touched + 2 * (d + i) * pairs)}


def kv_bytes_per_token_layer(cfg):
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["torch_dtype"]]


def flash_decode_paged_windowed(cfg, counts):
    """Paged decode attention with two kinds of layer: a full layer
    reads each active sequence's whole cache a tick (``ctx`` sums the
    context lengths over slots and ticks), a sliding layer the last
    ``sliding_window`` positions of it (``window_ctx`` sums min(context,
    window)); QK^T and PV are 2 * 2 * heads * head_dim FLOPs a position
    read."""
    kinds = cfg["layer_types"]
    sliding = sum(k.startswith("sliding") for k in kinds)
    read = sliding * counts.get("window_ctx", 0) \
        + (len(kinds) - sliding) * counts.get("ctx", 0)
    return {"flops": 4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * read,
            "bytes": kv_bytes_per_token_layer(cfg) * read}


COSTS = {
    "moe_experts": moe_experts,
    "flash_decode_paged_windowed": flash_decode_paged_windowed,
}
