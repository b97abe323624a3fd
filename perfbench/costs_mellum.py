"""Operations and bytes the Mellum training step and its kernels NEED,
from shapes and from what the program counted (``perfbench/costs.py``'s
rules: what the mathematics requires — no recomputation, no padding, no
work on positions nobody asked for — so an implementation that does
more reads a lower share, never a higher one; a share above 100% means
a function here counts too much).

Each function takes the configuration and one dict of counts: the
generator's ``work`` (``tokens``, ``sequences``, ``predicted``: every
sequence of a cell is as long as every other) and, where named, what
the program counted on its ``mx.train_step`` spans (``pairs``: token-
expert pairs on held experts, ``touched``: held experts with a row;
both summed over the sparse layers and the steps). Returns
``{"flops": f, "bytes": b}``.
"""
from perfbench.costs import _BYTES


def visible_pairs(T, window=None):
    """(query, key) pairs a causal layer scores over one sequence of
    ``T`` positions: key j <= query i, and j > i - window where there
    is a window."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def attention_pairs(cfg, work):
    """Visible pairs summed over the layers and the sequences."""
    T = work["tokens"] // work["sequences"]
    per = sum(visible_pairs(T, cfg["sliding_window"]
                            if kind.startswith("sliding") else None)
              for kind in cfg["layer_types"])
    return per * work["sequences"]


def attention_fwd_flops(cfg, work):
    """QK^T and PV: 2 x 2 x head_dim FLOPs a visible pair a query head."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * attention_pairs(cfg, work)


def flash_attention_train(cfg, work):
    """What the flash kernels (fwd, dkv) need: two matmuls forward and
    four backward (dV, dP, dQ, dK) a visible pair — 12 FLOPs x head_dim
    x query heads; the backward's recomputation of the scores is not
    counted. Bytes: q, k, v read and o written forward; q, k, v, dO
    read and dQ, dK, dV written backward."""
    H, K, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    item = _BYTES[cfg["torch_dtype"]]
    rows = work["tokens"] * len(cfg["layer_types"])
    return {"flops": 3 * attention_fwd_flops(cfg, work),
            "bytes": item * d * (5 * H + 6 * K) * rows}


def moe_experts_train(cfg, counts):
    """``moe_grouped_matmul`` in a training step: the grouped SwiGLU
    forward (three products with a (hidden, expert width) matrix a
    pair) and the rows' gradient (the same three against the
    transposes): 12 x d x i FLOPs a pair. Bytes: each touched expert's
    three matrices read once a pass, a pair's row in and out of each
    product."""
    d, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = _BYTES[cfg["torch_dtype"]]
    pairs, touched = counts.get("pairs", 0), counts.get("touched", 0)
    return {"flops": 12 * d * i * pairs,
            "bytes": item * 2 * (3 * d * i * touched
                                 + 2 * (d + i) * pairs)}


def moe_wgrad(cfg, counts):
    """``moe_grouped_matmul_wgrad``: x_g^T dy_g for the three matrices,
    6 x d x i FLOPs a pair. Bytes: both operands' rows read for each
    matrix, each touched expert's three gradients written."""
    d, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = _BYTES[cfg["torch_dtype"]]
    pairs, touched = counts.get("pairs", 0), counts.get("touched", 0)
    return {"flops": 6 * d * i * pairs,
            "bytes": item * (3 * d * i * touched + 3 * (d + i) * pairs)}


def train_step(cfg, work):
    """Forward + backward of the steps in ``work``: 3x the forward
    (weights' and activations' gradients). Forward: the four attention
    projections and the router on every token, the scores on the
    visible pairs, the held experts on the pairs the program counted,
    the head's slice on the predicted positions."""
    D, H, K, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    L = len(cfg["layer_types"])
    dense = 2 * D * d * (H + K) + D * cfg["num_experts_published"]
    fwd = (2 * dense * L * work["tokens"]
           + attention_fwd_flops(cfg, work)
           + 6 * D * cfg["moe_intermediate_size"] * work.get("pairs", 0)
           + 2 * D * cfg["vocab_size"] * work["predicted"])
    return {"flops": 3 * fwd, "bytes": 0}


COSTS = {
    "train_step": train_step,
    "flash_attention_train": flash_attention_train,
    "moe_experts_train": moe_experts_train,
    "moe_wgrad": moe_wgrad,
}
