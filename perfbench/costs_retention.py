"""Operations and bytes the power-retention kernels NEED, from shapes
and from what the program counted (``perfbench/costs.py``'s rules: what
the mathematics requires, so an implementation that does more reads a
lower share, never a higher one; a share above 100% means a function
here counts too much).

Each function takes the configuration and the counts a reader summed
over the window's spans, and returns ``{"flops": f, "bytes": b}``. The
state is counted at the width the mathematics needs, the d (d + 1) / 2
distinct products of the symmetric degree-2 feature map (8,256 at d =
128) against d values and the normaliser's one: a layout that pads it
(the program's 8,320 by cyclic offset) moves more and reads lower.
"""
from perfbench.costs import _BYTES


def feature_width(cfg):
    """Entries of the symmetric feature map of degree 2 of a head."""
    d = cfg["head_dim"]
    if cfg["retention_degree"] != 2:
        raise ValueError("costs are written for the degree-2 map")
    return d * (d + 1) // 2


def retention_state_bytes(cfg):
    """One sequence's state in one layer: S (feature width x head_dim)
    and z (feature width) a kv head, in the state's dtype."""
    return cfg["num_key_value_heads"] * feature_width(cfg) \
        * (cfg["head_dim"] + 1) * _BYTES[cfg["retention_state_dtype"]]


def _map_flops(cfg):
    """phi(q)^T S for every query head and phi(k) v^T for every kv
    head: 2 FLOPs an entry of S, a position a layer."""
    return 2 * feature_width(cfg) * cfg["head_dim"] \
        * (cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def _qkvy_bytes(cfg):
    """q and y at every query head, k and v at every kv head, in the
    model's dtype, and the gate's float32 a kv head."""
    return (2 * cfg["num_attention_heads"]
            + 2 * cfg["num_key_value_heads"]) * cfg["head_dim"] \
        * _BYTES[cfg["torch_dtype"]] + 4 * cfg["num_key_value_heads"]


def power_retention_step(cfg, counts):
    """The decode step: every active row of every tick (``ssm_rows``
    sums them over the ticks) reads and writes its state once in each
    layer, and reads q, k, v, the gate and writes y beside it.
    HBM-bound: 0.7 FLOP a byte."""
    rows = counts.get("ssm_rows", 0) * cfg["num_hidden_layers"]
    return {"flops": rows * _map_flops(cfg),
            "bytes": rows * (2 * retention_state_bytes(cfg)
                             + _qkvy_bytes(cfg))}


def power_retention_chunked(cfg, counts):
    """The chunked form over a prompt: every valid prompt position
    (``scan_tokens``) needs, in each layer, phi(q) against the state for
    every query head and phi(k) v^T into it for every kv head, and q,
    k, v read and y written; the state stays on the chip and phi is
    never in HBM. The quadratic part inside a chunk is the
    implementation's choice of chunk and is not counted. MXU-bound."""
    tokens = counts.get("scan_tokens", 0) * cfg["num_hidden_layers"]
    return {"flops": tokens * _map_flops(cfg),
            "bytes": tokens * _qkvy_bytes(cfg)}


COSTS = {
    "power_retention_step": power_retention_step,
    "power_retention_chunked": power_retention_chunked,
}
