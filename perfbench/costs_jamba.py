"""Operations and bytes the Jamba kernels NEED, from shapes and from
what the program counted (``perfbench/costs.py``'s rules: what the
mathematics requires, so an implementation that does more reads a lower
share, never a higher one; a share above 100% means a function here
counts too much).

Each function takes the configuration and the counts a reader summed
over the window's spans, and returns ``{"flops": f, "bytes": b}``.
FLOPs are counted at the MXU's bf16 peak like every other cost here;
the recurrence runs on the VPU and the EUP, which ``peaks.py`` has no
row for, so both state-space kernels are held to their BYTES: for the
decode update that is its true bound, for the prompt scan it is a bound
the kernel cannot reach (see its metric's ``bound``).
"""
from perfbench.costs import _BYTES


def _recurrent_layers(cfg):
    n, p, o = (cfg["num_hidden_layers"], cfg["attn_layer_period"],
               cfg["attn_layer_offset"])
    return n - sum(1 for l in range(n) if l % p == o)


def _d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def ssm_state_bytes(cfg):
    """One sequence's recurrent state in one layer: h (d_inner x
    d_state in the state's dtype)."""
    return _d_inner(cfg) * cfg["mamba_d_state"] \
        * _BYTES[cfg["ssm_state_dtype"]]


def ssm_state_update(cfg, counts):
    """The decode step of the recurrence: every active row of every
    tick (``ssm_rows`` sums them over the ticks) reads and writes its
    state once in each recurrent layer, and reads x, dt, writes y (the
    model's dtype) and B, C beside it. About 7 vector operations and
    one exponential a state element; HBM-bound."""
    dn, n = _d_inner(cfg), cfg["mamba_d_state"]
    item = _BYTES[cfg["torch_dtype"]]
    rows = counts.get("ssm_rows", 0) * _recurrent_layers(cfg)
    return {"flops": 0,
            "bytes": rows * (2 * ssm_state_bytes(cfg)
                             + (3 * dn + 2 * n) * item)}


def selective_scan(cfg, counts):
    """The scan over a prompt: every valid prompt token
    (``scan_tokens``) needs, in each recurrent layer, x, dt and z read
    and y written at d_inner in the model's dtype, B and C read; the
    state stays on the chip. (T, d_inner, d_state) is not needed in
    HBM, so an implementation that writes it reads a share 8x lower."""
    dn, n = _d_inner(cfg), cfg["mamba_d_state"]
    item = _BYTES[cfg["torch_dtype"]]
    tokens = counts.get("scan_tokens", 0) * _recurrent_layers(cfg)
    return {"flops": 0, "bytes": tokens * (4 * dn + 2 * n) * item}


def flash_decode_paged_attention_layers(cfg, counts):
    """Paged decode attention of the attention layers alone: each reads
    every active sequence's cached keys and values a tick (``ctx`` sums
    the context lengths over slots and ticks); QK^T and PV are
    2 * 2 * heads * head_dim FLOPs a position read."""
    layers = cfg["num_hidden_layers"] - _recurrent_layers(cfg)
    read = layers * counts.get("ctx", 0)
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["torch_dtype"]]
    return {"flops": 4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * read,
            "bytes": kv * read}


COSTS = {
    "ssm_state_update": ssm_state_update,
    "selective_scan": selective_scan,
    "flash_decode_paged_attention_layers":
        flash_decode_paged_attention_layers,
}
