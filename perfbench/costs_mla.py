"""Operations and bytes the latent-attention kernels NEED, from shapes
and from what the program counted (``perfbench/costs.py``'s rules: what
the mathematics requires, so an implementation that does more reads a
lower share, never a higher one; a share above 100% means a function
here counts too much).

Each function takes the configuration and the counts a reader summed
over the window's spans, and returns ``{"flops": f, "bytes": b}``. The
expert layer's cost is ``costs_afmoe.moe_experts``, which the cell's
metric names as it is.
"""
from perfbench.costs import _BYTES


def latent_row_bytes(cfg):
    """One cached position of one layer as published: the latent and
    the one rotated key all heads share. Not a padded width, and not
    twice (keys and values are the same row)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * _BYTES[cfg["torch_dtype"]]


def flash_decode_paged_latent(cfg, counts):
    """Absorbed decode attention over the cached latents: every layer
    reads each active sequence's cached rows ONCE a tick (``ctx`` sums
    the context lengths over slots and ticks); every head scores a row
    over its latent + rope entries and mixes its latent entries, 2
    FLOPs each. 121 FLOP/byte at the published widths, half of the
    v5e's ridge: HBM-bound, with the MXU at half of its peak beside
    it."""
    read = cfg["num_hidden_layers"] * counts.get("ctx", 0)
    lat, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"flops": 2 * cfg["num_attention_heads"] * (2 * lat + rope)
            * read,
            "bytes": latent_row_bytes(cfg) * read}


COSTS = {
    "flash_decode_paged_latent": flash_decode_paged_latent,
}
