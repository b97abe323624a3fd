"""Operations and bytes that the algorithms NEED, from shapes.

These are the numerators of every MFU and roofline share. They count
what the mathematics requires — no recomputation, no padding, no work
on positions nobody asked for — so an implementation that does more
reads a lower share, never a higher one. A share above 100% means a
function here counts too much: fix the function.

Each function takes the configuration (the JSON object of
``perfbench/configs/<config>.json``) and the ``work`` counters that the
traffic generator gathered over the window, and returns
``{"flops": f, "bytes": b}``. Metric files name them by key of
``COSTS``.
"""

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


# -- BERT ------------------------------------------------------------------

def bert_encoder_matmul_params(cfg):
    """Weights that multiply every token: the encoder layers' four
    attention projections and two feed-forward matrices."""
    h, i, n = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    return n * (4 * h * h + 2 * h * i)


def bert_mlm_head_params(cfg):
    """Weights that multiply a MASKED position: the MLM transform and
    the vocabulary decoder (the published model gathers the masked
    positions before this head; positions nobody predicts need none of
    it)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return h * h + h * v


def bert_layer_flops(cfg, valid):
    """Forward FLOPs of one encoder layer over one sequence of
    ``valid`` tokens: matmuls 2 FLOPs a weight a token, attention
    scores and mix 2 * 2 * valid^2 * hidden."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return 2 * valid * (4 * h * h + 2 * h * i) + 4 * valid * valid * h


def bert_pretrain_step(cfg, work):
    """Forward + backward of the steps in ``work``: 3x the forward
    (weights' and activations' gradients). ``work`` carries the sums
    over every sequence of every step: ``tokens`` (valid), ``masked``,
    ``valid_sq`` (sum of valid^2), ``sequences``."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    fwd = (2 * bert_encoder_matmul_params(cfg) * work["tokens"]
           + 4 * h * n * work["valid_sq"]
           + 2 * bert_mlm_head_params(cfg) * work["masked"]
           # pooler + NSP classifier: one position a sequence
           + 2 * (h * h + 2 * h) * work["sequences"])
    return {"flops": 3 * fwd, "bytes": 0}


def flash_attention_train(cfg, work):
    """What the three flash-attention kernels (fwd, dq, dkv) need over
    ``work``: per layer and sequence of ``valid`` tokens two matmuls
    forward (QK^T, PV) and four backward (dV, dP, dQ, dK), each
    2 * valid^2 * hidden FLOPs — the backward's recomputation of the
    scores is not counted. Bytes: q, k, v, o read or written forward;
    q, k, v, o, do read and dq, dk, dv written backward."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    item = _BYTES[cfg.get("compute_dtype", "bfloat16")]
    return {"flops": 12 * h * n * work["valid_sq"],
            "bytes": 12 * h * n * work["tokens"] * item}


# -- decoder ---------------------------------------------------------------

def decoder_layer_params(cfg):
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    return 2 * h * h + 2 * h * kv + 3 * h * i


def decoder_layer_flops(cfg, context):
    """Forward FLOPs of one decoder layer for ONE new token attending
    to ``context`` cached positions."""
    h = cfg["hidden_size"]
    return 2 * decoder_layer_params(cfg) + 4 * context * h


def kv_bytes_per_token_layer(cfg):
    """Keys and values of one position in one layer, in the served
    type."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * d * _BYTES[cfg["torch_dtype"]]


def flash_decode_paged(cfg, work):
    """What paged decode attention needs over ``work``: every layer of
    every tick reads each active sequence's cached keys and values once
    (``context_tokens`` sums the context lengths over slots and ticks)
    and does QK^T and PV, 2 * 2 * hidden FLOPs a cached position. The
    query, the output and the block table are noise beside the cache
    and are not counted. HBM-bound: about 1 FLOP a byte with 4 query
    heads sharing a KV head."""
    h, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    ctx = work["context_tokens"]
    return {"flops": 4 * h * n * ctx,
            "bytes": kv_bytes_per_token_layer(cfg) * n * ctx}


COSTS = {
    "bert_pretrain_step": bert_pretrain_step,
    "flash_attention_train": flash_attention_train,
    "flash_decode_paged": flash_decode_paged,
}


def roofline_seconds(cost, peaks):
    """The least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peaks["flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")
