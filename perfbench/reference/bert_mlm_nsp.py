"""Plain reference of BERT pre-training (MLM + NSP): forward, loss,
gradients and AdamW in straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. No kernels, no fusion, no
import from the program under test.

It follows google-research/bert ``modeling.py`` with the departures the
served model (``mxnet_tpu/models/bert.py``) makes, each noted in
``perfbench/configs/bert_base.json`` under ``assumed``: an untied MLM
decoder matrix, LayerNorm epsilon 1e-5, tanh-approximated GELU, the MLM
head applied at every position with the loss masked afterwards.

It also owns what both sides are given: the seeded weights
(:func:`make_weights`) and the seeded batches (:func:`make_batches`).
They are the benchmark's, not the program's.
"""
import math

import numpy as np

MASK_ID = 103
FIRST_WORD_ID = 1000
LN_EPS = 1e-5


def param_shapes(cfg):
    """{parameter name: (shape, kind)} in the program's naming; kind is
    "weight" (N(0, initializer_range)), "zeros" or "ones"."""
    h, i, v = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["vocab_size"])
    out = {
        "bert.word_embed.weight": ((v, h), "weight"),
        "bert.token_type_embed.weight": ((cfg["type_vocab_size"], h),
                                         "weight"),
        "bert.position_embed.weight": (
            (cfg["max_position_embeddings"], h), "weight"),
        "bert.embed_norm.gamma": ((h,), "ones"),
        "bert.embed_norm.beta": ((h,), "zeros"),
    }
    for l in range(cfg["num_hidden_layers"]):
        p = f"bert.layer{l}."
        for proj in ("query", "key", "value", "out"):
            out[p + f"attention.{proj}_proj.weight"] = ((h, h), "weight")
            out[p + f"attention.{proj}_proj.bias"] = ((h,), "zeros")
        out[p + "norm1.gamma"] = ((h,), "ones")
        out[p + "norm1.beta"] = ((h,), "zeros")
        out[p + "ffn1.weight"] = ((i, h), "weight")
        out[p + "ffn1.bias"] = ((i,), "zeros")
        out[p + "ffn2.weight"] = ((h, i), "weight")
        out[p + "ffn2.bias"] = ((h,), "zeros")
        out[p + "norm2.gamma"] = ((h,), "ones")
        out[p + "norm2.beta"] = ((h,), "zeros")
    out.update({
        "bert.pooler.weight": ((h, h), "weight"),
        "bert.pooler.bias": ((h,), "zeros"),
        "mlm_dense.weight": ((h, h), "weight"),
        "mlm_dense.bias": ((h,), "zeros"),
        "mlm_norm.gamma": ((h,), "ones"),
        "mlm_norm.beta": ((h,), "zeros"),
        "mlm_decoder.weight": ((v, h), "weight"),
        "mlm_decoder.bias": ((v,), "zeros"),
        "nsp_classifier.weight": ((2, h), "weight"),
        "nsp_classifier.bias": ((2,), "zeros"),
    })
    return out


def stored_dtype(name, cfg):
    """The type a parameter is kept in: the compute type, except the
    LayerNorm scales and shifts which stay float32 (what
    ``amp.convert_block`` does)."""
    if name.rsplit(".", 1)[-1] in ("gamma", "beta"):
        return "float32"
    return cfg["compute_dtype"]


def make_weights(cfg, seed, device=None):
    """Every parameter, on the device, in one jitted call from the
    seed, in the type it is trained in. Returns {name: array}."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    names = sorted(shapes)
    std = cfg["initializer_range"]

    def make(key):
        out = {}
        for i, n in enumerate(names):
            shape, kind = shapes[n]
            dt = jnp.dtype(stored_dtype(n, cfg))
            if kind == "weight":
                out[n] = (jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
                          * std).astype(dt)
            elif kind == "ones":
                out[n] = jnp.ones(shape, dt)
            else:
                out[n] = jnp.zeros(shape, dt)
        return out

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(make)(key)


def make_batches(cfg, job, seed, n, batch):
    """``n`` distinct host batches (numpy) of ``batch`` sequences:
    (input_ids, token_types, valid_length, mlm_labels, mlm_mask,
    nsp_labels). Sequences are ``seq_len`` positions, ragged
    ``valid_length`` in [valid_min, seq_len] (so the attention kernel's
    padding path runs), ``mlm_share`` of the valid positions masked.
    Every batch holds the same multiset of valid lengths in another
    order, so every step of every seed does the same amount of work."""
    rs = np.random.RandomState(seed % (2 ** 32 - 1))
    T, V = job["seq_len"], cfg["vocab_size"]
    lengths = np.linspace(job["valid_min"], T, batch).round().astype(
        np.int32)
    out = []
    for _ in range(n):
        valid = rs.permutation(lengths).astype(np.int32)
        ids = rs.randint(FIRST_WORD_ID, V, (batch, T)).astype(np.int32)
        pos = np.arange(T)[None, :]
        in_seq = pos < valid[:, None]
        split = (valid * rs.uniform(0.3, 0.7, batch)).astype(np.int32)
        types = ((pos >= split[:, None]) & in_seq).astype(np.int32)
        # exactly round(share * valid) masked positions a row
        score = rs.uniform(size=(batch, T))
        score[~in_seq] = 2.0
        k = np.maximum(1, np.round(job["mlm_share"] * valid)).astype(int)
        kth = np.sort(score, axis=1)[np.arange(batch), k - 1]
        mask = score <= kth[:, None]
        labels = np.where(mask, ids, 0).astype(np.int32)
        ids = np.where(mask, MASK_ID, ids)
        ids = np.where(in_seq, ids, 0).astype(np.int32)
        nsp = rs.randint(0, 2, batch).astype(np.int32)
        out.append((ids, types, valid, labels, mask.astype(np.float32),
                    nsp))
    return out


def batch_work(batch):
    """The counters a batch adds to ``work`` (see perfbench/costs.py)."""
    valid = batch[2].astype(np.int64)
    return {"tokens": int(valid.sum()), "masked": int(batch[4].sum()),
            "valid_sq": int((valid * valid).sum()),
            "sequences": int(valid.shape[0])}


# -- the model -------------------------------------------------------------

def _ln(x, g, b):
    import jax.numpy as jnp
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _dense(x, p, name, q=None):
    w = p[name + ".weight"]
    if q is not None:
        x, w = q(x), q(w)
    return x @ w.T + p[name + ".bias"]


def forward(p, cfg, ids, types, valid, q=None):
    """(mlm logits (B, T, V), nsp logits (B, 2)). ``q`` optionally
    rounds every matmul operand (the lower-precision control)."""
    import jax
    import jax.numpy as jnp

    B, T = ids.shape
    H = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // H
    x = (p["bert.word_embed.weight"][ids]
         + p["bert.position_embed.weight"][jnp.arange(T)][None]
         + p["bert.token_type_embed.weight"][types])
    x = _ln(x, p["bert.embed_norm.gamma"], p["bert.embed_norm.beta"])
    key_ok = (jnp.arange(T)[None, :] < valid[:, None])[:, None, None, :]
    qq = (lambda a: a) if q is None else q
    for l in range(cfg["num_hidden_layers"]):
        n = f"bert.layer{l}."
        qh = _dense(x, p, n + "attention.query_proj", q).reshape(B, T, H, d)
        kh = _dense(x, p, n + "attention.key_proj", q).reshape(B, T, H, d)
        vh = _dense(x, p, n + "attention.value_proj", q).reshape(B, T, H, d)
        s = jnp.einsum("bthd,bshd->bhts", qq(qh), qq(kh)) / math.sqrt(d)
        s = jnp.where(key_ok, s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", qq(a), qq(vh)).reshape(B, T, H * d)
        x = _ln(x + _dense(o, p, n + "attention.out_proj", q),
                p[n + "norm1.gamma"], p[n + "norm1.beta"])
        f = jax.nn.gelu(_dense(x, p, n + "ffn1", q), approximate=True)
        x = _ln(x + _dense(f, p, n + "ffn2", q),
                p[n + "norm2.gamma"], p[n + "norm2.beta"])
    pooled = jnp.tanh(_dense(x[:, 0], p, "bert.pooler", q))
    m = jax.nn.gelu(_dense(x, p, "mlm_dense", q), approximate=True)
    m = _ln(m, p["mlm_norm.gamma"], p["mlm_norm.beta"])
    return _dense(m, p, "mlm_decoder", q), _dense(pooled, p,
                                                  "nsp_classifier", q)


def _ce(logits, labels):
    import jax
    import jax.numpy as jnp
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - pick


def block_loss_sum(p, cfg, rows, q=None):
    """The block's share of the step's loss: masked-LM cross-entropy
    SUMMED over the block's masked positions and NSP cross-entropy
    summed over its rows; the caller divides by the step's totals."""
    import jax.numpy as jnp
    ids, types, valid, labels, mask, nsp = rows
    mlm, nsp_logits = forward(p, cfg, ids, types, valid, q)
    return (jnp.sum(_ce(mlm, labels) * mask),
            jnp.sum(_ce(nsp_logits, nsp)))


def _fp8(x, dtype, top):
    import jax.numpy as jnp
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _fp8_round():
    """Rounding to float8 with one scale a tensor, as fp8 training does
    it: e4m3 for a matmul's operands on the way forward, e5m2 for the
    gradient that comes back through them. The nearest precision below
    bfloat16 (the control of "How correct is decided")."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def q(x):
        return _fp8(x, jnp.float8_e4m3fn, 448.0)

    q.defvjp(lambda x: (q(x), None),
             lambda _, g: (_fp8(g, jnp.float8_e5m2, 57344.0),))
    return q


def follow(cfg, job, weights, batches, hyper, block_rows, lower=False):
    """Follow the first ``len(batches)`` steps from ``weights``.

    Gradients of the whole batch are accumulated over blocks of
    ``block_rows`` rows (so the reference's memory stays under the
    program's), AdamW steps float32 weights, and after each update the
    weights are rounded to the type they are stored in (the
    configuration keeps bfloat16 weights with no float32 master copy).
    ``lower=True`` is the control: every matmul operand rounded to fp8
    (e4m3 forward, e5m2 for the gradient coming back).

    Returns ``{"losses": [...], "grad_norm": {name: float} (first
    step, as the optimizer gets it), "matrices": [names of the leaves
    with two dimensions], "change_norm": {name: float} (after the last
    step)}``."""
    import jax
    import jax.numpy as jnp

    q = _fp8_round() if lower else None
    names = sorted(weights)
    stored = {n: weights[n].dtype for n in names}
    w0 = {n: weights[n].astype(jnp.float32) for n in names}

    @jax.jit
    def block_grads(p, rows, n_masked, n_rows):
        def loss(p):
            a, b = block_loss_sum(p, cfg, rows, q)
            return a / n_masked + b / n_rows
        return jax.value_and_grad(loss)(p)

    @jax.jit
    def accumulate(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @jax.jit
    def adamw(p, g, m, v, t):
        b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["epsilon"]
        lr, wd = hyper["learning_rate"], hyper["wd"]
        out_p, out_m, out_v = {}, {}, {}
        for n in names:
            out_m[n] = b1 * m[n] + (1 - b1) * g[n]
            out_v[n] = b2 * v[n] + (1 - b2) * jnp.square(g[n])
            upd = (out_m[n] / (1 - b1 ** t)) / (
                jnp.sqrt(out_v[n] / (1 - b2 ** t)) + eps) + wd * p[n]
            # the stored type's arithmetic: the update is rounded to it
            # and so is the difference
            st = stored[n]
            out_p[n] = (p[n].astype(st) - (lr * upd).astype(st)
                        ).astype(jnp.float32)
        return out_p, out_m, out_v

    @jax.jit
    def leaf_norms(a):
        return {n: jnp.sqrt(jnp.sum(jnp.square(a[n]))) for n in names}

    @jax.jit
    def diff_norms(a, b):
        return {n: jnp.sqrt(jnp.sum(jnp.square(a[n] - b[n])))
                for n in names}

    with jax.default_matmul_precision("highest"):
        p = dict(w0)
        m = {n: jnp.zeros_like(w0[n]) for n in names}
        v = {n: jnp.zeros_like(w0[n]) for n in names}
        losses, grad_norm = [], None
        for t, batch in enumerate(batches, start=1):
            B = batch[0].shape[0]
            n_masked = max(float(batch[4].sum()), 1.0)
            acc, total = None, 0.0
            for r in range(0, B, block_rows):
                rows = tuple(jnp.asarray(a[r:r + block_rows])
                             for a in batch)
                l, g = block_grads(p, rows, n_masked, float(B))
                total += float(l)
                acc = g if acc is None else accumulate(acc, g)
            losses.append(total)
            if t == 1:
                grad_norm = {n: float(x)
                             for n, x in leaf_norms(acc).items()}
            p, m, v = adamw(p, acc, m, v, float(t))
        change = {n: float(x) for n, x in diff_norms(p, w0).items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "matrices": [n for n in names if w0[n].ndim == 2],
            "change_norm": change}
