"""Plain reference of pre-training one chip's share of a Mellum decoder
(JetBrains Mellum 2, ``model_type`` ``mellum``): forward, next-token
loss, gradients and AdamW in straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. No kernels, no fusion, no
import from the program under test: its own YaRN table, its own router,
the held experts as a plain loop over every token.

Written from the layer equations of ``perfbench/configs/
mellum2_12b.json`` (positions 0..T-1 of one sequence, causal):

    x0 = E[ids]
    u = RMS(x; ln_in);  q, k, v = u Wq^T, u Wk^T, u Wv^T
    q, k <- RMS over each head's 128 (q_norm, k_norm)        [assumed]
    q, k <- rotation (half-split): sliding layers plain RoPE, full
            layers YaRN's blended frequencies, cos and sin both times
            attention_factor
    scores q . k / sqrt(128), causal; sliding layers keep keys
            i - window < j <= i; 8 query heads a kv head
    x <- x + att Wo^T
    m = RMS(x; ln_mlp);  p = softmax_fp32(m Wr^T) over all 64
    top-8 of p;  w = p[sel] / sum(p[sel])
    x <- x + sum over (sel and held) of w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = RMS(x; ln_f) W_head^T;  loss = mean next-token CE

What experts the chip does not hold would add is left out, as in the
program. ``CONTROLS`` names the reference altered one way; each must
fail the comparison that decides ``correct``.

It also owns what both sides are given: the seeded weights
(:func:`make_weights`) and the seeded batches (:func:`make_batches`).
"""
import functools
import math

import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"

#: the reference altered one way (``follow(control=...)``): matmul
#: operands in fp8 (e4m3 forward, e5m2 for the gradient coming back);
#: the window ignored on sliding layers; plain rotation in place of
#: YaRN on full layers; attention_factor left out; top-7 for top-8; the
#: top-8 weights not normalised
CONTROLS = ("fp8", "no_window", "plain_rope_full", "no_attention_factor",
            "top7", "no_topk_norm")

LAYER_ROLES = ("ln_in", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
               "ln_mlp", "router", "ex_gate", "ex_up", "ex_down")


def param_shapes(cfg):
    """{parameter name: (shape, kind)} in the program's naming; kind is
    "weight" (N(0, initializer_range)), "embedding" (N(0,
    embedding_std)), "closing" (a residual branch's last matrix, wo and
    the experts' down: N(0, residual_out_std)) or "ones" (a norm's
    gain)."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, K, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    I, E, n = (cfg["moe_intermediate_size"], cfg["num_experts_published"],
               cfg["num_experts"])
    layer = {"ln_in": (D,), "wq": (H * d, D), "wk": (K * d, D),
             "wv": (K * d, D), "q_norm": (d,), "k_norm": (d,),
             "wo": (D, H * d), "ln_mlp": (D,), "router": (E, D),
             "ex_gate": (n, D, I), "ex_up": (n, D, I),
             "ex_down": (n, I, D)}
    assert tuple(layer) == LAYER_ROLES
    out = {"model.embed_tokens.weight": ((V, D), "embedding"),
           "model.norm.gamma": ((D,), "ones"),
           "lm_head.weight": ((V, D), "weight")}
    for l in range(cfg["num_hidden_layers"]):
        for role, shape in layer.items():
            out[f"model.layers.{l}.{role}"] = (
                shape, "ones" if len(shape) == 1 else
                "closing" if role in ("wo", "ex_down") else "weight")
    return out


def stored_dtype(name, cfg):
    """The type a parameter is kept in: the configuration's, except the
    norms' gains, which stay float32."""
    kind = param_shapes(cfg)[name][1]
    return "float32" if kind == "ones" else cfg["torch_dtype"]


def make_weights(cfg, seed, device=None):
    """Every parameter, on the device, in one jitted call from the
    seed, in the type it is trained in. Returns {name: array}."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    names = sorted(shapes)
    std = {"weight": cfg["initializer_range"],
           "embedding": cfg["embedding_std"],
           "closing": cfg["residual_out_std"]}

    def make(key):
        out = {}
        for i, n in enumerate(names):
            shape, kind = shapes[n]
            if kind in std:
                out[n] = (jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
                          * std[kind]).astype(jnp.dtype(cfg["torch_dtype"]))
            else:
                out[n] = jnp.ones(shape, jnp.float32)
        return out

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(make)(key)


def make_batches(cfg, job, seed, n, batch):
    """``n`` distinct host batches (numpy) of ``batch`` packed
    sequences: (input_ids, labels, label_mask). Every position is
    valid, ids are drawn uniformly from the vocabulary slice, a
    position's label is the next token and the last position of a
    sequence predicts nothing (label 0, mask 0)."""
    rs = np.random.RandomState(seed % (2 ** 32 - 1))
    T, V = job["seq_len"], cfg["vocab_size"]
    out = []
    for _ in range(n):
        ids = rs.randint(0, V, (batch, T)).astype(np.int32)
        labels = np.concatenate(
            [ids[:, 1:], np.zeros((batch, 1), np.int32)], axis=1)
        mask = np.ones((batch, T), np.float32)
        mask[:, -1] = 0.0
        out.append((ids, labels, mask))
    return out


def batch_work(batch):
    """The counters a batch adds to ``work`` (perfbench/costs_mellum)."""
    B, T = batch[0].shape
    return {"tokens": B * T, "sequences": B,
            "predicted": int(batch[2].sum())}


# -- the model -------------------------------------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * g


def yarn_frequencies(rope, dim):
    """The ``dim`` / 2 rotation frequencies of a full layer
    (``rope_type`` ``yarn``): below the first correction dim the plain
    ones, above the second those of positions ``factor`` times closer,
    a linear ramp between (the dims at which a frequency turns
    ``beta_fast`` / ``beta_slow`` times over the original context)."""
    base = float(rope["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return plain / rope["factor"] * ramp + plain * (1 - ramp)


def rotation(cfg, kind, T, control=None):
    """(cos, sin), each (T, 64) float32, of a layer kind."""
    rope = cfg["rope_parameters"][kind]
    dim = cfg["head_dim"]
    scale = 1.0 if control == "no_attention_factor" \
        else rope.get("attention_factor", 1.0)
    if rope["rope_type"] == "yarn" and control != "plain_rope_full":
        inv = yarn_frequencies(rope, dim)
    else:
        inv = float(rope["rope_theta"]) ** (
            -np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return ((np.cos(ang) * scale).astype(np.float32),
            (np.sin(ang) * scale).astype(np.float32))


def _rotate(x, cos, sin):
    """Half-split rotation of (T, heads, 128)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, window, block, qq):
    """Causal attention of one sequence, scores a block of queries at a
    time: q (T, H, d), k, v (T, K, d); keys i - window < j <= i
    (`window` a number, so that both layer kinds are one program; None:
    every earlier key)."""
    import jax
    import jax.numpy as jnp

    T, H, d = q.shape
    rep = H // k.shape[1]
    kf, vf = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = min(block, T)
    if window is None:
        window = T      # every earlier key

    @jax.checkpoint
    def rows(args):
        qb, i0 = args
        s = jnp.einsum("thd,shd->hts", qq(qb), qq(kf)) / math.sqrt(d)
        i = i0 + jnp.arange(block)[:, None]
        j = jnp.arange(T)[None, :]
        keep = (j <= i) & (j > i - window)
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", qq(p), qq(vf))

    out = jax.lax.map(rows, (q.reshape(T // block, block, H, d),
                             jnp.arange(0, T, block)))
    return out.reshape(T, H * d)


def route(cfg, lp, m, control=None):
    """(sel (T, k), w (T, k)): the softmax's top-k over all published
    experts, the picked probabilities normalised to sum to one."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"] - (control == "top7")
    p = jax.nn.softmax(m @ lp["router"].T, axis=-1)
    w, sel = jax.lax.top_k(p, k)
    if cfg["norm_topk_prob"] and control != "no_topk_norm":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def experts(cfg, lp, m, qq, control=None):
    """The held experts' part of the routed sum, (T, D): every held
    expert on every token, one after the other, weighted by what the
    router gave it (zero where it was not picked)."""
    import jax
    import jax.numpy as jnp

    sel, w = route(cfg, lp, m, control)
    lo = cfg["held_experts_lo"]

    @jax.checkpoint     # an expert's activations are rebuilt, not kept
    def add_expert(out, expert):
        e, wg, wu, wd = expert
        on = jnp.sum(jnp.where(sel == lo + e, w, 0.0), axis=-1)
        h = jax.nn.silu(qq(m) @ qq(wg)) * (qq(m) @ qq(wu))
        return out + on[:, None] * (qq(h) @ qq(wd)), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (jnp.arange(cfg["num_experts"]), lp["ex_gate"], lp["ex_up"],
         lp["ex_down"]))
    return out


def layer_params(p, l):
    return {r: p[f"model.layers.{l}.{r}"] for r in LAYER_ROLES}


def layer_kind(cfg, kind, T, control=None):
    """What makes a layer of a kind: (cos, sin, window), the window a
    number (T where every earlier key is kept)."""
    cos, sin = rotation(cfg, kind, T, control)
    window = cfg["sliding_window"] \
        if kind == SLIDING and control != "no_window" else T
    return cos, sin, window


def layer(cfg, lp, x, kind, block, qq, control=None):
    """One layer on one sequence, x (T, D); `kind` is a layer type or
    what `layer_kind` made of one."""
    T = x.shape[0]
    H, K, d, eps = (cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"],
                    cfg["rms_norm_eps"])
    u = _rms(x, lp["ln_in"], eps)
    q = _rms((qq(u) @ qq(lp["wq"]).T).reshape(T, H, d), lp["q_norm"], eps)
    k = _rms((qq(u) @ qq(lp["wk"]).T).reshape(T, K, d), lp["k_norm"], eps)
    v = (qq(u) @ qq(lp["wv"]).T).reshape(T, K, d)
    cos, sin, window = layer_kind(cfg, kind, T, control) \
        if isinstance(kind, str) else kind
    att = _attention(_rotate(q, cos, sin), _rotate(k, cos, sin), v,
                     window, block, qq)
    x = x + qq(att) @ qq(lp["wo"]).T
    m = _rms(x, lp["ln_mlp"], eps)
    return x + experts(cfg, lp, m, qq, control)


def _rounder(control):
    return _fp8_round() if control == "fp8" else (lambda a: a)


def head_loss_sum(hp, cfg, x, labels, mask, qq):
    """Next-token cross-entropy SUMMED over a sequence's predicted
    positions, from the last layer's output x (T, D); hp holds
    ``model.norm.gamma`` and ``lm_head.weight``."""
    import jax
    import jax.numpy as jnp

    logits = qq(_rms(x, hp["model.norm.gamma"], cfg["rms_norm_eps"])) \
        @ qq(hp["lm_head.weight"]).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - pick) * mask)


def sequence_loss_sum(p, cfg, ids, labels, mask, control=None, block=512):
    """The whole model on one sequence of ids (T,): its loss SUMMED
    over the predicted positions (the caller divides by the step's
    count). What the tests differentiate; ``follow`` walks the same
    functions a layer at a time."""
    qq = _rounder(control)
    x = p["model.embed_tokens.weight"][ids]
    for l, kind in enumerate(cfg["layer_types"]):
        x = layer(cfg, layer_params(p, l), x, kind, block, qq, control)
    return head_loss_sum(p, cfg, x, labels, mask, qq)


def _fp8_round():
    """Rounding to float8 with one scale a tensor, as fp8 training does
    it: e4m3 for a matmul's operands on the way forward, e5m2 for the
    gradient that comes back through them; by ``reduce_precision``
    (IEEE-style: finite up to 240 and 57,344), never by a cast there
    and back, which the compiler may drop. The nearest precision below
    bfloat16."""
    import jax
    import jax.numpy as jnp

    def rnd(x, e, m, top):
        s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return jax.lax.reduce_precision(x * s, e, m) / s

    @jax.custom_vjp
    def q(x):
        return rnd(x, 4, 3, 240.0)

    q.defvjp(lambda x: (q(x), None),
             lambda _, g: (rnd(g, 5, 2, 57344.0),))
    return q


def follow(cfg, job, weights, batches, hyper, block_rows, control=None):
    """Follow the first ``len(batches)`` steps from ``weights``.

    Gradients of the whole batch are accumulated a sequence at a time
    (``block_rows`` is 1) and a layer at a time, scores a block of
    queries at a time, so that the float32 gradient sum and moments of
    every parameter leave room; AdamW's arithmetic is float32, and
    after each update the weights are rounded to the type they are
    stored in (bfloat16 with no float32 master copy; the norms' gains
    float32). ``control`` is one of ``CONTROLS``.

    Returns ``{"losses": [...], "grad_norm": {name: float} (first
    step, as the optimizer gets it), "matrices": [names of the leaves
    with two or more dimensions], "change_norm": {name: float} (after
    the last step)}``."""
    import jax
    import jax.numpy as jnp

    if control is not None and control not in CONTROLS:
        raise ValueError(f"no control {control!r}: {CONTROLS}")
    if block_rows != 1:
        raise ValueError("the reference follows a sequence at a time")
    names = sorted(weights)
    stored = {n: weights[n].dtype for n in names}
    block = job.get("reference_query_block", 512)
    f32 = lambda t: {n: a.astype(jnp.float32)  # noqa: E731
                     for n, a in t.items()}

    qq = _rounder(control)
    kinds = cfg["layer_types"]
    HEAD = ("model.norm.gamma", "lm_head.weight")
    EMBED = "model.embed_tokens.weight"

    # The weights are kept in their stored type (every value they take
    # is one of it) and widened where they are used. A sequence's
    # gradient is taken a layer at a time, by hand: forward keeping each
    # layer's input, then back from the loss, each layer's float32
    # gradient added to the step's sum in place as it appears. One
    # layer's widened weights and gradient are alive at a time, so that
    # the float32 sum and moments of 595M parameters leave room.
    # `kind` is (cos, sin, window) as values: both layer kinds run one
    # compiled program each way
    def one_layer(kind):
        return lambda lp32, x: layer(cfg, lp32, x, kind, block, qq,
                                     control)

    layer_fwd = jax.jit(lambda lp, x, kind: one_layer(kind)(f32(lp), x))

    @functools.partial(jax.jit, donate_argnums=1)
    def layer_bwd(lp, acc, x, dx, kind):
        _, vjp = jax.vjp(one_layer(kind), f32(lp), x)
        g, dx = vjp(dx)
        return {r: acc[r] + g[r] for r in acc}, dx

    @functools.partial(jax.jit, donate_argnums=1)
    def head_bwd(hp, acc, x, labels, mask, n_predicted):
        l, (g, dx) = jax.value_and_grad(
            lambda hp32, x: head_loss_sum(hp32, cfg, x, labels, mask, qq)
            / n_predicted, argnums=(0, 1))(f32(hp), x)
        return l, {n: acc[n] + g[n] for n in acc}, dx

    embed_bwd = jax.jit(lambda acc, ids, dx: acc.at[ids].add(dx),
                        donate_argnums=0)

    def add_seq_grads(p, acc, ids, labels, mask, n_predicted):
        """(the sequence's share of the loss, acc + its gradient); acc
        is {name: float32 array} and is consumed."""
        lps = [{r: p[f"model.layers.{l}.{r}"] for r in LAYER_ROLES}
               for l in range(len(kinds))]
        made = {k: tuple(jnp.asarray(a) for a in layer_kind(
            cfg, k, ids.shape[0], control)) for k in set(kinds)}
        xs = [p[EMBED][ids].astype(jnp.float32)]
        for lp, kind in zip(lps, kinds):
            xs.append(layer_fwd(lp, xs[-1], made[kind]))
        acc = dict(acc)
        sub = {n: acc.pop(n) for n in HEAD}
        loss, sub, dx = head_bwd({n: p[n] for n in HEAD}, sub, xs.pop(),
                                 labels, mask, n_predicted)
        acc.update(sub)
        for l in reversed(range(len(kinds))):
            pre = f"model.layers.{l}."
            sub = {r: acc.pop(pre + r) for r in LAYER_ROLES}
            sub, dx = layer_bwd(lps[l], sub, xs.pop(), dx, made[kinds[l]])
            acc.update({pre + r: v for r, v in sub.items()})
        acc[EMBED] = embed_bwd(acc[EMBED], ids, dx)
        return loss, acc

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def adamw(p, g, m, v, t):
        b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["epsilon"]
        lr, wd = hyper["learning_rate"], hyper["wd"]
        out_p, out_m, out_v = {}, {}, {}
        for n in names:
            out_m[n] = b1 * m[n] + (1 - b1) * g[n]
            out_v[n] = b2 * v[n] + (1 - b2) * jnp.square(g[n])
            upd = (out_m[n] / (1 - b1 ** t)) / (
                jnp.sqrt(out_v[n] / (1 - b2 ** t)) + eps) \
                + wd * p[n].astype(jnp.float32)
            # the stored type's arithmetic: the update is rounded to it
            # and so is the difference
            out_p[n] = p[n] - (lr * upd).astype(stored[n])
        return out_p, out_m, out_v

    @jax.jit
    def leaf_norms(a):
        return {n: jnp.sqrt(jnp.sum(jnp.square(a[n]))) for n in names}

    @jax.jit
    def diff_norms(a, b):
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            a[n].astype(jnp.float32) - b[n].astype(jnp.float32))))
            for n in names}

    zeros = jax.jit(lambda t: {n: jnp.zeros(t[n].shape, jnp.float32)
                               for n in names})
    with jax.default_matmul_precision("highest"):
        p, m, v = weights, None, None
        losses, grad_norm = [], None
        for t, (ids, labels, mask) in enumerate(batches, start=1):
            n_predicted = max(float(mask.sum()), 1.0)
            acc, total = zeros(weights), 0.0
            for r in range(ids.shape[0]):
                l, acc = add_seq_grads(
                    p, acc, jnp.asarray(ids[r]), jnp.asarray(labels[r]),
                    jnp.asarray(mask[r]), n_predicted)
                total += float(l)
            losses.append(total)
            if t == 1:
                grad_norm = {n: float(x)
                             for n, x in leaf_norms(acc).items()}
            if m is None:       # the moments exist from the first update on
                m, v = zeros(weights), zeros(weights)
            p, m, v = adamw(p, acc, m, v, float(t))
            del acc
        change = {n: float(x) for n, x in diff_norms(p, weights).items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "matrices": [n for n in names if weights[n].ndim >= 2],
            "change_norm": change}
