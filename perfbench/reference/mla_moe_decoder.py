"""Plain reference of a latent-attention (MLA) decoder over a sparse
expert layer (Sarvam-105B, ``model_type`` ``sarvam_mla``; the DeepSeek-V2
layer): one full forward in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``, no kernels, no cache, no
batching, no import from the program under test. It is the
NON-ABSORBED form: every head's keys and values are materialised from
the latent and a full causal softmax runs over them, where the
program's decode attends the cached latents themselves with ``W_uk``
folded into the query and ``W_uv`` onto the output; the check holds
that re-association. It computes ONE CHIP'S SHARE of the stated
deployment, as the configuration file cuts it: every token is scored
and its top-k picked over all ``num_experts_published`` experts, and the
routed sum runs over the picked experts that are among the
``num_experts`` held here (experts ``held_experts_lo`` onward).

The layer, from the published config (x is (T, hidden), eps 1e-6)::

    h0 = E[ids]                                    (embedding unscaled)
    u  = RMSNorm(x; g_in)
    q  = RMSNorm over each head's 192 of u Wq^T (g_q) = [q_nope 128 | q_rope 64]
    a  = u Wkv_a^T (576);  c = RMSNorm(a[:512]; g_kv);  k_rope = a[512:]
    q_rope, k_rope rotated at the position, YaRN frequencies over the 64
    [k_nope_h | v_h] = c Wkv_b_h^T  (512 -> 128 + 128);  k_h = [k_nope_h | k_rope]
    att_h = softmax(scale q_h k_h^T + causal mask) v_h
            scale = 192^-1/2 * (0.1 ln 40 + 1)^2
    x  = x + concat_h(att_h) Wo^T
    m  = RMSNorm(x; g_mlp)
    layer 0: f = SwiGLU(m)     later: s = sigmoid(m Wr^T) in float32
           sel = top-8(s + b);  w = 2.5 * s[sel] / sum(s[sel])
           f = SwiGLU_shared(m) + sum over sel of w_e * SwiGLU_e(m)
    x  = x + f;   logits = RMSNorm(x_L; g_f) W_head^T

What the catalog's config leaves open is listed under ``assumed`` in
the configuration file. Computed in blocks: attention a block of query
rows at a time, an expert over the rows routed to it, gathered into a
block of fixed size (a layer that overflows it runs again with a larger
block: nothing is dropped). It runs after the program's state is freed,
layer by layer, each layer's weights made again from the seed by the
function that made the served ones.
"""
import math

import numpy as np

_ATTN = ("ln_in", "wq", "q_norm", "wkv_a", "kv_norm", "wkv_b", "wo",
         "ln_mlp")
DENSE_LEAVES = _ATTN + ("gate", "up", "down")
MOE_LEAVES = _ATTN + ("router", "bias", "sh_gate", "sh_up", "sh_down",
                      "ex_gate", "ex_up", "ex_down")
CONTROLS = ("fp8", "no_rope_term", "no_mscale", "plain_rope",
            "no_latent_norm", "top7", "no_bias")


def is_moe(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def _shapes(cfg):
    h, v, H = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    lat, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    i, m = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    n, e = cfg["num_experts"], cfg["num_experts_published"]
    return {"ln_in": (h,), "wq": (H * (nope + rope), h),
            "q_norm": (nope + rope,), "wkv_a": (lat + rope, h),
            "kv_norm": (lat,), "wkv_b": (H * (nope + dv), lat),
            "wo": (h, H * dv), "ln_mlp": (h,),
            "gate": (i, h), "up": (i, h), "down": (h, i),
            "router": (e, h), "bias": (e,),
            "sh_gate": (m, h), "sh_up": (m, h), "sh_down": (h, m),
            # the held experts stacked, input-major: x @ W
            "ex_gate": (n, h, m), "ex_up": (n, h, m),
            "ex_down": (n, m, h),
            "embed": (v, h), "norm": (h,), "head": (v, h)}


class Weights:
    """The seeded weights, made on the device in the served type:
    N(0, initializer_range) matrices, unit norm scales, a selection
    bias N(0, router_bias_std) (small and non-zero, so it changes some
    selections), the embedding N(0, embedding_std). One jitted call a
    layer (one executable for the dense layers, one for the sparse),
    one for embedding, final norm and head; the served copy and the
    reference's layer-by-layer copy come from the same calls with the
    same keys."""

    def __init__(self, cfg, seed, device=None):
        import jax
        import jax.numpy as jnp

        shapes, std = _shapes(cfg), cfg["initializer_range"]
        stds = {"bias": cfg["router_bias_std"],
                "embed": cfg.get("embedding_std", std)}
        dt = jnp.dtype(cfg["torch_dtype"])

        def leaf(k, name, i):
            if len(shapes[name]) == 1 and name != "bias":
                return jnp.ones(shapes[name], dt)
            return (jax.random.normal(jax.random.fold_in(k, i),
                                      shapes[name], jnp.float32)
                    * stds.get(name, std)).astype(dt)

        def maker(names):
            return jax.jit(lambda k: {n: leaf(k, n, i)
                                      for i, n in enumerate(names)})

        self._dense = maker(DENSE_LEAVES)
        self._moe = maker(MOE_LEAVES)
        self._ends = maker(("embed", "norm", "head"))
        root = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        self._root = jax.device_put(root, device) \
            if device is not None else root
        self._fold = jax.random.fold_in
        self.cfg = cfg
        self.num_layers = cfg["num_hidden_layers"]

    def layer(self, l):
        make = self._moe if is_moe(self.cfg, l) else self._dense
        return make(self._fold(self._root, l + 1))

    def ends(self):
        return self._ends(self._fold(self._root, 0))

    def all(self):
        out = dict(self.ends())
        out["layers"] = [self.layer(l) for l in range(self.num_layers)]
        return out


def make_weights(cfg, seed, device=None):
    return Weights(cfg, seed, device).all()


# -- the model ---------------------------------------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * g


def yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg, with_mscale=True):
    """192^-1/2, times mscale(factor, mscale_all_dim)^2."""
    rs = cfg["rope_scaling"]
    scale = cfg["q_head_dim"] ** -0.5
    if with_mscale:
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope_frequencies(cfg, yarn=True):
    """The qk_rope_head_dim / 2 rotation frequencies: plain
    theta^(-2i/dim), or with them the ``deepseek_yarn`` blend, which
    keeps a dim that turns more than beta_fast times over the original
    positions, slows one that turns less than beta_slow times by
    ``factor``, and ramps linearly between."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    half = dim // 2
    inv = base ** (-np.arange(half, dtype=np.float64) / half)
    if not yarn:
        return inv.astype(np.float32)
    rs = cfg["rope_scaling"]

    def dim_turning(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_turning(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_turning(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (inv / rs["factor"] * ramp + inv * (1 - ramp)) \
        .astype(np.float32)


def _rope(x, pos, inv):
    """Rotate-half rotary embedding on (T, H, d) at positions (T,)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fp8_round(x):
    """Round to float8 e4m3 with one scale a tensor: the nearest
    precision below bfloat16."""
    import jax.numpy as jnp
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def bf16_round(x):
    """Round to bfloat16: the program's own arithmetic."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


ROUNDERS = {"fp8": fp8_round, "bf16": bf16_round}


def route(cfg, m, router, bias, top_k=None, use_bias=True):
    """(sel (T, k), w (T, k)): sigmoid scores in float32 over every
    published expert, top-k of score + bias, weights over the picked."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(m @ router.T)
    k = top_k or cfg["num_experts_per_tok"]
    _, sel = jax.lax.top_k(s + bias if use_bias else s, k)
    picked = jnp.take_along_axis(s, sel, 1)
    return sel, cfg["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)


def _swiglu(r, h, g, u, dn):
    """Dense convention: y = x @ W.T."""
    import jax
    return r(jax.nn.silu(h @ g.T) * (h @ u.T)) @ dn.T


def _routed(cfg, control, w, m, cap=None):
    """The held experts' part of the routed sum for rows m (T, D):
    (sum over picked AND held experts of w_e * SwiGLU_e(m), overflow,
    sel). An expert's rows are gathered into a block of `cap` rows
    (every row when None); `overflow` counts the rows past it."""
    import jax
    import jax.numpy as jnp

    r = ROUNDERS.get(control, lambda a: a)
    lo, n = cfg.get("held_experts_lo", 0), cfg["num_experts"]
    T = m.shape[0]
    k = cfg["num_experts_per_tok"] - 1 if control == "top7" else None
    sel, wt = route(cfg, m, w["router"], w["bias"], k,
                    control != "no_bias")
    cap = T if cap is None else min(cap, T)
    mz = jnp.concatenate([r(m), jnp.zeros((1, m.shape[1]))], 0)

    def expert(e, carry):
        out, over = carry
        hit = sel == lo + e                               # (T, k)
        we = jnp.sum(jnp.where(hit, wt, 0.0), -1)
        took = hit.any(-1)
        idx = jnp.nonzero(took, size=cap, fill_value=T)[0]
        rows = mz[idx]
        y = r(jax.nn.silu(rows @ w["ex_gate"][e])
              * (rows @ w["ex_up"][e])) @ w["ex_down"][e]
        scale = jnp.concatenate([we, jnp.zeros((1,))])[idx]
        out = out.at[idx].add(y * scale[:, None], mode="drop")
        return out, over + jnp.maximum(took.sum() - cap, 0)

    out, over = jax.lax.fori_loop(
        0, n, expert, (jnp.zeros_like(m), jnp.zeros((), jnp.int32)))
    return out, over, sel


def ffn_parts(cfg, lp, m):
    """(shared, routed) of one sparse layer's feed-forward on rows m
    (T, D) in float32: the shared expert's output, and the held
    experts' part of the routed sum. The shares of a deployment add up:
    the routed parts of all shares plus the shared part once are the
    uncut layer's feed-forward."""
    import jax
    import jax.numpy as jnp

    w = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), lp)
    with jax.default_matmul_precision("highest"):
        part, _, _ = _routed(cfg, None, w, m)
        return _swiglu(lambda a: a, m, w["sh_gate"], w["sh_up"],
                       w["sh_down"]), part


def _layer(cfg, moe, q_block, control=None, cap=None):
    """Jitted (layer weights, x (T, D)) -> (x (T, D), overflow, sel):
    float32; T a multiple of ``q_block``. ``control`` alters the
    mathematics the way one of CONTROLS says; ``overflow`` counts rows
    an expert's block of ``cap`` rows could not hold (the caller runs
    the layer again with a larger one); ``sel`` is the picked experts
    (T, k), or None for a dense layer."""
    import jax
    import jax.numpy as jnp

    H = cfg["num_attention_heads"]
    lat, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dv, eps = cfg["v_head_dim"], cfg["rms_norm_eps"]
    r = ROUNDERS.get(control, lambda a: a)
    inv = jnp.asarray(rope_frequencies(cfg, control != "plain_rope"))
    scale = softmax_scale(cfg, control != "no_mscale")

    def attention(q_nope, q_rope, k_nope, k_rope, v):
        """Full causal softmax, a block of query rows at a time; the
        score is the sum of its two terms, the content's and the
        position's."""
        T = q_nope.shape[0]

        def block(args):
            b, qn, qr = args
            qpos = b * q_block + jnp.arange(q_block)
            sc = jnp.einsum("thd,shd->hts", qn, k_nope)
            if control != "no_rope_term":
                sc = sc + jnp.einsum("thd,sd->hts", qr, k_rope)
            ok = jnp.arange(T)[None, :] <= qpos[:, None]
            p = r(jax.nn.softmax(jnp.where(ok[None], sc * scale, -1e30),
                                 axis=-1))
            return jnp.einsum("hts,shd->thd", p, v)

        n = T // q_block
        out = jax.lax.map(block, (
            jnp.arange(n), q_nope.reshape(n, q_block, H, nope),
            q_rope.reshape(n, q_block, H, -1)))
        return out.reshape(T, H * dv)

    def f(lp, x):
        lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
        w = {name: r(a) if a.ndim >= 2 and name != "router" else a
             for name, a in lp.items()}
        T = x.shape[0]
        pos = jnp.arange(T)
        u = r(_rms(x, w["ln_in"], eps))
        q = _rms((u @ w["wq"].T).reshape(T, H, -1), w["q_norm"], eps)
        a = u @ w["wkv_a"].T
        c = a[:, :lat]
        if control != "no_latent_norm":
            c = _rms(c, w["kv_norm"], eps)
        q_rope = _rope(q[..., nope:], pos, inv)
        k_rope = _rope(a[:, None, lat:], pos, inv)[:, 0]
        kv = (r(c) @ w["wkv_b"].T).reshape(T, H, nope + dv)
        att = attention(r(q[..., :nope]), r(q_rope), r(kv[..., :nope]),
                        r(k_rope), r(kv[..., nope:]))
        x = x + r(att) @ w["wo"].T
        m = _rms(x, w["ln_mlp"], eps)
        over, sel = jnp.zeros((), jnp.int32), None
        if moe:
            part, over, sel = _routed(cfg, control, w, m, cap)
            ff = _swiglu(r, r(m), w["sh_gate"], w["sh_up"],
                         w["sh_down"]) + part
        else:
            ff = _swiglu(r, r(m), w["gate"], w["up"], w["down"])
        return x + ff, over, sel

    return jax.jit(f)


def forward(cfg, seed, ids_list, device=None, q_block=256, control=None,
            weights=None):
    """The hidden state after the last layer, (T_pad, D) float32, for
    each id sequence (each padded to the longest's multiple of
    ``q_block``: one shape, so each kind of layer compiles once), and
    per sequence the picked experts of every sparse layer."""
    import jax.numpy as jnp

    weights = weights or Weights(cfg, seed, device)
    embed = weights.ends()["embed"]
    t_pad = max(len(i) for i in ids_list)
    t_pad += -t_pad % q_block
    xs = [embed[jnp.asarray(np.pad(np.asarray(i, np.int32),
                                   (0, t_pad - len(i))))]
          .astype(jnp.float32) for i in ids_list]
    fns, sels = {}, [[] for _ in xs]

    def layer_fn(l, cap):
        key = (is_moe(cfg, l), cap)
        if key not in fns:
            fns[key] = _layer(cfg, key[0], q_block, control, cap)
        return fns[key]

    # an expert's block holds 4x its even share of a long sequence's
    # rows; a layer that routes more to one expert runs again with a
    # block twice the size, so nothing is ever dropped
    cap = t_pad if t_pad <= 4096 else max(
        512, t_pad * cfg["num_experts_per_tok"] * 4
        // cfg["num_experts_published"])
    for l in range(cfg["num_hidden_layers"]):
        lp = weights.layer(l)
        for j, x in enumerate(xs):
            y, over, sel = layer_fn(l, cap)(lp, x)
            while int(over):
                cap = min(2 * cap, t_pad)
                y, over, sel = layer_fn(l, cap)(lp, x)
            xs[j] = y
            if sel is not None:
                sels[j].append(np.asarray(sel))
        del lp
    return xs, sels


def logits(cfg, seed, ids, device=None, q_block=256, control=None):
    """(T, vocab) float32 logits of one id sequence: the reference's
    full forward, for the CPU tests."""
    import jax
    import jax.numpy as jnp

    weights = Weights(cfg, seed, device)
    ends = weights.ends()
    with jax.default_matmul_precision("highest"):
        x = forward(cfg, seed, [ids], device, q_block, control,
                    weights)[0][0][:len(ids)]
        return _rms(x, ends["norm"].astype(jnp.float32),
                    cfg["rms_norm_eps"]) \
            @ ends["head"].astype(jnp.float32).T


def served_token_gaps(cfg, seed, sequences, device=None, q_block=256,
                      control=False):
    """For each ``(prompt ids, served ids)``: at every served position,
    how far the served token's reference logit lies below the
    reference's best (0 where the reference would have served the same
    token). One teacher-forced pass over prompt + served tokens.
    Returns a list of float32 arrays, one per sequence.

    ``control`` (one of CONTROLS; True is "fp8") puts the reference,
    computed that way, in the program's place: at each position of the
    same prompts and tokens it reads the gap of the token the control
    pass puts first."""
    import jax
    import jax.numpy as jnp

    control = "fp8" if control is True else control or None
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}: {CONTROLS}")
    weights = Weights(cfg, seed, device)
    ends = weights.ends()
    ids = [np.concatenate([np.asarray(p, np.int32),
                           np.asarray(s, np.int32)])[:-1]
           for p, s in sequences]
    with jax.default_matmul_precision("highest"):
        xs, _ = forward(cfg, seed, ids, device, q_block, None, weights)
        ys = forward(cfg, seed, ids, device, q_block, control,
                     weights)[0] if control else None
        low = ROUNDERS.get(control, lambda a: a)

        def logits_of(x, norm, head, r=lambda a: a):
            return r(_rms(x, norm.astype(jnp.float32),
                          cfg["rms_norm_eps"])) @ r(head.astype(
                              jnp.float32)).T

        @jax.jit
        def gaps(x, norm, head, nxt):
            lg = logits_of(x, norm, head)
            got = jnp.take_along_axis(lg, nxt[:, None], 1)[:, 0]
            return jnp.max(lg, axis=-1) - got

        @jax.jit
        def first_of_control(y, norm, head):
            return jnp.argmax(logits_of(y, norm, head, low), -1)

        out = []
        for j, (x, (prompt, served)) in enumerate(zip(xs, sequences)):
            nxt = np.zeros(x.shape[0], np.int32)
            both = np.concatenate([np.asarray(prompt, np.int32),
                                   np.asarray(served, np.int32)])
            nxt[:len(both) - 1] = both[1:]
            nxt = jnp.asarray(nxt)
            if control:
                nxt = first_of_control(ys[j], ends["norm"],
                                       ends["head"]).astype(jnp.int32)
            g = np.asarray(gaps(x, ends["norm"], ends["head"], nxt))
            out.append(g[len(prompt) - 1:len(both) - 1])
    return out
