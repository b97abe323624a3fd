"""Plain reference of a Jamba decoder (AI21 Jamba family, ``model_type``
jamba): one full forward in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``, the recurrence as a
``lax.scan`` over time, plain causal attention, no kernel, no cache, no
batching, no import from the program under test. The whole model: no
width, no depth and no vocabulary is cut.

The layer, from the published config and ``modeling_jamba.py`` (``i``
the layer index; no projection has a bias but the convolution and
``dt_proj``; RMSNorm eps ``rms_norm_eps``)::

    h   = x + Mixer_i(RMSNorm(x; g_in));  out = h + MLP(RMSNorm(h; g_ff))
    MLP(u) = (silu(u Wg^T) * (u Wu^T)) Wd^T
    Mixer_i = attention where i % attn_layer_period == attn_layer_offset,
              else Mamba
    attention: q (H x d), k, v (K x d) = u Wq^T, u Wk^T, u Wv^T; no
        rotation, no positional term; softmax(q k^T / sqrt(d), j <= i) v; Wo
    Mamba on u (T, D), Dn = expand * D, N = d_state, R = dt_rank:
        x, z = split(u Win^T);  x = silu(conv1d_causal(x; w (d_conv, Dn), b))
        dt_r, B, C = split(x Wx^T);  dt_r, B, C = RMSNorm of each (g_dt, g_b, g_c)
        dt = softplus(dt_r Wdt^T + b_dt);  A = -exp(A_log)
        h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t
        y_t = h_t . C_t + D * x_t;  out = (y * silu(z)) Wout^T
    after the last layer: logits = RMSNorm(x; g_f) E^T   (tied embedding)

Departures from the published code, each also under ``assumed`` in the
configuration file: the order of the layer kinds is read from
``attn_layer_period`` / ``attn_layer_offset`` as ``modeling_jamba.py``
reads them (the catalog does not give it); ``head_dim`` is hidden /
heads; the convolution's weight is stored tap-major ``(d_conv, Dn)``
with the last tap on the current input, and ``A_log`` state-major
``(N, Dn)`` (the checkpoint's ``(Dn, 1, d_conv)`` and ``(Dn, N)``
transposed: the same numbers); the state ``h`` is float32 (the
published step kernel accumulates in float32; its eager path keeps the
state in the model's dtype); the convolution's weight and bias,
``A_log``, ``D`` and ``b_dt`` are float32.
The weights are seeded, not the checkpoint's: Mamba-1's published
initialisation for the recurrence, N(0, initializer_range) matrices.

It runs after the program's state is freed, layer by layer, each
layer's weights made again from the seed by the function that made the
served ones. ``CONTROLS`` alter the mathematics; the control pass is
put in the program's place on the same prompts and tokens.
"""
import math

import numpy as np

MAMBA_LEAVES = ("ln_in", "in_proj", "conv_w", "conv_b", "x_proj",
                "dt_norm", "b_norm", "c_norm", "dt_proj", "dt_bias",
                "A_log", "D", "out_proj", "ln_ff", "gate", "up", "down")
ATTN_LEAVES = ("ln_in", "wq", "wk", "wv", "wo", "ln_ff", "gate", "up",
               "down")
#: fp8: every matmul operand in float8 e4m3; state_bf16: the state
#: rounded to bfloat16 after every step; no_norms: the three norms on
#: dt, B and C dropped; rope: rotary positions (theta 10000) applied in
#: the attention layers; no_tail: the convolution's carried inputs
#: zeroed where prefill hands over to decode (the end of the prompt)
CONTROLS = ("fp8", "state_bf16", "no_norms", "rope", "no_tail")


def is_attention(cfg, l):
    return l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def _shapes(cfg):
    h, v, i = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["intermediate_size"]
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    dn, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return {"ln_in": (h,), "ln_ff": (h,), "gate": (i, h), "up": (i, h),
            "down": (h, i), "wq": (q, h), "wk": (kv, h), "wv": (kv, h),
            "wo": (h, q), "in_proj": (2 * dn, h),
            "conv_w": (cfg["mamba_d_conv"], dn), "conv_b": (dn,),
            "x_proj": (r + 2 * n, dn), "dt_norm": (r,), "b_norm": (n,),
            "c_norm": (n,), "dt_proj": (dn, r), "dt_bias": (dn,),
            "A_log": (n, dn), "D": (dn,), "out_proj": (h, dn),
            "embed": (v, h), "norm": (h,)}


class Weights:
    """The seeded weights, made on the device in the served type:
    N(0, initializer_range) matrices, unit norm gains, and Mamba-1's
    published initialisation of the recurrence: ``A_log`` = log(1..N)
    for every channel, ``D`` = 1, ``b_dt`` the inverse softplus of a
    step drawn log-uniform in [dt_min, dt_max], the convolution's
    weight and bias U(+-1/sqrt(d_conv)) (a Conv1d's default). One
    jitted call a layer (one executable a kind), one for embedding and
    final norm; the served copy and the reference's layer-by-layer copy
    come from the same calls with the same keys."""

    def __init__(self, cfg, seed, device=None):
        import jax
        import jax.numpy as jnp

        shapes, std = _shapes(cfg), cfg["initializer_range"]
        dt = jnp.dtype(cfg["torch_dtype"])
        n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
        lo, hi = math.log(cfg["dt_min"]), math.log(cfg["dt_max"])

        def leaf(key, name, i):
            key, shape = jax.random.fold_in(key, i), shapes[name]
            if name == "A_log":
                return jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32))[:, None], shape)
            if name == "D":
                return jnp.ones(shape, jnp.float32)
            if name == "dt_bias":
                step = jnp.exp(jax.random.uniform(key, shape,
                                                  jnp.float32, lo, hi))
                return step + jnp.log(-jnp.expm1(-step))
            if name in ("conv_w", "conv_b"):
                b = 1.0 / math.sqrt(k)
                return jax.random.uniform(key, shape, jnp.float32,
                                          -b, b)
            if len(shape) == 1:
                return jnp.ones(shape, dt)
            return (jax.random.normal(key, shape, jnp.float32)
                    * std).astype(dt)

        def maker(names):
            return jax.jit(lambda key: {nm: leaf(key, nm, i)
                                        for i, nm in enumerate(names)})

        self._mamba = maker(MAMBA_LEAVES)
        self._attn = maker(ATTN_LEAVES)
        self._ends = maker(("embed", "norm"))
        root = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        self._root = jax.device_put(root, device) \
            if device is not None else root
        self._fold = jax.random.fold_in
        self.cfg = cfg
        self.num_layers = cfg["num_hidden_layers"]

    def layer(self, l):
        make = self._attn if is_attention(self.cfg, l) else self._mamba
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


def _rope(x, pos, base):
    """Rotate-half rotary embedding on (T, H, d) at positions (T,)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
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
_NOT_MATMUL = ("conv_w", "A_log")


def _mlp(r, w, x, eps):
    import jax
    u = r(_rms(x, w["ln_ff"], eps))
    return x + r(jax.nn.silu(u @ w["gate"].T) * (u @ w["up"].T)) \
        @ w["down"].T


def _layer(cfg, attention, q_block, control=None):
    """Jitted (layer weights, x (T, D), handover) -> (x (T, D), rms of
    the final state | 0): float32; T a multiple of ``q_block``;
    ``handover`` is the position at which the served sequence went from
    prefill to decode (only the no_tail control reads it)."""
    import jax
    import jax.numpy as jnp

    H, K, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    dn, n, rk = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    kc = cfg["mamba_d_conv"]
    r = ROUNDERS.get(control, lambda a: a)

    def attend(w, x):
        T = x.shape[0]
        u = r(_rms(x, w["ln_in"], eps))
        q = (u @ w["wq"].T).reshape(T, H, d)
        k = (u @ w["wk"].T).reshape(T, K, d)
        v = (u @ w["wv"].T).reshape(T, K, d)
        if control == "rope":
            pos = jnp.arange(T)
            q, k = _rope(q, pos, 10000.0), _rope(k, pos, 10000.0)
        q, k, v = r(q), r(k), r(v)
        qg = q.reshape(T // q_block, q_block, K, H // K, d)
        kpos = jnp.arange(T)

        def block(args):
            b, qb = args
            qpos = b * q_block + jnp.arange(q_block)
            sc = jnp.einsum("tkrd,skd->krts", qb, k) / math.sqrt(d)
            ok = kpos[None, :] <= qpos[:, None]
            p = r(jax.nn.softmax(jnp.where(ok[None, None], sc, -1e30),
                                 axis=-1))
            return jnp.einsum("krts,skd->tkrd", p, v)

        a = jax.lax.map(block, (jnp.arange(T // q_block), qg))
        return r(a.reshape(T, H * d)) @ w["wo"].T, jnp.zeros(())

    def mamba(w, x, handover):
        T = x.shape[0]
        u = r(_rms(x, w["ln_in"], eps))
        xz = u @ w["in_proj"].T
        xr, z = xz[:, :dn], xz[:, dn:]
        xp = jnp.pad(xr, ((kc - 1, 0), (0, 0)))
        t = jnp.arange(T)
        acc = jnp.broadcast_to(w["conv_b"], (T, dn))
        for j in range(kc):
            term = xp[j:j + T] * w["conv_w"][j]
            if control == "no_tail":
                src = t - (kc - 1) + j
                term = jnp.where(((t >= handover)
                                  & (src < handover))[:, None], 0.0, term)
            acc = acc + term
        xc = jax.nn.silu(acc)
        p = r(xc) @ w["x_proj"].T
        dt_r, b, c = p[:, :rk], p[:, rk:rk + n], p[:, rk + n:]
        if control != "no_norms":
            dt_r = _rms(dt_r, w["dt_norm"], eps)
            b = _rms(b, w["b_norm"], eps)
            c = _rms(c, w["c_norm"], eps)
        dt = jax.nn.softplus(r(dt_r) @ w["dt_proj"].T + w["dt_bias"])
        a = -jnp.exp(w["A_log"])                            # (N, Dn)

        def step(h, inp):
            x_t, dt_t, b_t, c_t = inp
            h = jnp.exp(dt_t[None, :] * a) * h \
                + (dt_t * x_t)[None, :] * b_t[:, None]
            if control == "state_bf16":
                h = bf16_round(h)
            return h, jnp.sum(h * c_t[:, None], axis=0)

        h, y = jax.lax.scan(step, jnp.zeros((n, dn), jnp.float32),
                            (xc, dt, b, c))
        g = (y + w["D"] * xc) * jax.nn.silu(z)
        return r(g) @ w["out_proj"].T, jnp.sqrt(jnp.mean(jnp.square(h)))

    def f(lp, x, handover):
        lp = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), lp)
        w = {name: r(v) if v.ndim >= 2 and name not in _NOT_MATMUL
             else v for name, v in lp.items()}
        out, state_rms = attend(w, x) if attention \
            else mamba(w, x, handover)
        return _mlp(r, w, x + out, eps), state_rms

    return jax.jit(f)


def forward(cfg, seed, ids_list, device=None, q_block=512, control=None,
            weights=None, handovers=None):
    """The hidden state after the last layer, (T_pad, D) float32, for
    each id sequence (each padded to the longest's multiple of
    ``q_block``: one shape, so each kind of layer compiles once), and a
    dict of what bring-up watches: the RMS of the residual stream after
    the last layer and of every recurrent layer's final state, of the
    first sequence."""
    import jax.numpy as jnp

    weights = weights or Weights(cfg, seed, device)
    embed = weights.ends()["embed"]
    t_pad = max(len(i) for i in ids_list)
    t_pad += -t_pad % q_block
    xs = [embed[jnp.asarray(np.pad(np.asarray(i, np.int32),
                                   (0, t_pad - len(i))))]
          .astype(jnp.float32) for i in ids_list]
    handovers = handovers or [len(i) for i in ids_list]
    fns = {a: _layer(cfg, a, q_block, control) for a in (False, True)}
    state_rms = []
    for l in range(cfg["num_hidden_layers"]):
        lp = weights.layer(l)
        attention = is_attention(cfg, l)
        for j, x in enumerate(xs):
            xs[j], srms = fns[attention](lp, x, jnp.int32(handovers[j]))
            if j == 0 and not attention:
                state_rms.append(float(srms))
        del lp
    n0 = len(ids_list[0])
    watch = {"state_rms": state_rms,
             "stream_rms": float(jnp.sqrt(jnp.mean(jnp.square(
                 xs[0][:n0]))))}
    return xs, watch


def served_token_gaps(cfg, seed, sequences, device=None, q_block=512,
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
    hand = [len(p) for p, _ in sequences]
    with jax.default_matmul_precision("highest"):
        xs, _ = forward(cfg, seed, ids, device, q_block, None, weights)
        ys = forward(cfg, seed, ids, device, q_block, control, weights,
                     hand)[0] if control else None
        low = ROUNDERS.get(control, lambda a: a)

        def logits_of(x, norm, embed, r=lambda a: a):
            return r(_rms(x, norm.astype(jnp.float32),
                          cfg["rms_norm_eps"])) @ r(embed.astype(
                              jnp.float32)).T

        @jax.jit
        def gaps(x, norm, embed, nxt):
            logits = logits_of(x, norm, embed)
            got = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
            return jnp.max(logits, axis=-1) - got

        @jax.jit
        def first_of_control(y, norm, embed):
            return jnp.argmax(logits_of(y, norm, embed, low), -1)

        out = []
        for j, (x, (prompt, served)) in enumerate(zip(xs, sequences)):
            nxt = np.zeros(x.shape[0], np.int32)
            both = np.concatenate([np.asarray(prompt, np.int32),
                                   np.asarray(served, np.int32)])
            nxt[:len(both) - 1] = both[1:]
            nxt = jnp.asarray(nxt)
            if control:
                nxt = first_of_control(ys[j], ends["norm"],
                                       ends["embed"]).astype(jnp.int32)
            g = np.asarray(gaps(x, ends["norm"], ends["embed"], nxt))
            out.append(g[len(prompt) - 1:len(both) - 1])
    return out
