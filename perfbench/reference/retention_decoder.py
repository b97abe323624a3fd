"""Plain reference of a power-retention decoder (Manifest AI's Brumby
family, ``model_type`` brumby): one full forward in float32
``jax.numpy`` at ``default_matmul_precision("highest")``, the retention
in its QUADRATIC form (every position against every earlier one, as
written below: no state, no chunks, no feature map), in blocks of query
rows, no kernel, no cache, no batching, no import from the program under
test. No width and no vocabulary row is cut; the depth is the
configuration's.

The layer, from the published config (the Qwen3-14B block: 40 query
heads on 8 key/value heads of 128, per-head RMSNorm on q and k, RoPE,
SwiGLU, no bias) with the softmax attention replaced by power retention
(Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239); ``h`` a query head of kv group ``j``::

    u   = RMSNorm(x; g_in)
    q_h = RoPE_t(RMSNorm_d((u Wq^T)_h; g_q))   k_j = RoPE_t(RMSNorm_d((u Wk^T)_j; g_k))
    v_j = (u Wv^T)_j            log g_j = log sigmoid((u Wg^T + b_g)_j)
    a_{t,s,h} = (q_{t,h} . k_{s,j})^p * exp(sum_{r=s+1..t} log g_{r,j})     s <= t,  p = 2
    y_{t,h}   = sum_s a_{t,s,h} v_{s,j} / (sum_s a_{t,s,h} + eps)
    h   = x + concat_h(y_h) Wo^T;   out = h + MLP(RMSNorm(h; g_ff))
    MLP(u) = (silu(u Wg^T) * (u Wu^T)) Wd^T
    after the last layer: logits = RMSNorm(x; g_f) W_head^T   (untied)

ASSUMED, each also under ``assumed`` in the configuration file with its
reason (the published config gives the sizes, not the retention; the
model's own modeling file is not in reach, so these are written from
the published description): the degree ``p`` = 2; the gate, one scalar a
kv head a position, ``log sigmoid`` of a linear map of the layer's
normed input, in float32; the normaliser (the same sum without v) and
its ``eps`` 1e-6; the per-head q / k RMSNorm and the rotation kept from
the Qwen3 block the model was retrained from; the symmetric feature map
of the equivalent recurrence (8,256 distinct products, not 16,384: the
quadratic form here does not depend on it); a float32 state in that
recurrence (the ``state_bf16`` control rounds it every position and
must fail the check); ``gate_init``: ``W_g`` ~ N(0, 0.002) and ``b_g``
= log(h - 1) with the horizon h = 1 / (1 - g) drawn log-uniform in
[64, 8192] positions (with N(0, 0.02) and no offset a seeded model
forgets in two positions and no check could see the state a prompt
built).

The weights are seeded, not the checkpoint's. It runs after the
program's state is freed, layer by layer, each layer's weights made
again from the seed by the function that made the served ones.
``CONTROLS`` alter the mathematics; the control pass is put in the
program's place on the same prompts and tokens.
"""
import json
import math

import numpy as np

LAYER_LEAVES = ("ln_in", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "bg",
                "wo", "ln_ff", "gate", "up", "down")
#: fp8: every matmul operand in float8 e4m3; state_bf16: the recurrence
#: with its state rounded to bfloat16 after every position (the one
#: control that needs the state: it scans, with the plain upper-triangle
#: feature map); degree_1: p = 1; no_gate: g = 1; no_normaliser: the
#: division dropped; no_rope / no_qk_norm: the rotation / the per-head
#: norms dropped; short_memory: the sums cut to the last 512 positions
CONTROLS = ("fp8", "state_bf16", "degree_1", "no_gate", "no_normaliser",
            "no_rope", "no_qk_norm", "short_memory")
SHORT_MEMORY = 512


def _shapes(cfg):
    h, v, i = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["intermediate_size"]
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"ln_in": (h,), "ln_ff": (h,), "gate": (i, h), "up": (i, h),
            "down": (h, i), "wq": (q, h), "wk": (kv, h), "wv": (kv, h),
            "wo": (h, q), "q_norm": (d,), "k_norm": (d,),
            "wg": (cfg["num_key_value_heads"], h),
            "bg": (cfg["num_key_value_heads"],),
            "embed": (v, h), "norm": (h,), "head": (v, h)}


class Weights:
    """The seeded weights, made on the device in the served type:
    N(0, initializer_range) matrices, unit norm gains, and the gate's
    ``gate_init`` (``W_g`` ~ N(0, gate_init.weight_std), ``b_g`` =
    log(h - 1), h log-uniform in gate_init.horizon, float32). One jitted
    call a layer, one for embedding, final norm and head; the served
    copy and the reference's layer-by-layer copy come from the same
    calls with the same keys."""

    def __init__(self, cfg, seed, device=None):
        import jax
        import jax.numpy as jnp

        shapes, std = _shapes(cfg), cfg["initializer_range"]
        dt = jnp.dtype(cfg["torch_dtype"])
        gi = cfg["gate_init"]
        lo, hi = (math.log(h) for h in gi["horizon"])

        def leaf(key, name, i):
            key, shape = jax.random.fold_in(key, i), shapes[name]
            if name == "bg":
                return jnp.log(jnp.exp(jax.random.uniform(
                    key, shape, jnp.float32, lo, hi)) - 1.0)
            if len(shape) == 1:
                return jnp.ones(shape, dt)
            s = gi["weight_std"] if name == "wg" else std
            return (jax.random.normal(key, shape, jnp.float32)
                    * s).astype(dt)

        def maker(names):
            return jax.jit(lambda key: {nm: leaf(key, nm, i)
                                        for i, nm in enumerate(names)})

        self._layer = maker(LAYER_LEAVES)
        self._ends = maker(("embed", "norm", "head"))
        root = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        self._root = jax.device_put(root, device) \
            if device is not None else root
        self._fold = jax.random.fold_in
        self.cfg = cfg
        self.num_layers = cfg["num_hidden_layers"]

    def layer(self, l):
        return self._layer(self._fold(self._root, l + 1))

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
    """Round to bfloat16's 8 bits of exponent and 7 of mantissa, the
    result kept in float32. ``reduce_precision`` and not a cast there
    and back: inside one fusion the TPU's code generator drops such a
    round trip as excess precision, and the scanned state came back
    float32 bit for bit (PERF.md, section 2)."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


ROUNDERS = {"fp8": fp8_round, "bf16": bf16_round}


def _mlp(r, w, x, eps):
    import jax
    u = r(_rms(x, w["ln_ff"], eps))
    return x + r(jax.nn.silu(u @ w["gate"].T) * (u @ w["up"].T)) \
        @ w["down"].T


_LAYERS = {}


def _layer(cfg, q_block, control=None):
    """Jitted (layer weights, x (T, D)) -> x (T, D): float32; T a
    multiple of ``q_block``. One jit a (configuration, block, control):
    a second pass at the same shapes compiles nothing."""
    key = (json.dumps(cfg, sort_keys=True), q_block, control)
    if key not in _LAYERS:
        _LAYERS[key] = _make_layer(cfg, q_block, control)
    return _LAYERS[key]


def _make_layer(cfg, q_block, control):
    import jax
    import jax.numpy as jnp

    H, K, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    G = H // K
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    r_eps = cfg["retention_eps"]
    degree = 1 if control == "degree_1" else cfg["retention_degree"]
    r = ROUNDERS.get(control, lambda a: a)

    def inputs(w, x):
        T = x.shape[0]
        u = r(_rms(x, w["ln_in"], eps))
        q = (u @ w["wq"].T).reshape(T, H, d)
        k = (u @ w["wk"].T).reshape(T, K, d)
        v = (u @ w["wv"].T).reshape(T, K, d)
        if control != "no_qk_norm":
            q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
        if control != "no_rope":
            pos = jnp.arange(T)
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        log_g = jax.nn.log_sigmoid(u @ w["wg"].T + w["bg"])     # (T, K)
        if control == "no_gate":
            log_g = jnp.zeros_like(log_g)
        return r(q), r(k), r(v), log_g

    def quadratic(w, x):
        """a_{t,s} as written: a block of query rows against every
        position."""
        T = x.shape[0]
        q, k, v, log_g = inputs(w, x)
        cum = jnp.cumsum(log_g, axis=0)                         # (T, K)
        qg = q.reshape(T // q_block, q_block, K, G, d)
        cg = cum.reshape(T // q_block, q_block, K)
        kpos = jnp.arange(T)

        def block(args):
            b, qb, cb = args
            qpos = b * q_block + jnp.arange(q_block)
            sc = jnp.einsum("tkgd,skd->kgts", qb, k)
            ok = kpos[None, :] <= qpos[:, None]
            if control == "short_memory":
                ok &= kpos[None, :] > qpos[:, None] - SHORT_MEMORY
            decay = jnp.where(
                ok[None], jnp.exp(jnp.minimum(
                    cb.T[:, :, None] - cum.T[:, None, :], 0.0)), 0.0)
            a = sc ** degree * decay[:, None]                   # (K,G,t,s)
            num = jnp.einsum("kgts,skd->tkgd", r(a), v)
            if control == "no_normaliser":
                return num
            den = jnp.sum(a, axis=-1)                           # (K,G,t)
            return num / (jnp.moveaxis(den, -1, 0)[..., None] + r_eps)

        y = jax.lax.map(block, (jnp.arange(T // q_block), qg, cg))
        return y.reshape(T, H * d)

    iu = np.triu_indices(d)
    coef = np.where(iu[0] == iu[1], 1.0, math.sqrt(2.0)).astype(np.float32)

    def phi(u):
        """The plain feature map: the d (d + 1) / 2 products u_i u_k,
        i <= k, sqrt 2 off the diagonal."""
        return coef * u[..., iu[0]] * u[..., iu[1]]

    def recurrent_bf16(w, x):
        """The recurrence, position by position, with the state
        rounded to bfloat16 after every update."""
        T = x.shape[0]
        q, k, v, log_g = inputs(w, x)
        D = iu[0].size

        def step(carry, inp):
            S, z = carry
            q_t, k_t, v_t, lg_t = inp
            g = jnp.exp(lg_t)
            pk = phi(k_t)                                       # (K, D)
            S = bf16_round(g[:, None, None] * S
                           + pk[:, :, None] * v_t[:, None, :])
            z = bf16_round(g[:, None] * z + pk)
            pq = phi(q_t.reshape(K, G, d))                      # (K, G, D)
            num = jnp.einsum("kgp,kpa->kga", pq, S)
            den = jnp.einsum("kgp,kp->kg", pq, z)
            return (S, z), (num / (den[..., None] + r_eps)).reshape(H * d)

        init = (jnp.zeros((K, D, d), jnp.float32),
                jnp.zeros((K, D), jnp.float32))
        return jax.lax.scan(step, init, (q, k, v, log_g))[1]

    mix = recurrent_bf16 if control == "state_bf16" else quadratic

    def f(lp, x):
        lp = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), lp)
        w = {name: r(v) if v.ndim >= 2 else v for name, v in lp.items()}
        return _mlp(r, w, x + r(mix(w, x)) @ w["wo"].T, eps)

    return jax.jit(f)


def forward(cfg, seed, ids_list, device=None, q_block=256, control=None,
            weights=None):
    """The hidden state after the last layer, (T_pad, D) float32, for
    each id sequence (each padded to the longest's multiple of
    ``q_block``: one shape, so the layer compiles once), and a dict of
    what bring-up watches: the RMS of the residual stream after the
    last layer, of the first sequence."""
    import jax.numpy as jnp

    weights = weights or Weights(cfg, seed, device)
    embed = weights.ends()["embed"]
    t_pad = max(len(i) for i in ids_list)
    t_pad += -t_pad % q_block
    xs = [embed[jnp.asarray(np.pad(np.asarray(i, np.int32),
                                   (0, t_pad - len(i))))]
          .astype(jnp.float32) for i in ids_list]
    del embed
    fn = _layer(cfg, q_block, control)
    for l in range(cfg["num_hidden_layers"]):
        lp = weights.layer(l)
        for j, x in enumerate(xs):
            xs[j] = fn(lp, x)
        del lp
    n0 = len(ids_list[0])
    watch = {"stream_rms": float(jnp.sqrt(jnp.mean(jnp.square(
        xs[0][:n0]))))}
    return xs, watch


def served_token_gaps(cfg, seed, sequences, device=None, q_block=256,
                      control=False):
    """For each ``(prompt ids, served ids)``: at every served position,
    how far the served token's reference logit lies below the
    reference's best (0 where the reference would have served the same
    token). One teacher-forced pass over prompt + served tokens; the
    logits are taken at the served positions alone (a row of them is
    the whole vocabulary). Returns a list of float32 arrays, one per
    sequence.

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
    ids = [np.concatenate([np.asarray(p, np.int32),
                           np.asarray(s, np.int32)])[:-1]
           for p, s in sequences]
    span = max(len(s) for _, s in sequences)
    span += -span % 128
    with jax.default_matmul_precision("highest"):
        xs, _ = forward(cfg, seed, ids, device, q_block, None, weights)
        ys = forward(cfg, seed, ids, device, q_block, control,
                     weights)[0] if control else None
        low = ROUNDERS.get(control, lambda a: a)
        ends = weights.ends()

        def logits_of(x, start, norm, head, r=lambda a: a):
            # the served positions alone, a fixed span of them
            rows = jax.lax.dynamic_slice_in_dim(
                jnp.pad(x, ((0, span), (0, 0))), start, span)
            return r(_rms(rows, norm.astype(jnp.float32),
                          cfg["rms_norm_eps"])) @ r(head.astype(
                              jnp.float32)).T

        @jax.jit
        def gaps(x, start, norm, head, nxt):
            logits = logits_of(x, start, norm, head)
            got = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
            return jnp.max(logits, axis=-1) - got

        @jax.jit
        def first_of_control(y, start, norm, head):
            return jnp.argmax(logits_of(y, start, norm, head, low), -1)

        out = []
        for j, (x, (prompt, served)) in enumerate(zip(xs, sequences)):
            start = jnp.int32(len(prompt) - 1)
            nxt = np.zeros(span, np.int32)
            nxt[:len(served)] = np.asarray(served, np.int32)
            nxt = jnp.asarray(nxt)
            if control:
                nxt = first_of_control(ys[j], start, ends["norm"],
                                       ends["head"]).astype(jnp.int32)
            out.append(np.asarray(gaps(x, start, ends["norm"],
                                       ends["head"], nxt))[:len(served)])
    return out
