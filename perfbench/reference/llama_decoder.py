"""Plain reference of a Llama-family decoder (RMSNorm, rotary
embedding, grouped-query attention, SwiGLU): one full forward in
float32 ``jax.numpy`` at ``default_matmul_precision("highest")``, no
kernels, no cache, no batching, no import from the program under test.
It follows the published Mistral-7B description; ``sliding_window`` is
null in v0.2, so attention is plain causal.

It runs after the program's state is freed, LAYER BY LAYER: each
layer's weights are made again from the seed by the same jitted
function that made the served ones (bit-identical values, upcast to
float32), used on every sampled sequence, and dropped. So its memory
stays far under the program's and it takes nothing the program made.

It owns the seeded weights (:class:`Weights`): they are the
benchmark's, not the program's.
"""
import math

import numpy as np

_LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "gate", "up",
                 "down")


def _shapes(cfg):
    h, i, v = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["vocab_size"])
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    return {"ln1": (h,), "wq": (h, h), "wk": (kv, h), "wv": (kv, h),
            "wo": (h, h), "ln2": (h,), "gate": (i, h), "up": (i, h),
            "down": (h, i), "embed": (v, h), "norm": (h,),
            "head": (v, h)}


class Weights:
    """The decoder's seeded weights, made on the device in the type they
    are served in (N(0, initializer_range) matrices, unit norm scales):
    one jitted call a layer, the same executable each time, and one for
    the embedding, final norm and head. The served copy and the
    reference's layer-by-layer copy come from the same calls with the
    same keys, so they are the same values."""

    def __init__(self, cfg, seed, device=None):
        import jax
        import jax.numpy as jnp

        shapes, std = _shapes(cfg), cfg["initializer_range"]
        dt = jnp.dtype(cfg["torch_dtype"])

        def leaf(k, name, i):
            if len(shapes[name]) == 1:
                return jnp.ones(shapes[name], dt)
            return (jax.random.normal(jax.random.fold_in(k, i),
                                      shapes[name], jnp.float32)
                    * std).astype(dt)

        self._layer = jax.jit(lambda k: {
            n: leaf(k, n, i) for i, n in enumerate(_LAYER_LEAVES)})
        self._ends = jax.jit(lambda k: {
            n: leaf(k, n, i)
            for i, n in enumerate(("embed", "norm", "head"))})
        root = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        self._root = jax.device_put(root, device) \
            if device is not None else root
        self._fold = jax.random.fold_in
        self.num_layers = cfg["num_hidden_layers"]

    def layer(self, l):
        return self._layer(self._fold(self._root, l + 1))

    def ends(self):
        return self._ends(self._fold(self._root, 0))

    def all(self):
        """The tree ``{"embed", "norm", "head", "layers": [...]}``."""
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
    precision below bfloat16 (the control of "How correct is decided")."""
    import jax.numpy as jnp
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _layer(cfg, q_block, lower=False):
    """Jitted (layer weights, x (T, D)) -> x (T, D) in float32;
    attention in blocks of ``q_block`` query rows so the scores stay
    small. ``lower=True`` rounds every matmul operand to fp8 (the
    control)."""
    import jax
    import jax.numpy as jnp

    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    eps, base = cfg["rms_norm_eps"], cfg["rope_theta"]
    r = fp8_round if lower else (lambda a: a)

    def f(lp, x):
        lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
        w = {n: r(a) if a.ndim == 2 else a for n, a in lp.items()}
        T = x.shape[0]
        pos = jnp.arange(T)
        h = r(_rms(x, w["ln1"], eps))
        q = _rope((h @ w["wq"].T).reshape(T, H, d), pos, base)
        k = r(_rope((h @ w["wk"].T).reshape(T, K, d), pos, base))
        v = r((h @ w["wv"].T).reshape(T, K, d))
        qg = r(q.reshape(T, K, H // K, d))
        outs = []
        for s in range(0, T, q_block):
            e = min(s + q_block, T)
            sc = jnp.einsum("tkrd,skd->krts", qg[s:e], k[:e]) \
                / math.sqrt(d)
            ok = pos[None, :e] <= pos[s:e, None]
            sc = jnp.where(ok[None, None], sc, -1e30)
            p = r(jax.nn.softmax(sc, axis=-1))
            outs.append(jnp.einsum("krts,skd->tkrd", p, v[:e]))
        att = jnp.concatenate(outs, 0).reshape(T, H * d)
        x = x + r(att) @ w["wo"].T
        h2 = r(_rms(x, w["ln2"], eps))
        return x + r(jax.nn.silu(h2 @ w["gate"].T)
                     * (h2 @ w["up"].T)) @ w["down"].T

    return jax.jit(f)


def served_token_gaps(cfg, seed, sequences, device=None, q_block=512,
                      control=False):
    """For each ``(prompt ids, served ids)``: at every served position,
    how far the served token's reference logit lies below the
    reference's best (0 where the reference would have served the same
    token). One teacher-forced pass over prompt + served tokens.
    Returns a list of float32 arrays, one per sequence.

    ``control=True`` puts the reference, computed in fp8, in the
    program's place: at each position of the same prompts and tokens it
    reads the gap of the token the fp8 pass puts first."""
    import jax
    import jax.numpy as jnp

    layer = _layer(cfg, q_block)
    low = _layer(cfg, q_block, lower=True) if control else None
    weights = Weights(cfg, seed, device)
    ends = weights.ends()
    embed = ends["embed"]
    with jax.default_matmul_precision("highest"):
        xs = []
        for prompt, served in sequences:
            ids = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(served, np.int32)])[:-1]
            # pad to a multiple of the block: few distinct shapes to
            # compile, and causal attention keeps the padding out of
            # every position before it
            ids = np.pad(ids, (0, -len(ids) % q_block))
            xs.append(embed[jnp.asarray(ids)].astype(jnp.float32))
        ys = list(xs)
        for l in range(cfg["num_hidden_layers"]):
            lp = weights.layer(l)
            xs = [layer(lp, x) for x in xs]
            if control:
                ys = [low(lp, y) for y in ys]
            del lp

        def logits_of(x, norm, head, r=lambda a: a):
            return r(_rms(x, norm.astype(jnp.float32),
                          cfg["rms_norm_eps"])) @ r(head.astype(
                              jnp.float32)).T

        @jax.jit
        def gaps(x, norm, head, nxt):
            logits = logits_of(x, norm, head)
            got = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
            return jnp.max(logits, axis=-1) - got

        @jax.jit
        def first_of_low(y, norm, head):
            return jnp.argmax(logits_of(y, norm, head, fp8_round), -1)

        out = []
        for x, (prompt, served) in zip(xs, sequences):
            # position i predicts token i + 1; whole padded rows at
            # once (shapes stay in the block's buckets), cut on the host
            nxt = np.zeros(x.shape[0], np.int32)
            both = np.concatenate([np.asarray(prompt, np.int32),
                                   np.asarray(served, np.int32)])
            nxt[:len(both) - 1] = both[1:]
            nxt = jnp.asarray(nxt)
            if control:
                nxt = first_of_low(ys[len(out)], ends["norm"],
                                   ends["head"]).astype(jnp.int32)
            g = np.asarray(gaps(x, ends["norm"], ends["head"], nxt))
            out.append(g[len(prompt) - 1:len(both) - 1])
    return out
