"""The plain reference of the dense decoder family, and its weights.

The benchmark makes the weights itself, from the seed, in one jitted call
on the device (:func:`make_params`), in the layout the program takes and
the type the configuration serves them in; the program and the reference
both read those.  The reference is a plain float32 ``jax.numpy`` forward at ``highest`` matmul precision, over a whole
sequence at once: pre-norm blocks of grouped-query attention (q/k RMS norm,
rotary positions) and a gated SiLU MLP, RMS norms scaled by ``1 + scale``
(the program's parameterisation of the published norm weight), embeddings
tied to the output head.  No kernel, cache or batching.

What is compared: for a served request, feed prompt + served tokens and
read, at each position where a token was served, how far that token's
reference logit lies below the reference's best (:func:`served_gap`).  The
control (:func:`control_gap`) puts the same forward, with every matmul
operand rounded to scaled float8 (e4m3), in the program's place, and reads
the same gap for the token it puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0   # largest finite float8_e4m3fn


def shapes(cfg: dict) -> dict:
    """The parameter tree's shapes, in the program's layout, from the
    configuration's published sizes."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f = cfg["intermediate_size"]
    vp = -(-cfg["vocab_size"] // 256) * 256
    return {
        "embed": {"table": (vp, d)},
        "final_norm": {"scale": (d,)},
        "layers_0": {
            "b0_attn": {"wq": (L, d, h * hd), "wk": (L, d, kv * hd),
                        "wv": (L, d, kv * hd), "wo": (L, h * hd, d),
                        "q_norm": (L, hd), "k_norm": (L, hd)},
            "b0_ln1": {"scale": (L, d)},
            "b0_ln2": {"scale": (L, d)},
            "b0_mlp": {"wg": (L, d, f), "wi": (L, d, f), "wo": (L, f, d)},
        },
    }


def _std(path, shp) -> float:
    """Matrices: 1/sqrt(fan in); the embedding: 1/sqrt(width); norm scales
    (offsets from 1): 0.1."""
    name = path[-1]
    if name in ("scale", "q_norm", "k_norm"):
        return 0.1
    if name == "table":
        return shp[-1] ** -0.5
    return shp[-2] ** -0.5


def make_params(cfg: dict, seed32: int):
    """Every weight drawn from ``seed32`` on the device in one jitted call,
    in the deployment's ``param_dtype``."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    specs = [(tuple(k.key for k in p), s) for p, s in leaves]
    dtype = jnp.dtype(cfg["deployment"]["param_dtype"])

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(specs))
        return jax.tree_util.tree_unflatten(treedef, [
            (jax.random.normal(k, s, jnp.float32) * _std(p, s)).astype(dtype)
            for k, (p, s) in zip(keys, specs)])

    return build(jax.random.key(seed32))


def _f8(x):
    """Scaled float8 (e4m3) rounding of a tensor: the control's operands."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, low):
    if low:
        a, b = _f8(a), _f8(b)
    return a @ b


def forward(cfg: dict, params, tokens, *, low: bool = False):
    """Final-norm hidden states (S, d) of one sequence; ``low`` rounds
    every matmul operand to float8 (the control)."""
    h, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.float32)
    freqs = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = pos[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def norm(x, scale):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return x * (1.0 + scale)

    def rope(x):
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a, m = p["b0_attn"], p["b0_mlp"]
        y = norm(x, p["b0_ln1"]["scale"])
        q = rope(norm(_mm(y, a["wq"], low).reshape(s, h, d), a["q_norm"]))
        k = rope(norm(_mm(y, a["wk"], low).reshape(s, kvh, d), a["k_norm"]))
        v = _mm(y, a["wv"], low).reshape(s, kvh, d)
        k, v = jnp.repeat(k, h // kvh, 1), jnp.repeat(v, h // kvh, 1)
        if low:
            q, k, v = _f8(q), _f8(k), _f8(v)
        att = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
        att = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), axis=-1)
        if low:
            att = _f8(att)
        o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, h * d)
        x = x + _mm(o, a["wo"], low)
        y = norm(x, p["b0_ln2"]["scale"])
        x = x + _mm(jax.nn.silu(_mm(y, m["wg"], low)) * _mm(y, m["wi"], low),
                    m["wo"], low)
        return x, None

    x = params["embed"]["table"][tokens]
    x, _ = jax.lax.scan(layer, x, params["layers_0"])
    return norm(x, params["final_norm"]["scale"])


def _logits(cfg, params, x, low=False):
    table = params["embed"]["table"][:cfg["vocab_size"]]
    return _mm(x, table.T, low)


def _f32(params):
    """The served weights, exactly, in float32."""
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


@functools.partial(jax.jit, static_argnums=0)
def _served_gap(cfg_items, params, tokens, targets, mask):
    cfg, params = dict(cfg_items), _f32(params)
    with jax.default_matmul_precision("highest"):
        logits = _logits(cfg, params, forward(cfg, params, tokens))
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.max(jnp.where(mask, best - got, -jnp.inf))


@functools.partial(jax.jit, static_argnums=0)
def _control_gap(cfg_items, params, tokens, mask):
    cfg, params = dict(cfg_items), _f32(params)
    with jax.default_matmul_precision("highest"):
        ref = _logits(cfg, params, forward(cfg, params, tokens))
        low = _logits(cfg, params, forward(cfg, params, tokens, low=True),
                      low=True)
    pick = jnp.argmax(low, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.max(jnp.where(mask, ref.max(-1) - got, -jnp.inf))


def _cfg_key(cfg: dict) -> tuple:
    keep = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keep)


def _pad_len(n: int) -> int:
    return max(256, 1 << (n - 1).bit_length())


def _sequence(prompt, served):
    """(tokens padded to a power of two, per-position target, mask): the
    served token i was produced at position len(prompt) - 1 + i."""
    seq = list(prompt) + list(served[:-1])
    n = len(seq)
    b = _pad_len(n)
    toks = np.zeros(b, np.int32)
    toks[:n] = seq
    tgt = np.zeros(b, np.int32)
    mask = np.zeros(b, bool)
    p0 = len(prompt) - 1
    tgt[p0:p0 + len(served)] = served
    mask[p0:p0 + len(served)] = True
    return jnp.asarray(toks), jnp.asarray(tgt), jnp.asarray(mask)


def served_gap(cfg: dict, params, prompt, served) -> float:
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over the positions it served."""
    toks, tgt, mask = _sequence(prompt, served)
    return float(_served_gap(_cfg_key(cfg), params, toks, tgt, mask))


def control_gap(cfg: dict, params, prompt, served) -> float:
    """The same gap for the tokens the float8 forward puts first."""
    toks, _, mask = _sequence(prompt, served)
    return float(_control_gap(_cfg_key(cfg), params, toks, mask))
