"""RWKV6 (Finch) block: time-mix with data-dependent decay + channel-mix.

The time-mix core is the WKV6 recurrence (kernels/wkv6); the data-dependent
decay w_t = exp(-exp(w0 + (x_t·A)·B)) is the Finch contribution (low-rank
LoRA on the decay).  Token-shift interpolation uses a single learned mu per
projection (a documented simplification of per-channel mus — structurally
identical dataflow).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import he_init, rmsnorm
from ..kernels.wkv6.ops import wkv6 as wkv6_kernel
from ..kernels.wkv6.ref import wkv6_chunked, wkv6_reference

LORA_RANK = 64


def init_rwkv_time_mix(kg, cfg, dtype=jnp.float32):
    e = cfg["embed"]
    h, d = cfg["heads"], cfg["head_dim"]
    assert h * d == e
    rank = min(LORA_RANK, e // 2)
    p = {
        "wr": he_init(kg(), (e, e), e, dtype),
        "wk": he_init(kg(), (e, e), e, dtype),
        "wv": he_init(kg(), (e, e), e, dtype),
        "wg": he_init(kg(), (e, e), e, dtype),
        "wo": he_init(kg(), (e, e), e, dtype),
        "w0": jnp.full((e,), -3.0, dtype),              # decay bias
        "wA": he_init(kg(), (e, rank), e, dtype),       # decay LoRA
        "wB": he_init(kg(), (rank, e), rank, dtype),
        "u": he_init(kg(), (h, d), d, dtype),           # bonus
        "mu": jnp.full((5,), 0.5, dtype),               # token-shift mixes
        "ln_scale": jnp.zeros((e,), dtype),             # per-head group norm
    }
    s = {
        "wr": ("embed", "heads_flat"), "wk": ("embed", "heads_flat"),
        "wv": ("embed", "heads_flat"), "wg": ("embed", "heads_flat"),
        "wo": ("heads_flat", "embed"),
        "w0": ("embed",), "wA": ("embed", "lora"), "wB": ("lora", "embed"),
        "u": ("heads", "head_dim"), "mu": ("mix",), "ln_scale": ("embed",),
    }
    return p, s


def _token_shift(x):
    return jnp.pad(x, [(0, 0), (1, 0), (0, 0)])[:, :-1]


def rwkv_time_mix(p, x, *, heads, head_dim, use_kernel=False, last_x=None,
                  state=None):
    """x: (B, T, E).  When ``state``/``last_x`` are given (decode), runs the
    single-step recurrence and returns (y, new_last_x, new_state)."""
    b, t, e = x.shape
    decode = state is not None
    xs = (jnp.concatenate([last_x[:, None], x[:, :-1]], axis=1)
          if decode else _token_shift(x))
    mu = p["mu"].astype(x.dtype)

    def mix(i):
        return x + mu[i] * (xs - x)

    r = jnp.einsum("bte,ef->btf", mix(0), p["wr"].astype(x.dtype))
    k = jnp.einsum("bte,ef->btf", mix(1), p["wk"].astype(x.dtype))
    v = jnp.einsum("bte,ef->btf", mix(2), p["wv"].astype(x.dtype))
    g = jnp.einsum("bte,ef->btf", mix(3), p["wg"].astype(x.dtype))
    lora = jnp.einsum("btr,re->bte",
                      jnp.tanh(jnp.einsum("bte,er->btr", mix(4),
                                          p["wA"].astype(x.dtype))),
                      p["wB"].astype(x.dtype))
    w = jnp.exp(-jnp.exp((p["w0"].astype(jnp.float32) +
                          lora.astype(jnp.float32))))    # (B,T,E) in (0,1)

    rh = r.reshape(b, t, heads, head_dim)
    kh = k.reshape(b, t, heads, head_dim)
    vh = v.reshape(b, t, heads, head_dim)
    wh = w.reshape(b, t, heads, head_dim)

    if decode:
        y, new_state = wkv6_reference(rh, kh, vh, wh.astype(rh.dtype),
                                      p["u"], initial_state=state)
    elif use_kernel:
        y = wkv6_kernel(rh, kh, vh, wh.astype(rh.dtype), p["u"])
        new_state = None
    else:
        # chunked jnp engine: state materializes once per chunk, not per
        # timestep (the sequential ref is the oracle, not an engine)
        y, new_state = wkv6_chunked(rh, kh, vh, wh.astype(rh.dtype), p["u"])

    y = y.reshape(b, t, e)
    y = rmsnorm(y, p["ln_scale"])                         # head-merge norm
    y = y * jax.nn.silu(g)
    out = jnp.einsum("btf,fe->bte", y, p["wo"].astype(x.dtype))
    if decode:
        return out, x[:, -1], new_state
    return out


def init_rwkv_channel_mix(kg, cfg, dtype=jnp.float32):
    e, f = cfg["embed"], cfg["ffn"]
    p = {
        "wk": he_init(kg(), (e, f), e, dtype),
        "wv": he_init(kg(), (f, e), f, dtype),
        "wr": he_init(kg(), (e, e), e, dtype),
        "mu": jnp.full((2,), 0.5, dtype),
    }
    s = {"wk": ("embed", "ffn"), "wv": ("ffn", "embed"),
         "wr": ("embed", "embed2"), "mu": ("mix",)}
    return p, s


def rwkv_channel_mix(p, x, last_x=None):
    xs = (jnp.concatenate([last_x[:, None], x[:, :-1]], axis=1)
          if last_x is not None else _token_shift(x))
    mu = p["mu"].astype(x.dtype)
    xk = x + mu[0] * (xs - x)
    xr = x + mu[1] * (xs - x)
    k = jnp.square(jax.nn.relu(
        jnp.einsum("bte,ef->btf", xk, p["wk"].astype(x.dtype))))
    kv = jnp.einsum("btf,fe->bte", k, p["wv"].astype(x.dtype))
    r = jax.nn.sigmoid(jnp.einsum("bte,ef->btf", xr, p["wr"].astype(x.dtype)))
    out = r * kv
    if last_x is not None:
        return out, x[:, -1]
    return out
