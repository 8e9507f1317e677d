"""Mamba2 block (zamba2's backbone): in-proj → short conv → SSD → gate → out.

The SSD core has two physical candidates (the planner's choice): the chunked
jnp form (``ssd_chunked_xla``) and the Pallas kernel (``ssd_pallas``), both
validated against the sequential-scan oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import he_init
from ..kernels.ssd.ops import ssd as ssd_kernel
from ..kernels.ssd.ref import ssd_chunked, ssd_reference

CONV_K = 4


def init_mamba2(kg, cfg, dtype=jnp.float32):
    e = cfg["embed"]
    n = cfg["state"]
    expand = cfg.get("expand", 2)
    ei = expand * e
    pdim = cfg.get("head_dim", 64)
    h = ei // pdim
    d_in = 2 * ei + 2 * n + h          # z, x, B, C, dt
    p = {
        "w_in": he_init(kg(), (e, d_in), e, dtype),
        "conv": he_init(kg(), (CONV_K, ei + 2 * n), CONV_K, dtype),
        "a_log": jnp.zeros((h,), dtype),
        "dt_bias": jnp.full((h,), -2.0, dtype),
        "d_skip": jnp.ones((h,), dtype),
        "w_out": he_init(kg(), (ei, e), ei, dtype),
    }
    s = {
        "w_in": ("embed", "inner_cat"), "conv": ("conv_k", "inner_cat2"),
        "a_log": ("heads",), "dt_bias": ("heads",), "d_skip": ("heads",),
        "w_out": ("inner", "embed"),
    }
    return p, s


def _split(cfg, zxbcdt):
    e = cfg["embed"]
    n = cfg["state"]
    ei = cfg.get("expand", 2) * e
    pdim = cfg.get("head_dim", 64)
    h = ei // pdim
    return jnp.split(zxbcdt, [ei, 2 * ei, 2 * ei + n, 2 * ei + 2 * n], axis=-1)


def _causal_conv(x, w, conv_state=None):
    """Depthwise causal conv over time.  x: (B,T,C), w: (K,C)."""
    k = w.shape[0]
    if conv_state is not None:                     # decode: (B, K-1, C)
        xx = jnp.concatenate([conv_state, x], axis=1)
        new_state = xx[:, -(k - 1):]
    else:
        xx = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
        new_state = None
    out = sum(xx[:, i:i + x.shape[1]] * w[i][None, None] for i in range(k))
    return jax.nn.silu(out), new_state


def mamba2_block(p, x, cfg, *, use_kernel=False, state=None,
                 conv_state=None):
    """x: (B,T,E).  Decode mode when ``state`` is given: returns
    (y, new_state, new_conv_state)."""
    b, t, e = x.shape
    n = cfg["state"]
    ei = cfg.get("expand", 2) * e
    pdim = cfg.get("head_dim", 64)
    h = ei // pdim
    decode = state is not None

    zxbcdt = jnp.einsum("bte,ed->btd", x, p["w_in"].astype(x.dtype))
    z, xin, bmat, cmat, dt = _split(cfg, zxbcdt)

    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, p["conv"].astype(x.dtype),
                                      conv_state)
    xin, bmat, cmat = jnp.split(conv_out, [ei, ei + n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))      # (B,T,H)
    a = jnp.exp(-dt * jnp.exp(p["a_log"].astype(jnp.float32)))  # (B,T,H)

    xh = xin.reshape(b, t, h, pdim)
    xs = xh * dt[..., None].astype(xh.dtype)                    # dt-scaled in
    bh = jnp.broadcast_to(bmat[:, :, None, :], (b, t, h, n))
    chh = jnp.broadcast_to(cmat[:, :, None, :], (b, t, h, n))

    if decode:
        y, new_state = ssd_reference(xs, a.astype(xs.dtype), bh, chh,
                                     initial_state=state)
    elif use_kernel:
        y = ssd_kernel(xs, a.astype(xs.dtype), bh, chh)
        new_state = None
    else:
        # chunked jnp engine (matmul re-expression; state per chunk)
        y, new_state = ssd_chunked(xs, a.astype(xs.dtype), bh, chh)

    y = y + xh * p["d_skip"].astype(xh.dtype)[None, None, :, None]
    y = y.reshape(b, t, ei) * jax.nn.silu(z)
    out = jnp.einsum("bti,ie->bte", y, p["w_out"].astype(x.dtype))
    if decode:
        return out, new_state, new_conv
    return out
