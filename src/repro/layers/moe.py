"""Mixture-of-Experts family — the planner's three dispatch candidates:

  * ``moe_dense_onehot`` — capacity-2.0 scatter dispatch (≈ no drops at
    typical balance); the Switch/Mixtral-JAX form whose all-to-all GSPMD
    emits from the expert sharding;
  * ``moe_dropping``     — capacity-1.0 dispatch (overflow tokens fall back
    to the residual path); half the expert flops;
  * ``moe_gmm``          — capacity dispatch + the Pallas grouped matmul.

Dispatch is scatter-based: each (token, k) assignment gets a rank within its
expert via a one-hot cumsum, then tokens scatter into the (E, C, D) expert
buffer and gather back after the expert MLP — O(T·K·E) bookkeeping and
O(E·C·D) buffers, never the O(T·E·C) dispatch tensor of the naive einsum
formulation (which is quadratic in tokens and unusable at pod scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import he_init
from .mlp import _ACTS
from ..kernels.moe_gmm.ops import grouped_matmul


def init_moe(kg, cfg, dtype=jnp.float32):
    e, f, x = cfg["embed"], cfg["ffn"], cfg["experts"]
    p = {
        "router": he_init(kg(), (e, x), e, dtype),
        "wi": he_init(kg(), (x, e, f), e, dtype),
        "wg": he_init(kg(), (x, e, f), e, dtype),
        "wo": he_init(kg(), (x, f, e), f, dtype),
    }
    s = {
        "router": ("embed", "experts"),
        "wi": ("experts", "embed", "ffn"),
        "wg": ("experts", "embed", "ffn"),
        "wo": ("experts", "ffn", "embed"),
    }
    return p, s


def _route(p, x, top_k):
    logits = jnp.einsum("bse,ex->bsx", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    weights, idx = jax.lax.top_k(logits, top_k)           # (B,S,K)
    weights = jax.nn.softmax(weights, axis=-1)
    return weights, idx


def moe_capacity_dispatch(p, x, *, top_k, experts, capacity_factor=2.0,
                          act="silu", use_gmm=False, constrain=None):
    """Row-grouped capacity dispatch.

    The scatter into expert buffers happens *per batch row* (the row dim is
    preserved through the scatter), so under batch→data sharding the scatter
    stays device-local; the (B, E, C, D) → (E, B·C, D) rearrange before the
    expert matmuls is what GSPMD lowers to the canonical MoE **all-to-all**
    across data↔model.  (A global scatter-add buffer instead lowers to an
    all-reduce of the whole expert buffer per layer — measured +1.5e12
    bytes/device on llama4-maverick×train_4k; see §Perf iter L2.)
    """
    b, s, e = x.shape
    cap = max(8, int(s * top_k * capacity_factor / experts))
    weights, idx = _route(p, x, top_k)                    # (B,S,K)

    flat_w = weights.reshape(b, s * top_k)                # (B, A)
    flat_i = idx.reshape(b, s * top_k)                    # (B, A)
    tok_of = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s), top_k)[None], (b, s * top_k))

    onehot = jax.nn.one_hot(flat_i, experts, dtype=jnp.int32)   # (B, A, E)
    rank = jnp.sum(jnp.cumsum(onehot, axis=1) * onehot, axis=-1) - 1
    keep = rank < cap                                            # (B, A)
    dest = jnp.where(keep, flat_i * cap + rank, experts * cap)   # (B, A)

    def dispatch_row(xr, dr, tr, kr):
        buf = jnp.zeros((experts * cap + 1, e), x.dtype)
        return buf.at[dr].add(xr[tr] * kr[:, None].astype(x.dtype))[:-1]

    buf = jax.vmap(dispatch_row)(x, dest, tok_of, keep)   # (B, E*C, D)
    if constrain is not None:
        buf = constrain(buf, ("batch", None, None))
    expert_in = buf.reshape(b, experts, cap, e)
    # (B, E, C, D) -> (E, B*C, D): the all-to-all boundary
    expert_in = jnp.moveaxis(expert_in, 1, 0).reshape(experts, b * cap, e)
    if constrain is not None:
        # pin the post-a2a layout: experts→model, token rows→data — without
        # this GSPMD can replicate the expert matmuls over data (measured
        # 5× compute on llama4 with replicated weights)
        expert_in = constrain(expert_in, ("experts", "batch", None))

    if use_gmm:
        up = grouped_matmul(expert_in, p["wi"].astype(x.dtype))
        gate = grouped_matmul(expert_in, p["wg"].astype(x.dtype))
        h = _ACTS[act](gate) * up
        out = grouped_matmul(h, p["wo"].astype(x.dtype))
    else:
        up = jnp.einsum("xce,xef->xcf", expert_in, p["wi"].astype(x.dtype))
        gate = jnp.einsum("xce,xef->xcf", expert_in, p["wg"].astype(x.dtype))
        h = _ACTS[act](gate) * up
        out = jnp.einsum("xcf,xfe->xce", h, p["wo"].astype(x.dtype))

    if constrain is not None:
        out = constrain(out, ("experts", "batch", None))
    # (E, B*C, D) -> (B, E*C, D): the return all-to-all
    out = jnp.moveaxis(out.reshape(experts, b, cap, e), 1, 0)
    out = out.reshape(b, experts * cap, e)
    if constrain is not None:
        out = constrain(out, ("batch", None, None))

    def combine_row(orow, dr, kr, wr):
        gathered = jnp.where(
            kr[:, None], orow[jnp.minimum(dr, experts * cap - 1)],
            jnp.zeros((1, e), x.dtype))
        contrib = gathered * wr[:, None].astype(x.dtype)
        return jnp.zeros((s, e), x.dtype).at[
            jnp.repeat(jnp.arange(s), top_k)].add(contrib)

    y = jax.vmap(combine_row)(out, dest, keep, flat_w)
    return y.reshape(b, s, e)


def moe_dense(p, x, *, top_k, experts, act="silu", capacity_factor=2.0,
              constrain=None):
    return moe_capacity_dispatch(p, x, top_k=top_k, experts=experts,
                                 capacity_factor=capacity_factor, act=act,
                                 constrain=constrain)


def moe_dropping(p, x, *, top_k, experts, act="silu", constrain=None):
    return moe_capacity_dispatch(p, x, top_k=top_k, experts=experts,
                                 capacity_factor=1.0, act=act,
                                 constrain=constrain)


def moe_gmm(p, x, *, top_k, experts, act="silu", capacity_factor=2.0,
            constrain=None):
    return moe_capacity_dispatch(p, x, top_k=top_k, experts=experts,
                                 capacity_factor=capacity_factor, act=act,
                                 use_gmm=True, constrain=constrain)


def moe_reference_dense(p, x, *, top_k, experts, act="silu"):
    """No-capacity oracle: every token reaches its experts (tests only)."""
    b, s, e = x.shape
    weights, idx = _route(p, x, top_k)
    up = jnp.einsum("bse,xef->bsxf", x, p["wi"].astype(x.dtype))
    gate = jnp.einsum("bse,xef->bsxf", x, p["wg"].astype(x.dtype))
    h = _ACTS[act](gate) * up
    out = jnp.einsum("bsxf,xfe->bsxe", h, p["wo"].astype(x.dtype))
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for j in range(top_k):
        oh = jax.nn.one_hot(idx[..., j], experts, dtype=x.dtype)
        sel = jnp.einsum("bsxe,bsx->bse", out, oh)
        y = y + sel.astype(jnp.float32) * weights[..., j:j + 1]
    return y.astype(x.dtype)
