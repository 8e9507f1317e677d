"""Attention layer family: projections, SDPA variants, KV-cache decode.

Three physical realizations of the same logical sdpa (the planner's
candidates):
  * ``sdpa_xla``        — full masked attention, materialized logits;
  * ``sdpa_banded_xla`` — O(S·W) chunked local-window attention;
  * ``attn_flash``      — the Pallas kernel (kernels/flash_attention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.flash_attention.ops import flash_attention
from ..kernels.flash_attention.ref import mha_reference
from .common import he_init, rmsnorm, rope


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def init_attention(kg, cfg_attn, dtype=jnp.float32):
    """cfg_attn: dict(embed, heads, kv_heads, head_dim, qk_norm)."""
    e = cfg_attn["embed"]
    h, k, d = cfg_attn["heads"], cfg_attn["kv_heads"], cfg_attn["head_dim"]
    p = {
        "wq": he_init(kg(), (e, h * d), e, dtype),
        "wk": he_init(kg(), (e, k * d), e, dtype),
        "wv": he_init(kg(), (e, k * d), e, dtype),
        "wo": he_init(kg(), (h * d, e), h * d, dtype),
    }
    s = {
        "wq": ("embed", "heads_flat"),
        "wk": ("embed", "kv_flat"),
        "wv": ("embed", "kv_flat"),
        "wo": ("heads_flat", "embed"),
    }
    if cfg_attn.get("qk_norm"):
        p["q_norm"] = jnp.zeros((d,), dtype)
        p["k_norm"] = jnp.zeros((d,), dtype)
        s["q_norm"] = ("head_dim",)
        s["k_norm"] = ("head_dim",)
    return p, s


# --------------------------------------------------------------------------
# projections
# --------------------------------------------------------------------------

def project_q(p, x, h, d):
    return jnp.einsum("bse,ef->bsf", x, p["wq"].astype(x.dtype)).reshape(
        x.shape[0], x.shape[1], h, d)


def project_kv(p, x, k, d):
    kk = jnp.einsum("bse,ef->bsf", x, p["wk"].astype(x.dtype)).reshape(
        x.shape[0], x.shape[1], k, d)
    vv = jnp.einsum("bse,ef->bsf", x, p["wv"].astype(x.dtype)).reshape(
        x.shape[0], x.shape[1], k, d)
    return kk, vv


def project_qkv_fused(p, x, h, k, d):
    """One gemm over the concatenated projection — the fused candidate."""
    w = jnp.concatenate(
        [p["wq"], p["wk"], p["wv"]], axis=-1).astype(x.dtype)
    out = jnp.einsum("bse,ef->bsf", x, w)
    q, kk, vv = jnp.split(out, [h * d, h * d + k * d], axis=-1)
    b, s = x.shape[:2]
    return (q.reshape(b, s, h, d), kk.reshape(b, s, k, d),
            vv.reshape(b, s, k, d))


def qk_prep(p, q, k, positions, *, qk_norm=False, use_rope=True,
            rope_theta=10000.0):
    if qk_norm and "q_norm" in p:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if use_rope:
        q = rope(q, positions, theta=rope_theta)
        k = rope(k, positions, theta=rope_theta)
    return q, k


def out_project(p, attn_out):
    b, s, h, d = attn_out.shape
    return jnp.einsum("bsf,fe->bse", attn_out.reshape(b, s, h * d),
                      p["wo"].astype(attn_out.dtype))


# --------------------------------------------------------------------------
# SDPA candidates
# --------------------------------------------------------------------------

def sdpa_full(q, k, v, *, causal=True, window=0):
    return mha_reference(q, k, v, causal=causal, window=window)


def sdpa_banded(q, k, v, *, window, causal=True):
    """Chunked local attention: O(S·W) compute.  Sequence is cut into chunks
    of size W; each query chunk attends to its own chunk plus the previous
    one, masked to the sliding window — the standard TPU-friendly banding."""
    b, s, h, d = q.shape
    _, _, kh, _ = k.shape
    w = int(window)
    if w <= 0 or w >= s:
        return sdpa_full(q, k, v, causal=causal, window=window)
    groups = h // kh
    pad = (-s) % w
    sp = s + pad
    qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
    kp = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
    vp = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
    nc = sp // w
    qc = qp.reshape(b, nc, w, h, d)
    kc = kp.reshape(b, nc, w, kh, d)
    vc = vp.reshape(b, nc, w, kh, d)
    # keys: previous chunk ++ own chunk  (window ≤ W ⇒ covered)
    k2 = jnp.concatenate([jnp.pad(kc[:, :-1], [(0, 0), (1, 0), (0, 0),
                                               (0, 0), (0, 0)]), kc], axis=2)
    v2 = jnp.concatenate([jnp.pad(vc[:, :-1], [(0, 0), (1, 0), (0, 0),
                                               (0, 0), (0, 0)]), vc], axis=2)
    kr = jnp.repeat(k2, groups, axis=3)
    vr = jnp.repeat(v2, groups, axis=3)
    logits = jnp.einsum("bcqhd,bckhd->bchqk", qc.astype(jnp.float32),
                        kr.astype(jnp.float32)) * (d ** -0.5)
    qi = jnp.arange(w)[:, None] + w                       # position in 2W axis
    ki = jnp.arange(2 * w)[None, :]
    mask = (ki <= qi) & (ki > qi - w)                     # causal ∧ window
    # first chunk's "previous" keys are padding
    first = (jnp.arange(nc) == 0).reshape(1, nc, 1, 1, 1)
    pad_keys = (ki < w)[None, None, None]                 # (1,1,1,1,2w)
    mask = mask[None, None, None] & ~(first & pad_keys)
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bchqk,bckhd->bcqhd", p, vr.astype(jnp.float32))
    out = out.reshape(b, sp, h, d)[:, :s]
    return out.astype(q.dtype)


def sdpa_flash(q, k, v, *, causal=True, window=0):
    return flash_attention(q, k, v, causal=causal, window=window)


# --------------------------------------------------------------------------
# KV-cache decode
# --------------------------------------------------------------------------

def decode_attend_gqa(q, cache_k, cache_v, valid_mask, *, k_scale=None,
                      v_scale=None):
    """Repeat-free GQA attention for decode: q (B, 1, H, D) grouped as
    (B, KV, G, D) against the cache (B, S, KV, D) directly.  ``jnp.repeat``
    on a multi-GB cache materializes a full copy per layer (measured +0.13 s
    on the qwen3 decode memory term); the grouped einsum reads the cache
    once.

    int8 caches pass per-(position, head) ``k_scale``/``v_scale``
    (B, S, KV, 1): the k-scale factors out of the qk contraction (applied to
    the logits) and the v-scale folds into the softmax weights — the int8
    tensors are the only cache-sized reads."""
    b, one, h, d = q.shape
    kv = cache_k.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, d)                     # (B, KV, G, D)
    scale = d ** -0.5
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        cache_k.astype(jnp.float32)) * scale
    if k_scale is not None:                          # (B,S,KV,1) -> (B,KV,1,S)
        logits = logits * k_scale[..., 0].transpose(0, 2, 1)[:, :, None, :] \
            .astype(jnp.float32)
    logits = jnp.where(valid_mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        p = p * v_scale[..., 0].transpose(0, 2, 1)[:, :, None, :] \
            .astype(jnp.float32)
    out = jnp.einsum("bkgs,bskd->bkgd", p, cache_v.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def quantize_kv(x, *, axis=-1):
    """abs-max int8 quantization along ``axis``: returns (int8, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    sc = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / sc), -127, 127)
    return q.astype(jnp.int8), sc.astype(jnp.bfloat16)


def decode_attend(q, cache_k, cache_v, index, *, window=0):
    """q: (B, 1, H, D); cache_k/v: (B, S_max, K, D); index: scalar count of
    valid cache entries *including* the newly-written position."""
    b, _, h, d = q.shape
    s_max = cache_k.shape[1]
    valid = jnp.arange(s_max)[None, :] < index                  # (1, S)
    if window and window > 0:
        valid = valid & (jnp.arange(s_max)[None, :] >= index - window)
    return mha_reference(q, cache_k, cache_v, causal=False,
                         kv_len_mask=jnp.broadcast_to(valid, (b, s_max)))


def cache_update(cache_k, cache_v, new_k, new_v, index):
    """Write the new token's k/v at position ``index`` (decode step)."""
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, new_k, index, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, new_v, index, axis=1)
    return ck, cv
