"""Public jit'd wrapper for the flash-attention kernel.

Accepts the framework-standard (B, S, H, D) layout, pads sequence lengths to
block multiples (masked out in-kernel via the length arguments), transposes
to the kernel's heads-major layout, and dispatches to the Pallas kernel —
compiled on a TPU and interpreted elsewhere (``core.backend``).

Differentiation: the kernel carries a ``custom_vjp`` whose backward is the
VJP of the pure-jnp oracle (recompute-from-inputs).  On TPU the backward
re-materializes the S×S logits (a dedicated backward kernel is the known
next step); numerically it is exactly the reference gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.backend import pallas_interpret
from .flash_attention import flash_attention_hmajor
from .ref import mha_reference


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def flash_attention(q, k, v, *, causal=True, window=0, sm_scale=None,
                    block_q=128, block_k=128, interpret=None):
    """q: (B, Sq, H, D); k, v: (B, Skv, K, D) -> (B, Sq, H, D).
    Differentiable (custom_vjp; backward = oracle VJP).  ``interpret=None``
    follows the backend."""
    if interpret is None:
        interpret = pallas_interpret()
    fn = _diffable(bool(causal), int(window or 0),
                   float(sm_scale) if sm_scale is not None else None,
                   block_q, block_k, bool(interpret))
    return fn(q, k, v)


@functools.lru_cache(maxsize=None)
def _diffable(causal, window, sm_scale, block_q, block_k, interpret):
    @jax.custom_vjp
    def f(q, k, v):
        return _forward(q, k, v, causal=causal, window=window,
                        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                        interpret=interpret)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=causal,
                                             window=window,
                                             sm_scale=sm_scale), q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _forward(q, k, v, *, causal=True, window=0, sm_scale=None,
             block_q=128, block_k=128, interpret):
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    bq = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (skv - 1).bit_length()))

    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, bq)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bk)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bk)

    # padding keys must be masked: kernel masks k_ids >= kv_len via kv_len
    # argument == true length? We pass padded lengths; instead mask by
    # shifting: true lengths are threaded through the causal/q-pos logic, so
    # pad on the *left* of kv would break alignment.  We pad on the right and
    # rely on the in-kernel (k_ids < kv_len)&(q_ids < q_len) guards with the
    # *true* lengths baked in below.
    out = _call_padded(qt, kt, vt, sq, skv, causal, window, sm_scale, bq, bk,
                       interpret)
    return out[:, :, :sq, :].transpose(0, 2, 1, 3)


def _call_padded(qt, kt, vt, true_q, true_kv, causal, window, sm_scale,
                 bq, bk, interpret):
    import functools
    from .flash_attention import _attn_kernel, NEG_INF  # noqa: F401
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = qt.shape
    _, kh, skv, _ = kt.shape
    groups = h // kh
    scale = float(sm_scale) if sm_scale is not None else qt.shape[-1] ** -0.5

    qr = qt.reshape(b * h, sq, d)
    kr = kt.reshape(b * kh, skv, d)
    vr = vt.reshape(b * kh, skv, d)
    grid = (b * h, sq // bq, skv // bk)

    kernel = functools.partial(
        _attn_kernel, sm_scale=scale, causal=causal, window=int(window or 0),
        block_q=bq, block_k=bk, kv_len=true_kv, q_len=true_q)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qb, kb: ((bh // h) * kh + (bh % h) // groups,
                                             kb, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qb, kb: ((bh // h) * kh + (bh % h) // groups,
                                             kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), qt.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)
