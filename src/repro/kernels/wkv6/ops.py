"""Public jit'd wrapper for the WKV6 kernel: (B, T, H, D) layout in/out,
sequence padding to chunk multiples (decay of padded steps set to 1 and k=0
so the state is unchanged and outputs beyond T are garbage we slice off)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.backend import pallas_interpret
from .ref import wkv6_reference
from .wkv6 import wkv6_hmajor


def wkv6(r, k, v, w, u, *, chunk=128, interpret=None):
    """Differentiable (custom_vjp; backward = oracle VJP).
    ``interpret=None`` follows the backend."""
    if interpret is None:
        interpret = pallas_interpret()
    return _diffable(chunk, bool(interpret))(r, k, v, w, u)


@functools.lru_cache(maxsize=None)
def _diffable(chunk, interpret):
    @jax.custom_vjp
    def f(r, k, v, w, u):
        return _forward(r, k, v, w, u, chunk=chunk, interpret=interpret)

    def fwd(r, k, v, w, u):
        return f(r, k, v, w, u), (r, k, v, w, u)

    def bwd(res, g):
        r, k, v, w, u = res
        _, vjp = jax.vjp(
            lambda *a: wkv6_reference(*a)[0], r, k, v, w, u)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _forward(r, k, v, w, u, *, chunk=128, interpret):
    b, t, h, d = r.shape
    c = min(chunk, max(8, t))
    rem = (-t) % c
    if rem:
        pad = [(0, 0), (0, rem), (0, 0), (0, 0)]
        r = jnp.pad(r, pad)
        k = jnp.pad(k, pad)                     # k=0 ⇒ no state update
        v = jnp.pad(v, pad)
        w = jnp.pad(w, pad, constant_values=1.0)  # w=1 ⇒ state preserved
    y = wkv6_hmajor(r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), w.transpose(0, 2, 1, 3), u,
                    chunk=c, interpret=interpret)
    return y.transpose(0, 2, 1, 3)[:, :t]
