"""Public jit'd wrapper for the grouped expert matmul: pads capacity and
feature dims to tile multiples, dispatches to the Pallas kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.backend import pallas_interpret
from .moe_gmm import gmm as _gmm
from .ref import gmm_reference


def _pad_axis(x, axis, mult):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def grouped_matmul(x, w, *, block=128, interpret=None):
    """Differentiable (custom_vjp; backward = einsum-oracle VJP).
    ``interpret=None`` follows the backend."""
    if interpret is None:
        interpret = pallas_interpret()
    return _diffable(block, bool(interpret))(x, w)


@functools.lru_cache(maxsize=None)
def _diffable(block, interpret):
    @jax.custom_vjp
    def f(x, w):
        return _forward(x, w, block=block, interpret=interpret)

    def fwd(x, w):
        return f(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        _, vjp = jax.vjp(gmm_reference, x, w)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _forward(x, w, *, block=128, interpret):
    e, c, d = x.shape
    f = w.shape[-1]
    bc = min(block, max(8, c))
    bd = min(block, max(8, d))
    bf = min(block, max(8, f))
    xp = _pad_axis(_pad_axis(x, 1, bc), 2, bd)
    wp = _pad_axis(_pad_axis(w, 1, bd), 2, bf)
    out = _gmm(xp, wp, block_c=bc, block_f=bf, block_d=bd,
               interpret=interpret)
    return out[:, :c, :f]
