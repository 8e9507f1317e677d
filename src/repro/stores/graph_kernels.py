"""Pallas TPU kernel for the graph store's frontier ops.

The core primitive of every CSR frontier op (k-hop expansion, PageRank
iteration) is the scatter-add ``y[dst[e]] += val[e]``.  A TPU has no fast
random scatter, so the kernel reformulates the reduction as a **one-hot
matmul**: for an edge block and a node block, ``(1, E_blk) @ (E_blk, N_blk)``
where the right operand is the mask ``dst[e] == node_id[n]`` — an
MXU-shaped contraction with no gathers or scatters inside the kernel.  The
node-block accumulator lives in VMEM scratch across the (sequential,
innermost) edge-block grid axis, so each output tile is written to HBM
exactly once — the bytes advantage the cost model credits the Pallas
candidate with.

The value gather ``x[src[e]] * w[e]`` happens *outside* the kernel (XLA
gathers are fine); the kernel owns the scatter side only.  Like every
Pallas kernel here, it compiles on a TPU and runs interpreted elsewhere
(``interpret=None``, :func:`repro.core.backend.pallas_interpret`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.backend import pallas_interpret

# f32 contraction on the MXU: the default TPU precision rounds the value
# operand to bfloat16, and these sums must match the f32 segment_sum
_EXACT = jax.lax.Precision.HIGHEST

# the TPU compiler counts grid steps in int32: a larger grid aborts the
# process inside the compiler instead of raising
MAX_GRID_STEPS = 2 ** 31 - 1


def check_grid(grid, kernel: str) -> tuple:
    """Refuse a one-hot grid the TPU compiler cannot index."""
    steps = 1
    for g in grid:
        steps *= int(g)
    if steps > MAX_GRID_STEPS:
        raise ValueError(
            f"{kernel}: grid {grid} has {steps} steps, beyond the "
            f"{MAX_GRID_STEPS} a TPU kernel can index")
    return grid


def _scatter_add_kernel(dst_ref, val_ref, o_ref, acc_ref, *, block_n):
    eb = pl.program_id(1)

    @pl.when(eb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    node_base = pl.program_id(0) * block_n
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1) + node_base
    dst = dst_ref[...]                       # (1, E_blk) int32
    val = val_ref[...]                       # (1, E_blk) float32
    onehot = (dst[0][:, None] == node_ids[0][None, :]).astype(jnp.float32)
    acc_ref[...] += jnp.dot(val, onehot, precision=_EXACT,
                            preferred_element_type=jnp.float32)

    @pl.when(eb == pl.num_programs(1) - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_nodes", "block_e", "block_n",
                                    "interpret"))
def scatter_add_pallas(vals, dst, *, num_nodes: int, block_e: int = 512,
                       block_n: int = 256, interpret: Optional[bool] = None):
    """``y[n] = sum over e with dst[e]==n of vals[e]`` for ``n < num_nodes``.

    Edge padding uses ``dst = -1`` (matches no node); node padding is
    sliced off the result.
    """
    e = vals.shape[0]
    if e == 0:  # zero-edge graph: nothing to scatter (shape is static)
        return jnp.zeros((num_nodes,), jnp.float32)
    be = min(block_e, max(8, e))
    bn = min(block_n, max(128, num_nodes))
    e_pad = (-e) % be
    n_pad = (-num_nodes) % bn
    vals = jnp.pad(vals.astype(jnp.float32), (0, e_pad))[None, :]
    dst = jnp.pad(dst.astype(jnp.int32), (0, e_pad),
                  constant_values=-1)[None, :]
    n_tot = num_nodes + n_pad

    grid = check_grid((n_tot // bn, (e + e_pad) // be), "scatter_add")
    out = pl.pallas_call(
        functools.partial(_scatter_add_kernel, block_n=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, be), lambda nb, ebk: (0, ebk)),
            pl.BlockSpec((1, be), lambda nb, ebk: (0, ebk)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda nb, ebk: (0, nb)),
        out_shape=jax.ShapeDtypeStruct((1, n_tot), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(dst, vals)
    return out[0, :num_nodes]
