"""Pallas TPU superkernels for predicate-pushdown over the stores.

Both kernels reuse the one-hot-matmul trick of :mod:`.graph_kernels` — a
segment reduction expressed as ``(1, E_blk) @ (E_blk, N_blk)`` against the
mask ``seg[e] == id[n]``, an MXU-shaped contraction with no scatters — and
add the pushdown twist: every input row carries a *keep* weight derived
from the pushed selection mask, and a whole input block whose keep weights
are all zero is **skipped** (``pl.when``), so masked-out postings/rows cost
neither the elementwise pass nor the matmul.  The accumulator lives in VMEM
scratch across the (sequential, innermost) input-block grid axis, so each
output tile is written to HBM exactly once — the bytes the cost model
credits these candidates with.

Value gathers (``w[term_ids]``, ``doc_len[doc_ids]``, ``mask[doc_ids]``)
happen *outside* the kernels (XLA gathers are fine, the TPU kernel owns the
reduction side only), mirroring ``scatter_add_pallas``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.backend import pallas_interpret
from .graph_kernels import check_grid

# f32 contractions on the MXU: the default TPU precision rounds operands to
# bfloat16, which would round values (and join-probe row indices above 256)
_EXACT = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# masked TF-IDF scoring: gather + mask + segment-sum in one pass
# --------------------------------------------------------------------------


def _masked_tfidf_kernel(doc_ref, qidf_ref, tf_ref, dl_ref, keep_ref,
                         o_ref, acc_ref, *, block_d):
    eb = pl.program_id(1)

    @pl.when(eb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keep = keep_ref[...]                     # (1, E_blk) float32 0/1
    doc_base = pl.program_id(0) * block_d    # grid queries stay outside when
    doc_ids = jax.lax.broadcasted_iota(
        jnp.int32, (1, block_d), 1) + doc_base

    @pl.when(jnp.any(keep > 0))
    def _compute():
        # fused elementwise (the "gather" products arrive pre-gathered):
        # contrib = q·idf[term] * tf / doc_len, zeroed for masked docs
        val = qidf_ref[...] * tf_ref[...] / dl_ref[...] * keep
        doc = doc_ref[...]
        onehot = (doc[0][:, None] == doc_ids[0][None, :]).astype(jnp.float32)
        acc_ref[...] += jnp.dot(val, onehot, precision=_EXACT,
                                preferred_element_type=jnp.float32)

    @pl.when(eb == pl.num_programs(1) - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "block_e", "block_d",
                                    "interpret"))
def masked_tfidf_pallas(doc_ids, qidf_t, tf, dl_t, keep, *, n_docs: int,
                        block_e: int = 512, block_d: int = 256,
                        interpret: Optional[bool] = None):
    """``score[d] = Σ_{postings e: doc[e]==d, keep[e]>0} qidf_t[e]·tf[e]/
    dl_t[e]`` — masked TF-IDF scores over pre-gathered posting features.

    Posting blocks whose ``keep`` weights are all zero are skipped inside
    the kernel.  Edge padding uses ``doc_ids = -1`` (matches no doc) with
    ``keep = 0``; doc padding is sliced off the result.
    """
    e = doc_ids.shape[0]
    if e == 0:
        return jnp.zeros((n_docs,), jnp.float32)
    be = min(block_e, max(8, e))
    bd = min(block_d, max(128, n_docs))
    e_pad = (-e) % be
    d_pad = (-n_docs) % bd

    def prep(a, fill=0):
        return jnp.pad(a, (0, e_pad), constant_values=fill)[None, :]

    doc_p = prep(doc_ids.astype(jnp.int32), -1)
    qidf_p = prep(qidf_t.astype(jnp.float32))
    tf_p = prep(tf.astype(jnp.float32))
    dl_p = prep(dl_t.astype(jnp.float32), 1)     # pad avoids 0/0
    keep_p = prep(keep.astype(jnp.float32))
    d_tot = n_docs + d_pad

    grid = check_grid((d_tot // bd, (e + e_pad) // be), "masked_tfidf")
    espec = pl.BlockSpec((1, be), lambda db, ebk: (0, ebk))
    out = pl.pallas_call(
        functools.partial(_masked_tfidf_kernel, block_d=bd),
        grid=grid,
        in_specs=[espec] * 5,
        out_specs=pl.BlockSpec((1, bd), lambda db, ebk: (0, db)),
        out_shape=jax.ShapeDtypeStruct((1, d_tot), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(doc_p, qidf_p, tf_p, dl_p, keep_p)
    return out[0, :n_docs]


# --------------------------------------------------------------------------
# prefix-sum compaction: valid rows scattered to their prefix positions
# --------------------------------------------------------------------------


def _compact_kernel(pos_ref, keep_ref, val_ref, o_ref, acc_ref, *, block_o):
    rb = pl.program_id(1)

    @pl.when(rb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keep = keep_ref[...]                     # (1, R_blk) float32 0/1
    out_base = pl.program_id(0) * block_o    # grid queries stay outside when
    out_ids = jax.lax.broadcasted_iota(
        jnp.int32, (1, block_o), 1) + out_base

    @pl.when(jnp.any(keep > 0))
    def _compute():
        pos = pos_ref[...]
        # one-hot over destination slots: row i lands at its prefix-sum
        # position; invalid rows (keep=0, pos=-1) match no slot
        onehot = ((pos[0][:, None] == out_ids[0][None, :])
                  .astype(jnp.float32) * keep[0][:, None])
        acc_ref[...] += jnp.dot(val_ref[...], onehot, precision=_EXACT,
                                preferred_element_type=jnp.float32)

    @pl.when(rb == pl.num_programs(1) - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_capacity", "block_r", "block_o",
                                    "interpret"))
def compact_prefix_pallas(vals, pos, keep, *, out_capacity: int,
                          block_r: int = 512, block_o: int = 256,
                          interpret: Optional[bool] = None):
    """Prefix-sum compaction of ``C`` stacked value rows: ``out[c, j] =
    vals[c, i]`` for the row ``i`` whose exclusive mask prefix-sum is ``j``
    (``pos = cumsum(valid) - 1``, computed outside in XLA; the kernel owns
    the scatter side as a destination-one-hot matmul, mirroring the other
    kernels' split).  Row blocks whose ``keep`` weights are all zero are
    skipped.  Values pass through one multiply by 1.0, so float columns are
    bit-exact and integer columns are exact up to 2^24 (the planner's
    candidate gate keeps this kernel off wider keys).

    Row padding uses ``pos = -1`` (matches no slot) with ``keep = 0``;
    positions beyond ``out_capacity`` fall outside every block's id range
    and drop — exactly the capacity-overflow semantics of ``compact``.
    """
    c, r = vals.shape
    if r == 0 or out_capacity == 0:
        return jnp.zeros((c, out_capacity), jnp.float32)
    br = min(block_r, max(8, r))
    bo = min(block_o, max(128, out_capacity))
    r_pad = (-r) % br
    o_pad = (-out_capacity) % bo

    pos_p = jnp.pad(pos.astype(jnp.int32), (0, r_pad),
                    constant_values=-1)[None, :]
    keep_p = jnp.pad(keep.astype(jnp.float32), (0, r_pad))[None, :]
    val_p = jnp.pad(vals.astype(jnp.float32), ((0, 0), (0, r_pad)))
    o_tot = out_capacity + o_pad

    grid = check_grid((o_tot // bo, (r + r_pad) // br), "compact_prefix")
    rspec = pl.BlockSpec((1, br), lambda ob, rbk: (0, rbk))
    out = pl.pallas_call(
        functools.partial(_compact_kernel, block_o=bo),
        grid=grid,
        in_specs=[rspec, rspec, pl.BlockSpec((c, br), lambda ob, rbk: (0, rbk))],
        out_specs=pl.BlockSpec((c, bo), lambda ob, rbk: (0, ob)),
        out_shape=jax.ShapeDtypeStruct((c, o_tot), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c, bo), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(pos_p, keep_p, val_p)
    return out[:, :out_capacity]


# --------------------------------------------------------------------------
# hash-join probe: unique-key build side compared on the MXU
# --------------------------------------------------------------------------


def _join_probe_kernel(lk_ref, rk_ref, rkeep_ref, o_ref):
    # (P_blk, N_build) key-equality one-hot, masked build rows excluded
    eq = ((lk_ref[...][0][:, None] == rk_ref[...][0][None, :])
          .astype(jnp.float32) * rkeep_ref[...][0][None, :])
    nb = rk_ref.shape[1]
    jvec = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0).astype(jnp.float32)
    m = jnp.concatenate([jvec, jnp.ones((nb, 1), jnp.float32)], axis=1)
    # one matmul: col 0 = matched build index, col 1 = match count (0/1)
    acc = jnp.dot(eq, m, precision=_EXACT,
                  preferred_element_type=jnp.float32)
    o_ref[...] = acc.T


@functools.partial(jax.jit,
                   static_argnames=("block_p", "interpret"))
def join_probe_pallas(lkeys, rkeys, rvalid, *, block_p: int = 512,
                      interpret: Optional[bool] = None):
    """Hash-join probe against a **unique-key** build side, realized as an
    MXU key-equality contraction: for each probe key, the matching build
    row index and a match flag.  Masked (invalid) build rows never match.

    The whole build side rides in one VMEM block, which is exactly why the
    planner gates this candidate on the build side's *expected count*: a
    capacity-bounded build (a compacted filter result, a top-k relation)
    fits; a full fact table does not.

    Returns ``(idx, matched)`` with ``idx.shape == lkeys.shape`` — bitwise
    the indices :func:`~repro.stores.column_store.hash_join` produces for
    matched rows (unmatched rows report index 0).
    """
    p = int(lkeys.shape[0])
    nr = int(rkeys.shape[0])
    if p == 0 or nr == 0:
        return (jnp.zeros((p,), jnp.int32), jnp.zeros((p,), jnp.bool_))
    bp = min(block_p, max(8, p))
    p_pad = (-p) % bp
    nr_pad = (-nr) % 128

    lk_p = jnp.pad(lkeys.astype(jnp.int32), (0, p_pad))[None, :]
    rk_p = jnp.pad(rkeys.astype(jnp.int32), (0, nr_pad))[None, :]
    rkeep = jnp.pad(rvalid.astype(jnp.float32), (0, nr_pad))[None, :]

    grid = ((p + p_pad) // bp,)
    bspec = pl.BlockSpec((1, nr + nr_pad), lambda pb: (0, 0))
    out = pl.pallas_call(
        _join_probe_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bp), lambda pb: (0, pb)), bspec, bspec],
        out_specs=pl.BlockSpec((2, bp), lambda pb: (0, pb)),
        out_shape=jax.ShapeDtypeStruct((2, p + p_pad), jnp.float32),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(lk_p, rk_p, rkeep)
    idx = out[0, :p].astype(jnp.int32)
    matched = out[1, :p] > 0
    return jnp.where(matched, idx, 0), matched


# --------------------------------------------------------------------------
# masked segment aggregate: group-by sum + count in one pass
# --------------------------------------------------------------------------


def _masked_segagg_kernel(key_ref, val_ref, mw_ref, o_ref, acc_ref,
                          *, block_g):
    rb = pl.program_id(1)

    @pl.when(rb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mw = mw_ref[...]                         # (1, R_blk) float32 0/1
    group_base = pl.program_id(0) * block_g  # grid queries stay outside when
    group_ids = jax.lax.broadcasted_iota(
        jnp.int32, (1, block_g), 1) + group_base

    @pl.when(jnp.any(mw > 0))
    def _compute():
        key = key_ref[...]
        onehot = (key[0][:, None] == group_ids[0][None, :]).astype(
            jnp.float32)
        # row 0: mask-weighted sums, row 1: mask counts — one matmul each,
        # sharing the one-hot tile
        stacked = jnp.concatenate([val_ref[...] * mw, mw], axis=0)
        acc_ref[...] += jnp.dot(stacked, onehot, precision=_EXACT,
                                preferred_element_type=jnp.float32)

    @pl.when(rb == pl.num_programs(1) - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "block_r", "block_g",
                                    "interpret"))
def masked_segment_agg_pallas(vals, keys, maskw, *, num_groups: int,
                              block_r: int = 512, block_g: int = 256,
                              interpret: Optional[bool] = None):
    """Mask-weighted group-by: ``(sums, counts)`` per group id in one
    kernel pass, skipping row blocks whose mask weights are all zero.

    Row padding uses ``keys = -1`` (matches no group) with ``maskw = 0``;
    group padding is sliced off.  ``mean`` is ``sums / max(counts, 1)``
    outside the kernel; ``max`` is not expressible as a one-hot matmul and
    keeps the segment-max fallback.
    """
    r = vals.shape[0]
    if r == 0:
        z = jnp.zeros((num_groups,), jnp.float32)
        return z, z
    br = min(block_r, max(8, r))
    bg = min(block_g, max(128, num_groups))
    r_pad = (-r) % br
    g_pad = (-num_groups) % bg

    key_p = jnp.pad(keys.astype(jnp.int32), (0, r_pad),
                    constant_values=-1)[None, :]
    val_p = jnp.pad(vals.astype(jnp.float32), (0, r_pad))[None, :]
    mw_p = jnp.pad(maskw.astype(jnp.float32), (0, r_pad))[None, :]
    g_tot = num_groups + g_pad

    grid = check_grid((g_tot // bg, (r + r_pad) // br),
                      "masked_segment_agg")
    rspec = pl.BlockSpec((1, br), lambda gb, rbk: (0, rbk))
    out = pl.pallas_call(
        functools.partial(_masked_segagg_kernel, block_g=bg),
        grid=grid,
        in_specs=[rspec] * 3,
        out_specs=pl.BlockSpec((2, bg), lambda gb, rbk: (0, gb)),
        out_shape=jax.ShapeDtypeStruct((2, g_tot), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, bg), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(key_p, val_p, mw_p)
    return out[0, :num_groups], out[1, :num_groups]
