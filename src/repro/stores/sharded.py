"""Sharded tri-store kernels: the stores partitioned over the mesh ``data``
axis.

Each store partitions along its natural record axis — ColumnStore by row
range, GraphStore by CSR dst-node blocks, TextStore by document range —
and every kernel here is an explicit :func:`shard_map` program whose only
cross-shard traffic is a named collective:

  * filter / count   — shard-local predicate, ``psum`` count (the feedback
    path: ``SelectivityFeedback`` keeps seeing *global* counts);
  * group-agg        — shard-local segment reduce + ``psum`` merge
    (float sums re-associate across shards: allclose, not bitwise);
  * broadcast join   — build side replicated, probe side row-partitioned;
    the probe-aligned output is **bitwise** equal to the dense join;
  * partitioned join — both sides hash-co-partitioned on the key via
    ``all_to_all`` into expected-count-bounded buckets (BoundedRel counts
    size the shuffle buffers), then joined shard-locally; slot order
    differs from the dense join (set-equal, not bitwise);
  * PageRank / k-hop — dst-block-partitioned SpMV with a per-iteration
    frontier ``all_gather``; the stable dst-block edge selection preserves
    per-destination contribution order, so results are **bitwise** equal;
  * top-k TF-IDF     — shard-local scoring + local top-k, then a fixed-
    capacity merge ordered by (score desc, doc asc) — exactly
    ``lax.top_k``'s lowest-index tie-breaking, so **bitwise** equal.

All inputs stay *logically global*: shard_map carves them by ``in_specs``,
so the same payloads run unsharded when no mesh (or a 1-wide data axis) is
present.  Global array lengths must divide the data-axis size — the stores
pad themselves when constructed with ``shards=``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map

from ..core.ir import ValidationError
from .column_store import hash_join, hash_join_nonunique
from .graph_store import accumulator_lanes, lane_segment_sum

P = jax.sharding.PartitionSpec


def data_axis_size(mesh) -> int:
    if mesh is None or "data" not in getattr(mesh, "axis_names", ()):
        return 1
    return int(mesh.shape["data"])


def _shardable(mesh, *lengths) -> bool:
    n = data_axis_size(mesh)
    return n > 1 and all(int(ln) % n == 0 for ln in lengths)


# --------------------------------------------------------------------------
# collective-byte attribution (the runtime side of the cost model's wire-
# byte formulas: what each kernel's collectives actually move, per shard)
# --------------------------------------------------------------------------


def coll_allgather_bytes(nbytes: float, n: int) -> float:
    """Per-shard wire bytes of all-gathering an ``nbytes`` value that is
    partitioned over ``n`` shards: each receives the other (n-1)/n."""
    n = max(1, int(n))
    return float(nbytes) * (n - 1) / n


def coll_psum_bytes(nbytes: float, n: int) -> float:
    """Per-shard wire bytes of a tree all-reduce over an ``nbytes``-sized
    replicated result: log2(n) exchange rounds."""
    import math
    return float(nbytes) * math.log2(max(int(n), 2))


def coll_all_to_all_bytes(nbytes: float, n: int) -> float:
    """Per-shard wire bytes of an all-to-all over staged buckets totalling
    ``nbytes`` per shard: every shard keeps its own 1/n and ships the
    rest."""
    n = max(1, int(n))
    return float(nbytes) * (n - 1) / n


# --------------------------------------------------------------------------
# filter count (the psum feedback path)
# --------------------------------------------------------------------------


def sharded_count(valid, mesh):
    """Global valid-row count as a shard-local sum + ``psum``: the count a
    row-partitioned filter hands to ``SelectivityFeedback`` (identical to
    the dense count — integer addition is associative)."""

    def body(v):
        return jax.lax.psum(jnp.sum(v.astype(jnp.int32)), "data")

    return shard_map(body, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P())(valid)


# --------------------------------------------------------------------------
# group aggregate (psum merge)
# --------------------------------------------------------------------------


def sharded_group_agg(values, keys, num_groups: int, mask, fn: str, mesh):
    """Mask-weighted segment aggregate over a row-partitioned relation:
    shard-local segment reduce, then ``psum`` (``pmax`` for ``max``) into
    the replicated (num_groups,) result.  Cross-shard float addition
    re-associates the dense sum — results are allclose, not bitwise."""
    ng = int(num_groups)

    def seg(v, k, m, red):
        w = m.astype(jnp.float32)
        if fn == "count":
            return red(jax.ops.segment_sum(w, k, num_segments=ng))
        vv = v.astype(jnp.float32)
        if fn == "sum":
            return red(jax.ops.segment_sum(vv * w, k, num_segments=ng))
        if fn == "mean":
            s = red(jax.ops.segment_sum(vv * w, k, num_segments=ng))
            c = red(jax.ops.segment_sum(w, k, num_segments=ng))
            return s / jnp.maximum(c, 1.0)
        if fn == "max":
            neg = jnp.where(m, vv, -jnp.inf)
            gm = jax.lax.pmax(
                jax.ops.segment_max(neg, k, num_segments=ng), "data")
            valid = jnp.isfinite(gm)
            return jnp.where(valid, gm, 0.0), valid
        raise ValidationError(f"sharded_group_agg: unknown fn {fn!r}")

    def body(v, k, m):
        return seg(v, k, m, lambda x: jax.lax.psum(x, "data"))

    out_specs = (P(), P()) if fn == "max" else P()
    vals = (jnp.zeros(keys.shape, jnp.float32) if values is None else values)
    return shard_map(body, mesh=mesh, in_specs=(P("data"),) * 3,
                     out_specs=out_specs)(vals, keys, mask)


# --------------------------------------------------------------------------
# joins
# --------------------------------------------------------------------------


def sharded_broadcast_join(lkeys, rkeys, mesh):
    """Unique-build-key equi-join with the build side replicated and the
    probe side row-partitioned: each shard probes its row block against the
    full build relation, so the probe-aligned ``(idx, matched)`` output is
    bitwise identical to the dense :func:`hash_join`."""

    def body(lk, rk):
        return hash_join(lk, rk)

    return shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                     out_specs=(P("data"), P("data")))(lkeys, rkeys)


def sharded_partitioned_join(lkeys, lmask, rkeys, rmask, capacity: int,
                             mesh, bucket_cap: int):
    """Non-unique-key equi-join with **both sides hash-co-partitioned on
    the key**: every shard routes its rows to ``owner = key % n_data`` via
    one ``all_to_all`` of fixed ``(n_data, bucket_cap)`` buckets, then runs
    the shard-local bounded join over what it received.

    ``bucket_cap`` bounds the shuffle buffer per (sender, owner) pair —
    the planner sizes it from the relation's *expected* count (BoundedRel
    cardinality), so a skewed key distribution overflows visibly (rows
    dropped, ``overflow=True``) instead of allocating for the worst case.

    Returns ``(lidx, ridx, valid, count, overflow)`` like
    :func:`hash_join_nonunique`, with ``lidx``/``ridx`` indexing the
    *global* row domain; output slots land in shard-major order, so the
    result is set-equal (not slot-identical) to the dense join.
    ``capacity`` must divide the data-axis size.
    """
    n = data_axis_size(mesh)
    cap = int(capacity)
    if cap % n:
        raise ValidationError(
            f"sharded_partitioned_join: capacity {cap} must divide "
            f"the data axis ({n})")
    cap_l = cap // n
    bcap = max(1, int(bucket_cap))

    def route(keys, mask, rows_l):
        """Scatter this shard's rows into (n, bcap) owner buckets."""
        gid0 = jax.lax.axis_index("data") * rows_l
        gids = gid0 + jnp.arange(rows_l, dtype=jnp.int32)
        owner = jnp.where(mask, keys % n, n)           # invalid -> trash
        order = jnp.argsort(owner, stable=True)
        so, sk, sg = owner[order], keys[order], gids[order]
        start = jnp.searchsorted(so, jnp.arange(n + 1, dtype=so.dtype))
        rank = jnp.arange(rows_l, dtype=jnp.int32) - start[
            jnp.clip(so, 0, n)].astype(jnp.int32)
        ok = (so < n) & (rank < bcap)
        slot = jnp.where(ok, so * bcap + rank, n * bcap)   # OOB -> dropped
        keys_b = jnp.zeros((n * bcap,), keys.dtype).at[slot].set(
            sk, mode="drop")
        gids_b = jnp.zeros((n * bcap,), jnp.int32).at[slot].set(
            sg, mode="drop")
        mask_b = jnp.zeros((n * bcap,), jnp.bool_).at[slot].set(
            ok, mode="drop")
        dropped = jnp.sum((so < n) & ~ok)
        return keys_b.reshape(n, bcap), gids_b.reshape(n, bcap), \
            mask_b.reshape(n, bcap), dropped

    def exchange(x):
        return jax.lax.all_to_all(x, "data", split_axis=0,
                                  concat_axis=0, tiled=False)

    def body(lk, lm, rk, rm):
        lkb, lgb, lmb, ldrop = route(lk, lm, lk.shape[0])
        rkb, rgb, rmb, rdrop = route(rk, rm, rk.shape[0])
        lk_r, lg_r, lm_r = [exchange(x).reshape(-1)
                            for x in (lkb, lgb, lmb)]
        rk_r, rg_r, rm_r = [exchange(x).reshape(-1)
                            for x in (rkb, rgb, rmb)]
        li, ri, valid, cnt, ovf = hash_join_nonunique(
            lk_r, lm_r, rk_r, rm_r, cap_l)
        count = jax.lax.psum(cnt, "data")
        shuffle_drop = jax.lax.psum(ldrop + rdrop, "data")
        overflow = (jax.lax.psum(ovf.astype(jnp.int32), "data")
                    + shuffle_drop) > 0
        return lg_r[li], rg_r[ri], valid, count, overflow

    return shard_map(
        body, mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(P("data"), P("data"), P("data"), P(), P()))(
            lkeys, lmask, rkeys, rmask)


# --------------------------------------------------------------------------
# graph: dst-block-partitioned SpMV
# --------------------------------------------------------------------------


def _block_spmv(xs_local, blk_src, blk_dstl, blk_w, blk_pos, n_local: int,
                lanes: int):
    """One SpMV step over this shard's dst-block edges.  ``xs_local`` is
    the shard's slice of the source vector; the full vector is gathered
    (the per-iteration frontier all-gather), contributions are computed in
    the stable dst-block edge order into the dense SpMV's accumulator
    lanes (by CSR position ``blk_pos``), and pad edges (``dst_local ==
    n_local``) are dropped by the scatter."""
    xs = jax.lax.all_gather(xs_local, "data", tiled=True)
    return lane_segment_sum(xs[blk_src] * blk_w, blk_dstl, n_local, lanes,
                            pos=blk_pos)


def _graph_lanes(g: dict) -> int:
    """The dense SpMV's accumulator lanes (from the global graph shape)."""
    return accumulator_lanes(int(g["src"].shape[0]),
                             int(g["indptr"].shape[0]) - 1)


def sharded_pagerank(g: dict, iters: int, damping: float,
                     personalization, mesh):
    """Damped power iteration over the dst-block-partitioned graph: rank /
    out-degree / personalization all row(node)-partitioned, one frontier
    all-gather per iteration.  The teleport normalization sums the *fully
    gathered* personalization (not a psum of partials), so every float
    reduction matches the dense kernel's association — bitwise equal."""
    n = int(g["indptr"].shape[0]) - 1
    nd = data_axis_size(mesh)
    n_local = n // nd
    has_p = personalization is not None
    p = (personalization.astype(jnp.float32) if has_p
         else jnp.full((n,), 1.0 / n, jnp.float32))

    lanes = _graph_lanes(g)

    def body(p_l, deg_l, src_b, dst_b, w_b, pos_b):
        if has_p:
            p_full = jax.lax.all_gather(p_l, "data", tiled=True)
            p0_l = p_l / jnp.maximum(jnp.sum(p_full), 1e-30)
        else:
            p0_l = p_l
        r_l = p0_l
        for _ in range(int(iters)):
            y_l = _block_spmv(r_l / deg_l, src_b, dst_b, w_b, pos_b,
                              n_local, lanes)
            r_l = (1.0 - damping) * p0_l + damping * y_l
        return r_l

    return shard_map(body, mesh=mesh, in_specs=(P("data"),) * 6,
                     out_specs=P("data"))(
        p, g["out_deg"], g["blk_src"], g["blk_dst_local"], g["blk_weights"],
        g["blk_pos"])


def sharded_expand(g: dict, frontier, hops: int, mesh):
    """k-hop frontier expansion on the dst-block-partitioned SpMV: one
    all-gather per hop, bitwise equal to the dense expansion."""
    n = int(g["indptr"].shape[0]) - 1
    n_local = n // data_axis_size(mesh)

    lanes = _graph_lanes(g)

    def body(x_l, src_b, dst_b, w_b, pos_b):
        x_l = x_l.astype(jnp.float32)
        for _ in range(int(hops)):
            x_l = _block_spmv(x_l, src_b, dst_b, w_b, pos_b, n_local, lanes)
        return x_l

    return shard_map(body, mesh=mesh, in_specs=(P("data"),) * 5,
                     out_specs=P("data"))(
        frontier, g["blk_src"], g["blk_dst_local"], g["blk_weights"],
        g["blk_pos"])


# --------------------------------------------------------------------------
# text: shard-local scoring + distributed top-k merge
# --------------------------------------------------------------------------


def sharded_tfidf_topk(corpus: dict, query, k: int, mesh):
    """Distributed top-k TF-IDF: score the doc-partitioned corpus shard-
    locally (bitwise: the stable doc-block posting selection preserves
    per-doc contribution order), take each shard's local top-k, then merge
    the fixed-capacity candidate lists by (score desc, doc asc) — exactly
    ``lax.top_k``'s ordering with lowest-index tie-breaking, so the merged
    result is bitwise equal to the dense top-k.

    Returns ``(ids, scores, valid)`` of length ``min(k, n_docs)``.
    """
    n_docs = int(corpus["doc_len"].shape[0])
    nd = data_axis_size(mesh)
    n_local = n_docs // nd
    k = min(int(k), n_docs)
    k_l = min(k, n_local)

    def body(len_l, idf, q, docl, term, tf):
        w = q.astype(jnp.float32) * idf
        contrib = w[term] * tf / len_l[jnp.clip(docl, 0, n_local - 1)]
        scores_l = jax.ops.segment_sum(contrib, docl,
                                       num_segments=n_local)
        vals, ids = jax.lax.top_k(scores_l, k_l)
        gids = (ids + jax.lax.axis_index("data") * n_local).astype(jnp.int32)
        return vals, gids

    vals, gids = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P(), P(), P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data")))(
        corpus["doc_len"], corpus["idf"], query.astype(jnp.float32),
        corpus["blk_doc_local"], corpus["blk_term_ids"], corpus["blk_tf"])
    # fixed-capacity merge: (n_data * k_l) candidates -> global top-k,
    # ordered by (score desc, doc asc) = lax.top_k's tie-breaking
    order = jnp.lexsort((gids, -vals))[:k]
    return (gids[order], vals[order], jnp.ones((k,), jnp.bool_))
