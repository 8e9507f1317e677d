"""Executor implementations for the tri-store physical operators.

Each store engine owns its impl table (``engines.py``); importing this
module registers the relational / graph / text implementations plus the two
cross-engine transfer realizations.  Every relational value — plan input,
intermediate, or output — is a :class:`~repro.stores.bounded.BoundedRel`
(a registered pytree: struct-of-arrays columns + validity + traced row
count), so a whole tri-model plan stays jittable end to end and the
*cardinality* of every intermediate is a first-class runtime value: masks
are no longer rel-engine-private, and the executor can observe
``count/capacity`` per site for selectivity feedback
(``ExecContext.aux["count_sink"]``, see ``PlannedFunction.observe``).

The relational ops are factored as pure *step functions* shared by the
per-op impls and the fused-chain impls (``rel_fused_*``): a fused chain
executes exactly the same step functions in the same order, so fusion is
bitwise-neutral by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engines import get_engine
from ..core.feedback import filter_site, sel_mask_site
from ..core.ledger import default_ledger
from .base import GRAPH_ENGINE, REL_ENGINE, TEXT_ENGINE
from .bounded import BoundedRel, as_bounded, compact_rel
from .column_store import (filter_mask, group_agg, hash_join,
                           hash_join_nonunique)
from .graph_store import (expand_frontier, expand_frontier_blockskip,
                          pagerank, triangle_count)
from .masked_kernels import (compact_prefix_pallas, join_probe_pallas,
                             masked_segment_agg_pallas, masked_tfidf_pallas)
from .sharded import (_shardable, coll_all_to_all_bytes, coll_allgather_bytes,
                      coll_psum_bytes, data_axis_size, sharded_broadcast_join,
                      sharded_count, sharded_expand, sharded_group_agg,
                      sharded_pagerank, sharded_partitioned_join,
                      sharded_tfidf_topk)
from .text_store import (ell_select_applies, masked_topk, tfidf_scores,
                         tfidf_topk, tfidf_topk_blockskip, tfidf_topk_masked)

_XLA = get_engine("xla")
_PALLAS = get_engine("pallas")


def _record_count(ctx, site, count, capacity):
    """Cardinality observation hook: when the caller planted a
    ``count_sink`` (PlannedFunction.observe runs plans eagerly with one),
    append this site's observed (count, capacity).  Counts stay on device
    — the sink drains in **one** ``device_get`` per run
    (``tracing.resolve_counts``), never per site."""
    sink = None if ctx is None else ctx.aux.get("count_sink")
    if sink is not None:
        sink.append((site, count, capacity))


def _annotate(ctx, **attrs):
    """Runtime-attribution hook: when the executor traced this op
    (``ExecContext.tracer``), report which dist strategy the impl actually
    dispatched and the per-shard collective bytes its kernel moves.  A
    cheap no-op when tracing is off."""
    tr = None if ctx is None else getattr(ctx, "tracer", None)
    if tr is not None:
        tr.annotate(**attrs)


# --------------------------------------------------------------------------
# relational engine: step functions + per-op impls
# --------------------------------------------------------------------------


def _step_rel_scan(tbl, attrs, ctx=None):
    rel = as_bounded(tbl)
    cols = attrs.get("cols")
    if cols:
        return rel.with_cols({c: rel.cols[c] for c in cols})
    return rel


def _step_rel_filter(tbl, attrs, ctx=None):
    rel = as_bounded(tbl)
    m = filter_mask(rel.cols[attrs["col"]], attrs["cmp"], attrs["value"])
    out = rel.narrowed(m)
    if ctx is not None and ctx.aux.get("count_sink") is not None:
        # record the *marginal* selectivity (survivors over the rows this
        # filter actually saw), not the cumulative count/capacity fraction
        # — estimate_selectivity multiplies marginals along the lineage,
        # so a cumulative observation would double-discount upstream
        # narrowing.  The planner-stamped site (stable across compaction
        # rerouting) wins over the self-derived one.
        site = attrs.get("site")
        if site is None:
            site = filter_site(attrs, rel.col_names(), rel.capacity)
        count = out.count
        mesh = getattr(ctx, "mesh", None)
        if (attrs.get("dist") == "row"
                and _shardable(mesh, out.valid.shape[0])):
            # shard-local survivor count + psum: integer addition is
            # associative, so SelectivityFeedback sees the exact count
            count = sharded_count(out.valid, mesh)
            _annotate(ctx, dist="row", coll="psum",
                      coll_bytes=coll_psum_bytes(4, data_axis_size(mesh)))
        _record_count(ctx, tuple(site), count,
                      jnp.maximum(rel.count, 1))
    return out


def _merge_join_cols(left, right, ro, idx):
    """Joined column set: every left column plus the right side's
    non-key, non-colliding columns gathered at ``idx``."""
    cols = dict(left.cols)
    for k, v in right.cols.items():
        if k == ro or k in cols:
            continue
        cols[k] = v[idx]
    return cols


def _step_rel_join(left, right, attrs, ctx=None):
    left, right = as_bounded(left), as_bounded(right)
    lo, ro = attrs["left_on"], attrs["right_on"]
    idx, matched = hash_join(left.cols[lo], right.cols[ro])
    rmask = right.valid[idx]
    cols = _merge_join_cols(left, right, ro, idx)
    valid = left.valid & matched & rmask
    return BoundedRel(cols, valid, None, left.overflow | right.overflow)


def _step_rel_join_probe(left, right, attrs, ctx=None):
    """The Pallas probe realization of ``rel_join``: key equality on the
    MXU against the (expected-count-bounded) build side.  Invalid build
    rows never match, so validity needs no second gather; gathered values
    at unmatched rows differ from the sort-probe path only under
    ``valid=False``, which every consumer weights away."""
    left, right = as_bounded(left), as_bounded(right)
    lo, ro = attrs["left_on"], attrs["right_on"]
    idx, matched = join_probe_pallas(left.cols[lo], right.cols[ro],
                                     right.valid)
    cols = _merge_join_cols(left, right, ro, idx)
    valid = left.valid & matched
    return BoundedRel(cols, valid, None, left.overflow | right.overflow)


def _step_bounded_join(left, right, attrs, ctx=None):
    left, right = as_bounded(left), as_bounded(right)
    lo, ro = attrs["left_on"], attrs["right_on"]
    lidx, ridx, valid, count, ovf = hash_join_nonunique(
        left.cols[lo], left.valid, right.cols[ro], right.valid,
        int(attrs["capacity"]))
    gathered = left.with_cols({k: v[lidx] for k, v in left.cols.items()})
    cols = _merge_join_cols(gathered, right, ro, ridx)
    return BoundedRel(cols, valid, count,
                      ovf | left.overflow | right.overflow)


def _step_rel_group_agg(tbl, attrs, ctx=None):
    rel = as_bounded(tbl)
    key = rel.cols[attrs["key"]]
    g = int(attrs["num_groups"])
    mask = rel.valid
    cols = {attrs["key"]: jnp.arange(g, dtype=jnp.int32)}
    for out_name, fn, col in attrs["aggs"]:
        vals = None if fn == "count" else rel.cols[col]
        r = group_agg(vals, key, g, mask, fn)
        if fn == "max":
            # the pair convention collapses into row validity: an
            # all-masked group is an *invalid row* of the output relation
            r, _valid = r
        cols[out_name] = r
    count = group_agg(None, key, g, mask, "count")
    return BoundedRel(cols, count > 0, None, rel.overflow)


def _step_compact(tbl, attrs, ctx=None):
    rel = as_bounded(tbl)
    out = compact_rel(rel, attrs.get("capacity"))
    _record_overflow(ctx, attrs, out)
    return out


def _record_overflow(ctx, attrs, out):
    """Report a compaction site's overflow flag to the observation sink:
    an overflowed bound dropped rows, and the feedback store's
    ``note_overflow`` makes ``choose_compaction`` back off from the site
    on re-plan instead of staying silently lossy."""
    site = attrs.get("site")
    if site is not None:
        _record_count(ctx, ("compact_overflow", tuple(site)),
                      out.overflow, 1)


def _step_compact_pallas(tbl, attrs, ctx=None):
    """Pallas realization of ``compact``: destination positions from an
    XLA prefix sum, the scatter as the one-hot-matmul compaction kernel.
    Bit-exact for float columns; integer columns round-trip through
    float32 (exact below 2^24, which the candidate gate enforces)."""
    rel = as_bounded(tbl)
    cap = int(attrs.get("capacity", rel.capacity))
    cap = max(1, min(cap, rel.capacity))
    keep = rel.valid.astype(jnp.float32)
    pos = jnp.where(rel.valid, jnp.cumsum(rel.valid.astype(jnp.int32)) - 1,
                    -1)
    names = tuple(rel.cols)
    stacked = jnp.stack([rel.cols[n].astype(jnp.float32) for n in names])
    out = compact_prefix_pallas(stacked, pos, keep, out_capacity=cap)
    count = jnp.minimum(rel.count, cap).astype(jnp.int32)
    valid = jnp.arange(cap, dtype=jnp.int32) < count
    cols = {}
    for i, n in enumerate(names):
        dt = rel.cols[n].dtype
        v = out[i]
        if jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_:
            v = jnp.round(v)
        cols[n] = v.astype(dt)
    overflow = rel.overflow | (rel.count > cap)
    out = BoundedRel(cols, valid, count, overflow)
    _record_overflow(ctx, attrs, out)
    return out


_REL_STEPS = {
    "rel_scan": lambda ins, attrs, ctx=None: _step_rel_scan(ins[0], attrs, ctx),
    "rel_filter": lambda ins, attrs, ctx=None: _step_rel_filter(ins[0], attrs,
                                                                ctx),
    "rel_join": lambda ins, attrs, ctx=None: _step_rel_join(ins[0], ins[1],
                                                            attrs, ctx),
    "bounded_join": lambda ins, attrs, ctx=None: _step_bounded_join(
        ins[0], ins[1], attrs, ctx),
    "rel_group_agg": lambda ins, attrs, ctx=None: _step_rel_group_agg(
        ins[0], attrs, ctx),
    "compact": lambda ins, attrs, ctx=None: _step_compact(ins[0], attrs, ctx),
}


def _run_chain(args, chain, ctx=None, *, stop_before_last=False):
    """Execute a ``rel_fused`` step chain over the node's bound inputs."""
    steps = chain[:-1] if stop_before_last else chain
    prev = None
    for op, attrs, srcs, _out_t in steps:
        ins = [prev if s == "prev" else args[int(s)] for s in srcs]
        prev = _REL_STEPS[op](ins, attrs, ctx)
    return prev


@REL_ENGINE.impl("rel_scan_col")
def _i_rel_scan(ctx, args, node):
    return _step_rel_scan(args[0], node.attrs, ctx)


@REL_ENGINE.impl("rel_filter_col")
def _i_rel_filter(ctx, args, node):
    return _step_rel_filter(args[0], node.attrs, ctx)


@REL_ENGINE.impl("rel_hash_join")
def _i_rel_join(ctx, args, node):
    a = node.attrs
    mesh = getattr(ctx, "mesh", None)
    if a.get("dist") == "broadcast":
        left, right = as_bounded(args[0]), as_bounded(args[1])
        if _shardable(mesh, left.capacity):
            # probe side row-partitioned, build side replicated: each shard
            # probes its block against the full build (bitwise = dense)
            idx, matched = sharded_broadcast_join(
                left.cols[a["left_on"]], right.cols[a["right_on"]], mesh)
            n = data_axis_size(mesh)
            build_b = sum(int(v.size) * v.dtype.itemsize
                          for v in right.cols.values()) + right.capacity
            _annotate(ctx, dist="broadcast", coll="all_gather",
                      coll_bytes=coll_allgather_bytes(build_b, n))
            cols = _merge_join_cols(left, right, a["right_on"], idx)
            valid = left.valid & matched & right.valid[idx]
            return BoundedRel(cols, valid, None,
                              left.overflow | right.overflow)
    return _step_rel_join(args[0], args[1], node.attrs, ctx)


@_PALLAS.impl("rel_join_probe_pallas")
def _i_rel_join_probe(ctx, args, node):
    return _step_rel_join_probe(args[0], args[1], node.attrs, ctx)


@REL_ENGINE.impl("bounded_join_col")
def _i_bounded_join(ctx, args, node):
    a = node.attrs
    mesh = getattr(ctx, "mesh", None)
    if a.get("dist") == "partitioned":
        left, right = as_bounded(args[0]), as_bounded(args[1])
        cap = int(a["capacity"])
        if _shardable(mesh, left.capacity, right.capacity, cap):
            # co-partition both sides on the key (one all-to-all of fixed
            # bucket_cap buckets), then join shard-locally.  Output rows
            # land in shard-major slot order: same match *set* as the
            # dense join, different slot order.
            bucket_cap = int(a.get("bucket_cap", 64))
            lidx, ridx, valid, count, ovf = sharded_partitioned_join(
                left.cols[a["left_on"]], left.valid,
                right.cols[a["right_on"]], right.valid,
                cap, mesh, bucket_cap)
            n = data_axis_size(mesh)
            # both sides route (n, bucket_cap) staged buckets of
            # (key, slot-index, validity) rows through one all_to_all
            staged = 2 * n * bucket_cap * (4 + 4 + 1)
            _annotate(ctx, dist="partitioned", coll="all_to_all",
                      coll_bytes=coll_all_to_all_bytes(staged, n),
                      bucket_cap=bucket_cap)
            # shuffle scratch counts toward the ledger high-water mark:
            # the staged buckets live only inside this executed program,
            # but their bytes are real device memory at peak
            default_ledger().note_transient(
                ("shuffle_buckets", node.id), staged * n,
                kind="shuffle_buckets")
            gathered = left.with_cols(
                {k: v[lidx] for k, v in left.cols.items()})
            cols = _merge_join_cols(gathered, right, a["right_on"], ridx)
            return BoundedRel(cols, valid, count,
                              ovf | left.overflow | right.overflow)
    return _step_bounded_join(args[0], args[1], node.attrs, ctx)


@REL_ENGINE.impl("rel_group_agg_col")
def _i_rel_group(ctx, args, node):
    a = node.attrs
    mesh = getattr(ctx, "mesh", None)
    rel = as_bounded(args[0])
    if a.get("dist") == "row" and _shardable(mesh, rel.capacity):
        # shard-local segment reduce + psum/pmax (cross-shard float sums
        # re-associate: allclose to the dense aggregate, not bitwise)
        key = rel.cols[a["key"]]
        g = int(a["num_groups"])
        _annotate(ctx, dist="row", coll="psum",
                  coll_bytes=coll_psum_bytes(
                      (len(a["aggs"]) + 1) * g * 4, data_axis_size(mesh)))
        cols = {a["key"]: jnp.arange(g, dtype=jnp.int32)}
        for out_name, fn, col in a["aggs"]:
            vals = None if fn == "count" else rel.cols[col]
            r = sharded_group_agg(vals, key, g, rel.valid, fn, mesh)
            if fn == "max":
                r, _valid = r
            cols[out_name] = r
        count = sharded_group_agg(None, key, g, rel.valid, "count", mesh)
        return BoundedRel(cols, count > 0, None, rel.overflow)
    return _step_rel_group_agg(args[0], node.attrs, ctx)


@REL_ENGINE.impl("compact_prefix_col")
def _i_compact(ctx, args, node):
    return _step_compact(args[0], node.attrs, ctx)


@_PALLAS.impl("compact_prefix_pallas")
def _i_compact_pallas(ctx, args, node):
    return _step_compact_pallas(args[0], node.attrs, ctx)


@REL_ENGINE.impl("rel_fused_col")
def _i_rel_fused(ctx, args, node):
    return _run_chain(args, node.attrs["chain"], ctx)


@_PALLAS.impl("rel_fused_agg_pallas")
def _i_rel_fused_agg(ctx, args, node):
    """Fused chain whose terminal group-by runs the masked segment-
    aggregate Pallas kernel (sum/count/mean; gated by the pattern set)."""
    chain = node.attrs["chain"]
    rel = as_bounded(_run_chain(args, chain, ctx, stop_before_last=True))
    attrs = chain[-1][1]
    key = rel.cols[attrs["key"]]
    g = int(attrs["num_groups"])
    mw = rel.valid.astype(jnp.float32)
    cols = {attrs["key"]: jnp.arange(g, dtype=jnp.int32)}
    count = None
    for out_name, fn, col in attrs["aggs"]:
        vals = mw if fn == "count" else rel.cols[col]
        s, c = masked_segment_agg_pallas(vals, key, mw, num_groups=g)
        count = c
        cols[out_name] = (c if fn == "count"
                          else s if fn == "sum"
                          else s / jnp.maximum(c, 1.0))
    if count is None:
        count, _ = masked_segment_agg_pallas(mw, key, mw, num_groups=g)
    return BoundedRel(cols, count > 0, None, rel.overflow)


@REL_ENGINE.impl("col_tensor_rel")
def _i_col_tensor(ctx, args, node):
    rel = as_bounded(args[0])
    v = rel.cols[node.attrs["col"]].astype(node.attrs.get("dtype", "float32"))
    return jnp.where(rel.valid, v, jnp.zeros_like(v))


@REL_ENGINE.impl("sel_mask_rel")
def _i_sel_mask(ctx, args, node):
    """Selection-mask export: scatter the relation's validity over an
    entity domain (``mask[v] = any selected row with col == v``) — the
    boolean predicate pushdown hands across the engine boundary."""
    rel = as_bounded(args[0])
    col = rel.cols[node.attrs["col"]]
    size = int(node.attrs["size"])
    m = rel.valid & (col >= 0) & (col < size)
    idx = jnp.clip(col, 0, size - 1)
    out = jnp.zeros((size,), jnp.bool_).at[idx].max(m)
    if ctx.aux.get("count_sink") is not None:
        _record_count(ctx, sel_mask_site(node.attrs),
                      jnp.sum(out.astype(jnp.int32)), size)
    return out


# --------------------------------------------------------------------------
# graph engine (CSR fallback) + Pallas frontier kernels
# --------------------------------------------------------------------------


@GRAPH_ENGINE.impl("graph_expand_csr")
def _i_expand_csr(ctx, args, node):
    g, mesh = args[0], getattr(ctx, "mesh", None)
    if (node.attrs.get("dist") == "block" and "blk_src" in g
            and _shardable(mesh, g["indptr"].shape[0] - 1,
                           g["blk_src"].shape[0])):
        hops = int(node.attrs.get("hops", 1))
        nodes_b = (g["indptr"].shape[0] - 1) * 4
        _annotate(ctx, dist="block", coll="all_gather",
                  coll_bytes=hops * coll_allgather_bytes(
                      nodes_b, data_axis_size(mesh)))
        return sharded_expand(g, args[1], hops, mesh)
    return expand_frontier(args[0], args[1],
                           hops=int(node.attrs.get("hops", 1)))


@GRAPH_ENGINE.impl("graph_expand_skip")
def _i_expand_skip(ctx, args, node):
    return expand_frontier_blockskip(args[0], args[1],
                                     hops=int(node.attrs.get("hops", 1)))


@_PALLAS.impl("graph_expand_pallas")
def _i_expand_pallas(ctx, args, node):
    return expand_frontier(args[0], args[1],
                           hops=int(node.attrs.get("hops", 1)),
                           use_pallas=True)


@GRAPH_ENGINE.impl("graph_pagerank_csr")
def _i_pagerank_csr(ctx, args, node):
    g, mesh = args[0], getattr(ctx, "mesh", None)
    if (node.attrs.get("dist") == "block" and "blk_src" in g
            and _shardable(mesh, g["indptr"].shape[0] - 1,
                           g["blk_src"].shape[0])):
        iters = int(node.attrs.get("iters", 10))
        nodes_b = (g["indptr"].shape[0] - 1) * 4
        _annotate(ctx, dist="block", coll="all_gather",
                  coll_bytes=iters * coll_allgather_bytes(
                      nodes_b, data_axis_size(mesh)))
        return sharded_pagerank(
            g, iters, float(node.attrs.get("damping", 0.85)),
            args[1] if len(args) > 1 else None, mesh)
    return pagerank(args[0], iters=int(node.attrs.get("iters", 10)),
                    damping=float(node.attrs.get("damping", 0.85)),
                    personalization=args[1] if len(args) > 1 else None)


@GRAPH_ENGINE.impl("graph_pagerank_skip")
def _i_pagerank_skip(ctx, args, node):
    """Personalization-sparsity pushdown: iteration 0's SpMV block-skips on
    the pushed mask's support; bitwise-identical to the dense iteration."""
    return pagerank(args[0], iters=int(node.attrs.get("iters", 10)),
                    damping=float(node.attrs.get("damping", 0.85)),
                    personalization=args[1] if len(args) > 1 else None,
                    skip_first=True)


@_PALLAS.impl("graph_pagerank_pallas")
def _i_pagerank_pallas(ctx, args, node):
    return pagerank(args[0], iters=int(node.attrs.get("iters", 10)),
                    damping=float(node.attrs.get("damping", 0.85)),
                    personalization=args[1] if len(args) > 1 else None,
                    use_pallas=True)


@GRAPH_ENGINE.impl("graph_tricount_csr")
def _i_tricount(ctx, args, node):
    return triangle_count(args[0])


# --------------------------------------------------------------------------
# text engine
# --------------------------------------------------------------------------


def _note_scoring(ctx, corpus, query):
    """EXPLAIN ANALYZE attribution of a dense TF-IDF scoring: the span's
    ``layout`` is the postings layout the corpus holds (``ell`` or
    ``coo``), and ``ell_select``, deferred like a BoundedRel count, is 1
    where the scoring took the ELL select path and 0 where it took the COO
    gather + scatter.  A no-op when tracing is off."""
    tr = None if ctx is None else getattr(ctx, "tracer", None)
    if tr is None or not tr.enabled:
        return
    tr.annotate(layout="ell" if "ell_term" in corpus else "coo")
    tr.defer("ell_select",
             ell_select_applies(corpus, query).astype(jnp.int32))


def _topk_rel(ids, scores, valid):
    """Top-k results are a BoundedRel by construction: the valid slots form
    a prefix, so the traced count is the true result size (what the old
    ``valid=False`` overflow-slot convention encoded implicitly)."""
    return BoundedRel({"doc": ids, "score": scores}, valid)


@TEXT_ENGINE.impl("text_topk_inv")
def _i_text_topk(ctx, args, node):
    k = int(node.attrs["k"])
    if len(args) == 3:
        # pushed candidate-doc mask, dense realization: score the whole
        # corpus, then mask + top-k (the bitwise reference the skipping
        # candidates must reproduce)
        _note_scoring(ctx, args[0], args[1])
        return _topk_rel(*tfidf_topk_masked(args[0], args[1], args[2], k))
    c, mesh = args[0], getattr(ctx, "mesh", None)
    if (node.attrs.get("dist") == "doc" and "blk_doc_local" in c
            and _shardable(mesh, c["doc_len"].shape[0],
                           c["blk_doc_local"].shape[0])):
        # shard-local score + local top-k, then a fixed-capacity candidate
        # merge (bitwise = the dense top-k, incl. tie-breaking)
        n = data_axis_size(mesh)
        _annotate(ctx, dist="doc", coll="all_gather",
                  coll_bytes=coll_allgather_bytes(n * k * 8, n))
        return _topk_rel(*sharded_tfidf_topk(c, args[1], k, mesh))
    _note_scoring(ctx, c, args[1])
    return _topk_rel(*tfidf_topk(c, args[1], k))


@TEXT_ENGINE.impl("text_topk_skip_inv")
def _i_text_topk_skip(ctx, args, node):
    return _topk_rel(*tfidf_topk_blockskip(args[0], args[1], args[2],
                                           int(node.attrs["k"])))


@_PALLAS.impl("text_topk_masked_pallas")
def _i_text_topk_pallas(ctx, args, node):
    """Masked TF-IDF scoring through the one-hot-matmul superkernel: the
    per-posting gathers run in XLA, the masked fused reduce in Pallas."""
    corpus, query, doc_mask = args
    w = query.astype(jnp.float32) * corpus["idf"]
    doc_ids = corpus["doc_ids"]
    scores = masked_tfidf_pallas(
        doc_ids, w[corpus["term_ids"]], corpus["tf"],
        corpus["doc_len"][doc_ids], doc_mask[doc_ids],
        n_docs=int(corpus["doc_len"].shape[0]))
    return _topk_rel(*masked_topk(scores, doc_mask, int(node.attrs["k"])))


@TEXT_ENGINE.impl("text_scores_inv")
def _i_text_scores(ctx, args, node):
    _note_scoring(ctx, args[0], args[1])
    return tfidf_scores(args[0], args[1])


@_XLA.impl("masked_topk_xla")
def _i_masked_topk(ctx, args, node):
    return _topk_rel(*masked_topk(args[0], args[1],
                                  int(node.attrs["k"])))


# --------------------------------------------------------------------------
# cross-engine transfer
# --------------------------------------------------------------------------


@_XLA.impl("xfer_pin")
def _i_xfer_pin(ctx, args, node):
    # AWESOME's in-memory placement: the value stays device-resident; the
    # receiving engine reads it in place (a no-op at run time — the win is
    # exactly that nothing happens here)
    return args[0]


def _host_roundtrip(v):
    return jax.tree.map(lambda a: np.array(a, copy=True), v)


@_XLA.impl("xfer_local", "xfer_repartition")
def _i_xfer_local(ctx, args, node):
    # layout-compatible handoff (and the repartition placement: the actual
    # all-to-all executes *fused inside* the partitioned join's shard_map —
    # this node is where the planner prices that traffic)
    return args[0]


@_XLA.impl("xfer_replicate")
def _i_xfer_replicate(ctx, args, node):
    # all-gather a data-partitioned value for dense consumers: realized as
    # a replicated sharding constraint on the mesh (GSPMD inserts the
    # gather); identity off-mesh
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return args[0]
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.tree.map(lambda a: jax.lax.with_sharding_constraint(a, rep),
                        args[0])


@_XLA.impl("xfer_spill")
def _i_xfer_spill(ctx, args, node):
    # per-op materialization: the value round-trips device -> host -> device
    # (what a naive federated mediator does between every engine call).
    # pure_callback keeps this expressible under jit while still forcing
    # the host copy at every execution.  BoundedRel is a registered pytree,
    # so relations spill column-wise like any other plan value.
    x = args[0]
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)), x)
    return jax.pure_callback(_host_roundtrip, shapes, x)
