"""Bounded relations: the BoundedRel runtime representation, non-unique
hash joins, compaction placement, incremental appends + plan-cache
invalidation, selectivity feedback, and the first-iteration PageRank
pushdown."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.adil import Analysis
from repro.core.feedback import SelectivityFeedback, filter_site
from repro.core.ir import (SystemCatalog, TableT, TensorT, ValidationError,
                           standard_catalog)
from repro.core.plan_cache import PlanCache
from repro.core.rewrite import (DEFAULT_PIPELINE, UNCOMPACTED_PIPELINE,
                                UNPUSHED_PIPELINE)
from repro.stores import (BoundedRel, ColumnStore, GraphStore, TextStore,
                          as_bounded, compact_rel, store_engines)
from repro.stores import ref as R
from repro.stores.column_store import hash_join_nonunique
from repro.stores.graph_store import pagerank
from repro.stores.masked_kernels import (compact_prefix_pallas,
                                         join_probe_pallas)
from repro.stores.runtime import _step_compact, _step_compact_pallas

CAT = standard_catalog()
SYS = SystemCatalog()
NOFUSE_PIPELINE = tuple(p for p in DEFAULT_PIPELINE if p != "fuse_store_ops")


def _has_compact(fn) -> bool:
    """Whether the planned function compacts anywhere — as a standalone
    physical node or as a step inside a fused rel chain."""
    for n in fn.concrete.topo():
        if "compact" in n.impl:
            return True
        for op, *_ in n.attrs.get("chain", ()):
            if op == "compact":
                return True
    return False


# --------------------------------------------------------------------------
# the BoundedRel representation
# --------------------------------------------------------------------------

def test_payload_is_bounded_rel_with_count():
    cs = ColumnStore({"id": np.arange(5, dtype=np.int32),
                      "v": np.ones(5, np.float32)}, capacity=8)
    rel = cs.payload()
    assert isinstance(rel, BoundedRel)
    assert rel.capacity == 8 and int(rel.count) == 5
    assert not bool(rel.overflow)
    # dict-like compat: columns + "_mask" view over validity
    assert set(rel) == {"id", "v", "_mask"}
    np.testing.assert_array_equal(np.asarray(rel["_mask"]),
                                  np.arange(8) < 5)
    # capacity headroom surfaces as the type's expected count
    assert cs.type == TableT((("id", "int32"), ("v", "float32")), 8, 5)
    with pytest.raises(ValidationError):
        ColumnStore({"x": np.arange(4)}, capacity=2)   # capacity < rows


def test_bounded_rel_is_a_pytree():
    rel = ColumnStore({"a": np.arange(6, dtype=np.int32)}).payload()
    doubled = jax.jit(lambda r: jax.tree.map(lambda x: x * 2, r))(rel)
    assert isinstance(doubled, BoundedRel)
    np.testing.assert_array_equal(np.asarray(doubled.cols["a"]),
                                  np.arange(6) * 2)


def test_narrowed_recomputes_count():
    rel = ColumnStore({"a": np.arange(10, dtype=np.int32)}).payload()
    narrowed = rel.narrowed(rel.cols["a"] < 3)
    assert int(narrowed.count) == 3 and narrowed.capacity == 10


# --------------------------------------------------------------------------
# non-unique hash join (capacity-bounded, overflow-flagged)
# --------------------------------------------------------------------------

def test_hash_join_nonunique_matches_reference(rng):
    for trial in range(5):
        nl, nr = rng.randint(1, 60), rng.randint(1, 40)
        lk = rng.randint(-5, 10, nl)
        lm = rng.rand(nl) > 0.3
        rk = rng.randint(-5, 10, nr)
        rm = rng.rand(nr) > 0.2
        for cap in (4, 37, 500):
            gl, gr_, gv, gc, go = [np.asarray(x) for x in hash_join_nonunique(
                jnp.asarray(lk), jnp.asarray(lm), jnp.asarray(rk),
                jnp.asarray(rm), cap)]
            wl, wr, wv, wc, wo = R.bounded_join_ref(lk, lm, rk, rm, cap)
            np.testing.assert_array_equal(gv, wv)
            np.testing.assert_array_equal(gl[wv], wl[wv])
            np.testing.assert_array_equal(gr_[wv], wr[wv])
            assert int(gc) == wc and bool(go) == wo


def test_hash_join_nonunique_empty_sides():
    z = hash_join_nonunique(jnp.asarray([1, 2]), jnp.asarray([True, True]),
                            jnp.zeros((0,), jnp.int32),
                            jnp.zeros((0,), jnp.bool_), 4)
    assert int(z[3]) == 0 and not bool(z[4])
    assert not bool(np.asarray(z[2]).any())


def test_bounded_join_through_planner_matches_numpy(rng):
    nodes, rows = 16, 120
    dims = ColumnStore({"tag": np.arange(nodes, dtype=np.int32),
                        "w": rng.rand(nodes).astype(np.float32)})
    facts = ColumnStore({"tag": rng.randint(0, nodes, rows).astype(np.int32),
                         "v": rng.rand(rows).astype(np.float32)})
    with Analysis("bj", CAT) as a:
        dm = a.bind("dims", dims)
        fc = a.bind("facts", facts)
        # dims probe x facts build: non-unique build keys, one output row
        # per (dim, matching fact) pair
        bj = a.op("bounded_join", dm, fc, left_on="tag", right_on="tag",
                  capacity=rows)
        agg = a.op("rel_group_agg", bj, key="tag", num_groups=nodes,
                   aggs=(("s", "sum", "v"),))
        a.store(a.op("col_tensor", agg, col="s", dim="nodes"))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    out = np.asarray(fn({}, {"dims": dims.payload(),
                             "facts": facts.payload()}))
    want = np.zeros(nodes, np.float32)
    for t, v in zip(facts.column("tag"), facts.column("v")):
        want[t] += v
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_bounded_join_overflow_flag_surfaces(rng):
    nodes, rows = 8, 64
    dims = ColumnStore({"tag": np.arange(nodes, dtype=np.int32)})
    facts = ColumnStore({"tag": rng.randint(0, nodes, rows).astype(np.int32),
                         "v": rng.rand(rows).astype(np.float32)})
    with Analysis("ovf", CAT) as a:
        dm = a.bind("dims", dims)
        fc = a.bind("facts", facts)
        bj = a.op("bounded_join", dm, fc, left_on="tag", right_on="tag",
                  capacity=8)        # 64 matches cannot fit
        a.store(bj)
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    out = fn({}, {"dims": dims.payload(), "facts": facts.payload()})
    assert isinstance(out, BoundedRel)
    assert bool(out.overflow) and int(out.count) == 8
    with pytest.raises(ValidationError):       # capacity must be >= 1
        with Analysis("bad", CAT) as b:
            dm = b.bind("dims", dims)
            fc = b.bind("facts", facts)
            b.store(b.op("bounded_join", dm, fc, left_on="tag",
                         right_on="tag", capacity=0))


# --------------------------------------------------------------------------
# compaction: kernels + planner placement + bitwise identity
# --------------------------------------------------------------------------

def test_compact_rel_matches_reference(rng):
    cs = ColumnStore({"a": np.arange(50, dtype=np.int32),
                      "b": rng.randn(50).astype(np.float32)})
    rel = cs.payload().narrowed(jnp.asarray(np.arange(50) % 7 == 0))
    for cap in (4, 16, 50):
        got = compact_rel(rel, cap)
        cols, valid, count, ovf = R.compact_ref(
            {k: np.asarray(rel.cols[k]) for k in rel.cols},
            np.asarray(rel.valid), cap)
        np.testing.assert_array_equal(np.asarray(got.valid), valid)
        assert int(got.count) == count and bool(got.overflow) == ovf
        for k in cols:
            np.testing.assert_array_equal(np.asarray(got.cols[k])[valid],
                                          cols[k][valid])


def test_compact_pallas_matches_gather(rng):
    cs = ColumnStore({"a": rng.randint(0, 1000, 90).astype(np.int32),
                      "b": rng.randn(90).astype(np.float32)})
    rel = cs.payload().narrowed(jnp.asarray(rng.rand(90) > 0.7))
    for cap in (8, 40):
        xla = _step_compact(rel, {"capacity": cap})
        pls = _step_compact_pallas(rel, {"capacity": cap})
        assert int(xla.count) == int(pls.count)
        v = np.asarray(xla.valid)
        np.testing.assert_array_equal(v, np.asarray(pls.valid))
        for k in ("a", "b"):
            np.testing.assert_array_equal(np.asarray(xla.cols[k])[v],
                                          np.asarray(pls.cols[k])[v])


def _selective_analysis(table, graph, corpus, *, selectivity, k=16):
    rows, nodes = table.rows, graph.n_nodes
    cut = int(rows * (1 - selectivity))
    with Analysis("sel", CAT) as a:
        tw = a.bind("tweets", table)
        gr = a.bind("g", graph)
        cx = a.bind("cx", corpus)
        q = a.input("q", TensorT((corpus.vocab,), "float32", ("vocab",)))
        t = a.op("rel_scan", tw)
        recent = a.op("rel_filter", t, col="ts", cmp="ge", value=cut,
                      selectivity=selectivity)
        m = a.op("sel_mask", recent, col="doc", size=corpus.n_docs)
        sc = a.op("text_scores", cx, q)
        hits = a.op("masked_topk", sc, m, k=k)
        j = a.op("rel_join", recent, hits, left_on="doc", right_on="doc")
        trel = a.op("rel_group_agg", j, key="hashtag", num_groups=nodes,
                    aggs=(("textrel", "sum", "score"),))
        seeds = a.op("rel_group_agg", recent, key="hashtag",
                     num_groups=nodes, aggs=(("seed", "count", None),))
        sv = a.op("col_tensor", seeds, col="seed", dim="nodes")
        pr = a.op("graph_pagerank", gr, sv, iters=3)
        tv = a.op("col_tensor", trel, col="textrel", dim="nodes")
        a.store(a.op("residual_add", pr, tv))
    return a


def _stores(rng, rows=400, nodes=64, vocab=32):
    table = ColumnStore({
        "hashtag": rng.randint(0, nodes, rows).astype(np.int32),
        "doc": np.arange(rows, dtype=np.int32),
        "ts": np.arange(rows, dtype=np.int32),
        "engagement": (rng.rand(rows) * 50).astype(np.float32),
    })
    e = rng.randint(0, nodes, (2, 300))
    graph = GraphStore.from_edges(e[0], e[1], nodes, symmetric=True)
    corpus = TextStore.from_docs(
        [rng.randint(0, vocab, rng.randint(2, 8)) for _ in range(rows)],
        vocab)
    return table, graph, corpus


def _inputs(table, graph, corpus, terms=(1, 2, 3)):
    return {"tweets": table.payload(), "g": graph.payload(),
            "cx": corpus.payload(),
            "q": jnp.asarray(corpus.query_vector(terms))}


def test_choose_compaction_inserts_and_stays_bitwise(rng):
    table, graph, corpus = _stores(rng)
    a = _selective_analysis(table, graph, corpus, selectivity=0.05)
    compacted = a.compile(SYS, engines=store_engines(), cache=False)
    masked = a.compile(SYS, engines=store_engines(), cache=False,
                       rewrite_pipeline=UNCOMPACTED_PIPELINE)
    assert _has_compact(compacted)
    assert not _has_compact(masked)
    ins = _inputs(table, graph, corpus)
    out_c = np.asarray(jax.jit(lambda i: compacted({}, i))(ins))
    out_m = np.asarray(jax.jit(lambda i: masked({}, i))(ins))
    np.testing.assert_array_equal(out_c, out_m)
    # EXPLAIN surfaces the cardinality reasoning
    text = compacted.explain()
    assert "count~" in text and "capacity=" in text


def test_compaction_skips_capacity_sensitive_consumers(rng):
    """A join whose output feeds a capacity-long tensor (col_tensor) must
    not have its probe side compacted: the output tensor's shape would
    change.  The planner detects the transitive capacity-sensitivity and
    leaves the plan alone."""
    table, graph, corpus = _stores(rng)
    with Analysis("shape", CAT) as a:
        tw = a.bind("tweets", table)
        cx = a.bind("cx", corpus)
        q = a.input("q", TensorT((corpus.vocab,), "float32", ("vocab",)))
        t = a.op("rel_scan", tw)
        recent = a.op("rel_filter", t, col="ts", cmp="ge",
                      value=int(table.rows * 0.95), selectivity=0.05)
        hits = a.op("text_topk", cx, q, k=8)
        j = a.op("rel_join", recent, hits, left_on="doc", right_on="doc")
        # capacity-long tensor out of the join: compaction would change
        # this output's shape from (rows,) to (capacity,)
        a.store(a.op("col_tensor", j, col="score"))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    assert not _has_compact(fn)
    uncompacted = a.compile(SYS, engines=store_engines(), cache=False,
                            rewrite_pipeline=UNCOMPACTED_PIPELINE)
    ins = {"tweets": table.payload(), "cx": corpus.payload(),
           "q": jnp.asarray(corpus.query_vector([1, 2]))}
    np.testing.assert_array_equal(np.asarray(fn({}, ins)),
                                  np.asarray(uncompacted({}, ins)))


def test_observed_overflow_backs_compaction_off(rng):
    """A compaction bound sized from a wildly wrong hint drops rows at run
    time; observing the run flags the site and re-planning stops
    compacting it (and the corrected selectivity estimate agrees)."""
    table, graph, corpus = _stores(rng)
    rows = table.rows

    def build():
        with Analysis("ovf", CAT) as a:
            tw = a.bind("tweets", table)
            t = a.op("rel_scan", tw)
            # actual selectivity 50%, hinted 1% -> capacity far too small
            f = a.op("rel_filter", t, col="ts", cmp="ge",
                     value=int(rows * 0.5), selectivity=0.01)
            seeds = a.op("rel_group_agg", f, key="hashtag",
                         num_groups=graph.n_nodes,
                         aggs=(("seed", "count", None),))
            a.store(a.op("col_tensor", seeds, col="seed", dim="nodes"))
        return a

    ins = {"tweets": table.payload()}
    fb = SelectivityFeedback()
    cache = PlanCache()
    fn1 = build().compile(SYS, engines=store_engines(), cache=cache,
                          feedback=fb)
    assert _has_compact(fn1)
    fn1.observe({}, ins, feedback=fb)
    site = filter_site({"col": "ts", "cmp": "ge", "value": int(rows * 0.5)},
                       table.type.col_names(), table.rows)
    assert fb.is_overflowed(site)
    fn2 = build().compile(SYS, engines=store_engines(), cache=cache,
                          feedback=fb)
    assert fn2.plan_id != fn1.plan_id
    assert not _has_compact(fn2)
    # the un-compacted re-plan is correct (the overflowed one was lossy)
    want = np.zeros(graph.n_nodes, np.float32)
    sel_rows = table.column("ts") >= int(rows * 0.5)
    for h in table.column("hashtag")[sel_rows]:
        want[h] += 1.0
    np.testing.assert_allclose(np.asarray(fn2({}, ins)), want)


def test_compile_refreshes_bound_store_types(rng):
    """Re-compiling the *same* Analysis object after an append must plan
    against the store's current statistics, not the bind-time snapshot."""
    st = ColumnStore({"x": np.arange(60, dtype=np.int32)}, capacity=128)
    with Analysis("stale", CAT) as a:
        tw = a.bind("t", st)
        a.store(a.op("rel_scan", tw))
    fn1 = a.compile(SYS, engines=store_engines(), cache=False)
    st.append({"x": np.arange(20, dtype=np.int32)})
    fn2 = a.compile(SYS, engines=store_engines(), cache=False)
    assert a.plan.inputs["t"].expected_count == 80
    out = fn2({}, {"t": st.payload()})
    assert int(out.count) == 80
    assert fn2.plan_id != fn1.plan_id


def test_choose_compaction_requires_confidence(rng):
    """A bare-heuristic filter (no hint, no observation) must not be
    compacted: an underestimated capacity would silently drop rows."""
    table, graph, corpus = _stores(rng)
    rows = table.rows
    with Analysis("noconf", CAT) as a:
        tw = a.bind("tweets", table)
        t = a.op("rel_scan", tw)
        f = a.op("rel_filter", t, col="ts", cmp="eq", value=3)  # no hint
        seeds = a.op("rel_group_agg", f, key="hashtag",
                     num_groups=graph.n_nodes,
                     aggs=(("seed", "count", None),))
        a.store(a.op("col_tensor", seeds, col="seed", dim="nodes"))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    assert not _has_compact(fn)


def test_compaction_edge_selectivities_bitwise(rng):
    table, graph, corpus = _stores(rng, rows=80, nodes=12, vocab=16)
    ins = _inputs(table, graph, corpus)
    for sel in (0.0, 0.05, 0.125):
        a = _selective_analysis(table, graph, corpus, selectivity=sel)
        compacted = a.compile(SYS, engines=store_engines(), cache=False)
        unpushed = a.compile(SYS, engines=store_engines(), cache=False,
                             rewrite_pipeline=UNPUSHED_PIPELINE)
        np.testing.assert_array_equal(np.asarray(compacted({}, ins)),
                                      np.asarray(unpushed({}, ins)))


# --------------------------------------------------------------------------
# incremental appends: version bumps provably invalidate cached plans
# --------------------------------------------------------------------------

def test_column_store_append_within_capacity():
    st = ColumnStore({"x": np.arange(60, dtype=np.int32)}, capacity=128)
    st.append({"x": np.arange(20, dtype=np.int32)})
    assert st.rows == 80 and st.capacity == 128 and st.version == 1
    rel = st.payload()
    assert rel.capacity == 128 and int(rel.count) == 80
    st.append({"x": np.arange(100, dtype=np.int32)})   # beyond capacity
    assert st.rows == 180 and st.capacity == 180 and st.version == 2
    with pytest.raises(ValidationError):               # schema mismatch
        st.append({"y": np.arange(3)})


def test_append_bumps_version_and_invalidates_cache(rng):
    cache = PlanCache()
    st = ColumnStore({"x": rng.randint(0, 4, 60).astype(np.int32)},
                     capacity=128)

    def build():
        with Analysis("inc", CAT) as a:
            tw = a.bind("t", st)
            f = a.op("rel_filter", a.op("rel_scan", tw), col="x", cmp="ge",
                     value=1)
            a.store(a.op("rel_group_agg", f, key="x", num_groups=4,
                         aggs=(("n", "count", None),)))
        return a

    fn1 = build().compile(SYS, engines=store_engines(), cache=cache)
    fn1b = build().compile(SYS, engines=store_engines(), cache=cache)
    assert fn1b.plan_id == fn1.plan_id and cache.hits == 1
    st.append({"x": rng.randint(0, 4, 30).astype(np.int32)})
    assert st.version == 1
    fn2 = build().compile(SYS, engines=store_engines(), cache=cache)
    assert fn2.plan_id != fn1.plan_id          # provably not the stale plan
    assert cache.hits == 1                     # the re-plan was a miss
    # and the recompiled plan sees the appended rows
    out = fn2({}, {"t": st.payload()})
    assert float(np.asarray(out["n"]).sum()) == float(
        (st.column("x") >= 1).sum())


def test_store_versions_alone_change_plan_id(rng):
    """The version vector is identity material in its own right — two
    compiles of the *same* plan under different store versions never share
    a cache entry."""
    st = ColumnStore({"x": np.arange(8, dtype=np.int32)})
    with Analysis("v", CAT) as a:
        tw = a.bind("t", st)
        a.store(a.op("rel_scan", tw))
    fn0 = a.compile(SYS, engines=store_engines(), cache=False,
                    store_versions=(("t", 0),))
    fn1 = a.compile(SYS, engines=store_engines(), cache=False,
                    store_versions=(("t", 1),))
    assert fn0.plan_id != fn1.plan_id


def test_text_store_append_reindexes(rng):
    vocab = 16
    docs1 = [rng.randint(0, vocab, rng.randint(2, 6)) for _ in range(10)]
    docs2 = [rng.randint(0, vocab, rng.randint(2, 6)) for _ in range(7)]
    inc = TextStore.from_docs(docs1, vocab)
    inc.append(docs2)
    full = TextStore.from_docs(docs1 + docs2, vocab)
    assert inc.version == 1 and inc.n_docs == full.n_docs
    np.testing.assert_array_equal(inc.doc_ids, full.doc_ids)
    np.testing.assert_array_equal(inc.term_ids, full.term_ids)
    np.testing.assert_array_equal(inc.tf, full.tf)
    np.testing.assert_array_equal(inc.doc_len, full.doc_len)
    np.testing.assert_allclose(inc.idf, full.idf, rtol=1e-6)


# --------------------------------------------------------------------------
# selectivity feedback: a mis-hinted filter self-corrects after observation
# --------------------------------------------------------------------------

def test_selectivity_feedback_self_corrects(rng):
    table, graph, corpus = _stores(rng)
    rows = table.rows

    def build():
        # actual selectivity ~5%, mis-hinted as 90%
        with Analysis("fb", CAT) as a:
            tw = a.bind("tweets", table)
            cx = a.bind("cx", corpus)
            q = a.input("q", TensorT((corpus.vocab,), "float32", ("vocab",)))
            t = a.op("rel_scan", tw)
            recent = a.op("rel_filter", t, col="ts", cmp="ge",
                          value=int(rows * 0.95), selectivity=0.9)
            m = a.op("sel_mask", recent, col="doc", size=corpus.n_docs)
            sc = a.op("text_scores", cx, q)
            hits = a.op("masked_topk", sc, m, k=16)
            j = a.op("rel_join", recent, hits, left_on="doc",
                     right_on="doc")
            trel = a.op("rel_group_agg", j, key="hashtag",
                        num_groups=graph.n_nodes,
                        aggs=(("textrel", "sum", "score"),))
            a.store(a.op("col_tensor", trel, col="textrel", dim="nodes"))
        return a

    ins = {"tweets": table.payload(), "cx": corpus.payload(),
           "q": jnp.asarray(corpus.query_vector([1, 2, 3]))}
    fb = SelectivityFeedback()
    cache = PlanCache()
    fn1 = build().compile(SYS, engines=store_engines(), cache=cache,
                          feedback=fb)
    impls1 = {n.impl for n in fn1.concrete.topo()}
    # mis-hint (0.9) keeps the dense text plan
    assert "text_topk_inv" in impls1
    assert "text_topk_skip_inv" not in impls1
    out1 = fn1.observe({}, ins, feedback=fb)
    assert len(fb) >= 1
    site = filter_site({"col": "ts", "cmp": "ge",
                        "value": int(rows * 0.95)},
                       table.type.col_names(), table.rows)
    assert fb.lookup(site) == pytest.approx(0.05, abs=0.01)
    fn2 = build().compile(SYS, engines=store_engines(), cache=cache,
                          feedback=fb)
    # new observations are a provable cache miss, and the corrected
    # estimate now clears the skip-candidate gate
    assert fn2.plan_id != fn1.plan_id
    impls2 = {n.impl for n in fn2.concrete.topo()}
    assert "text_topk_skip_inv" in impls2
    np.testing.assert_array_equal(np.asarray(out1),
                                  np.asarray(fn2({}, ins)))


def test_feedback_records_marginal_selectivity(rng):
    """Chained filters: each site must record its *own* survivor fraction
    (what estimate_selectivity multiplies along the lineage), not the
    cumulative count/capacity — a cumulative record would double-discount
    upstream narrowing on re-plan."""
    st = ColumnStore({"x": np.arange(100, dtype=np.int32)})
    with Analysis("marg", CAT) as a:
        tw = a.bind("t", st)
        f1 = a.op("rel_filter", a.op("rel_scan", tw), col="x", cmp="ge",
                  value=50)                      # 50% survive
        f2 = a.op("rel_filter", f1, col="x", cmp="lt", value=75)
        a.store(a.op("rel_group_agg", f2, key="x", num_groups=4,
                     aggs=(("n", "count", None),)))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    fb = SelectivityFeedback()
    fn.observe({}, {"t": st.payload()}, feedback=fb)
    cols = st.type.col_names()
    s1 = fb.lookup(filter_site({"col": "x", "cmp": "ge", "value": 50},
                               cols, st.rows))
    s2 = fb.lookup(filter_site({"col": "x", "cmp": "lt", "value": 75},
                               cols, st.rows))
    assert s1 == pytest.approx(0.5)
    # of the 50 survivors of f1, 25 pass f2: marginal 0.5, cumulative 0.25
    assert s2 == pytest.approx(0.5)


def test_compact_fuses_into_rel_chains(rng):
    """Inserting a compaction must not split the fused superkernel chain:
    scan->filter->compact->join->group_agg stays one rel_fused call."""
    table, graph, corpus = _stores(rng)
    a = _selective_analysis(table, graph, corpus, selectivity=0.05)
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    chains = [[s[0] for s in n.attrs["chain"]]
              for n in fn.logical.topo() if n.op == "rel_fused"]
    assert any("compact" in c for c in chains), chains


# --------------------------------------------------------------------------
# PageRank first-iteration pushdown
# --------------------------------------------------------------------------

def test_pagerank_skip_first_bitwise(rng):
    n = 64
    g = GraphStore.from_edges(rng.randint(0, n, 300),
                              rng.randint(0, n, 300), n, symmetric=True)
    gp = g.payload()
    for density in (0.0, 0.05, 1.0):
        p = np.where(rng.rand(n) < density, rng.rand(n), 0.0) \
            .astype(np.float32)
        dense = pagerank(gp, iters=5, personalization=jnp.asarray(p))
        skip = pagerank(gp, iters=5, personalization=jnp.asarray(p),
                        skip_first=True, block=64)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(skip))


def test_pagerank_skip_candidate_chosen_when_sparse(rng):
    table, graph, corpus = _stores(rng)
    a = _selective_analysis(table, graph, corpus, selectivity=0.02)
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    chosen = {r["pattern"]: r["chosen"] for r in fn.report}
    assert chosen["graph_pagerank_op"] == "pagerank_skip"
    unpushed = a.compile(SYS, engines=store_engines(), cache=False,
                         rewrite_pipeline=UNPUSHED_PIPELINE)
    ins = _inputs(table, graph, corpus)
    np.testing.assert_array_equal(np.asarray(fn({}, ins)),
                                  np.asarray(unpushed({}, ins)))


def test_pagerank_dense_personalization_keeps_csr(rng):
    table, graph, corpus = _stores(rng)
    a = _selective_analysis(table, graph, corpus, selectivity=1.0)
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    chosen = {r["pattern"]: r["chosen"] for r in fn.report}
    assert chosen.get("graph_pagerank_op", "pagerank_csr") == "pagerank_csr"


# --------------------------------------------------------------------------
# masked hash-join probe kernel
# --------------------------------------------------------------------------

def test_join_probe_pallas_matches_reference(rng):
    for trial in range(3):
        nr = rng.randint(1, 40)
        rk = rng.permutation(100)[:nr].astype(np.int32)    # unique keys
        rv = rng.rand(nr) > 0.3
        lk = rng.randint(0, 100, rng.randint(1, 90)).astype(np.int32)
        gi, gm = join_probe_pallas(jnp.asarray(lk), jnp.asarray(rk),
                                   jnp.asarray(rv), interpret=True)
        wi, wm = R.join_probe_ref(lk, rk, rv)
        np.testing.assert_array_equal(np.asarray(gm), wm)
        np.testing.assert_array_equal(np.asarray(gi), wi)


def test_join_probe_candidate_gated_by_build_expected(rng):
    table, graph, corpus = _stores(rng)
    a = _selective_analysis(table, graph, corpus, selectivity=0.05)
    # keep the join un-fused so the rel_join pattern is visible
    fn = a.compile(SYS, engines=store_engines(pallas=True), cache=False,
                   rewrite_pipeline=NOFUSE_PIPELINE)
    joins = [r for r in fn.report if r["pattern"] == "rel_join_op"]
    assert joins, "rel_join should be pattern-matched"
    # build side is the k=16 top-k relation: expected count clears the gate
    assert "join_probe_kernel" in joins[0]["costs"]
    rel_only = a.compile(SYS, engines=store_engines(), cache=False,
                         rewrite_pipeline=NOFUSE_PIPELINE)
    ins = _inputs(table, graph, corpus)
    # enabling pallas swaps several candidates (masked scoring, pagerank),
    # which are allclose-not-bitwise by design; the probe kernel itself is
    # bitwise vs its reference (test above)
    np.testing.assert_allclose(np.asarray(fn({}, ins)),
                               np.asarray(rel_only({}, ins)),
                               rtol=1e-5, atol=1e-7)


# --------------------------------------------------------------------------
# unified validity conventions (text top-k, group-agg max)
# --------------------------------------------------------------------------

def test_text_topk_emits_bounded_rel(rng):
    corpus = TextStore.from_docs([[0, 1]] * 20, vocab=4)
    with Analysis("tk", CAT) as a:
        cx = a.bind("cx", corpus)
        q = a.input("q", TensorT((4,), "float32", ("vocab",)))
        a.store(a.op("text_topk", cx, q, k=8))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    out = fn({}, {"cx": corpus.payload(),
                  "q": jnp.asarray(corpus.query_vector([0, 1]))})
    assert isinstance(out, BoundedRel)
    assert int(out.count) == 8 and not bool(out.overflow)
    assert set(out) == {"doc", "score", "_mask"}   # dict-compat surface


def test_group_agg_max_empty_groups_are_invalid_rows(rng):
    table = ColumnStore({"g": np.asarray([0, 0, 2], np.int32),
                         "v": np.asarray([0.0, -1.0, 5.0], np.float32)})
    with Analysis("gm", CAT) as a:
        tw = a.bind("t", table)
        a.store(a.op("rel_group_agg", tw, key="g", num_groups=3,
                     aggs=(("m", "max", "v"),)))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    out = fn({}, {"t": table.payload()})
    # group 1 has no rows: its output row is invalid, not "max == 0.0"
    np.testing.assert_array_equal(np.asarray(out.valid),
                                  [True, False, True])
    np.testing.assert_array_equal(np.asarray(out["m"]), [0.0, 0.0, 5.0])
    assert int(out.count) == 2
