"""Tri-store subsystem: store containers, relational/graph/text kernels,
cross-engine xfer placement, and end-to-end tri-model planning."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.adil import Analysis
from repro.core.adil_parser import parse_adil
from repro.core.engines import engine_names
from repro.core.ir import (CorpusT, GraphT, SystemCatalog, TableT, TensorT,
                           ValidationError, plan_id, standard_catalog)
from repro.core.rewrite import (DEFAULT_PIPELINE, place_xfers,
                                place_xfers_naive, rewrite)
from repro.stores import (ColumnStore, GraphStore, TextStore, store_engines)
from repro.stores import ref as R
from repro.stores.column_store import filter_mask, group_agg, hash_join
from repro.stores.graph_kernels import scatter_add_pallas
from repro.stores.graph_store import (accumulator_lanes, expand_frontier,
                                      expand_frontier_blockskip,
                                      lane_segment_sum, pagerank,
                                      triangle_count)
from repro.stores.text_store import (QUERY_TERMS, _tfidf_scores_coo,
                                     _tfidf_scores_ell, ell_select_applies,
                                     tfidf_scores, tfidf_topk)

CAT = standard_catalog()
SYS = SystemCatalog()


# --------------------------------------------------------------------------
# store containers
# --------------------------------------------------------------------------

def test_column_store_type_and_payload():
    cs = ColumnStore({"id": np.arange(5, dtype=np.int32),
                      "v": np.ones(5, np.float32)})
    assert cs.type == TableT((("id", "int32"), ("v", "float32")), 5)
    p = cs.payload()
    assert set(p) == {"id", "v", "_mask"}
    assert bool(p["_mask"].all())
    with pytest.raises(ValidationError):
        ColumnStore({"a": np.zeros(3), "b": np.zeros(4)})


def test_column_store_canonicalizes_64bit_columns():
    """64-bit host columns narrow to the 32-bit device representation
    explicitly: the declared type matches what actually executes, and keys
    that would wrap are refused instead of silently corrupted."""
    cs = ColumnStore({"id": np.arange(4),            # int64 on Linux
                      "v": np.ones(4, np.float64)})
    assert cs.type == TableT((("id", "int32"), ("v", "float32")), 4)
    assert str(cs.payload()["id"].dtype) == "int32"
    with pytest.raises(ValidationError):             # snowflake-scale ids
        ColumnStore({"id": np.array([2 ** 40, 1])})


def test_graph_store_csr_and_type():
    #  0 -> 1, 0 -> 2, 1 -> 2  (made symmetric)
    g = GraphStore.from_edges([0, 0, 1], [1, 2, 2], 3, symmetric=True)
    assert g.type == GraphT(3, 6)
    assert list(g.indptr) == [0, 2, 4, 6]
    assert sorted(zip(g.src.tolist(), g.indices.tolist())) == [
        (0, 1), (0, 2), (1, 0), (1, 2), (2, 0), (2, 1)]
    with pytest.raises(ValidationError):
        GraphStore.from_edges([0], [5], 3)


def test_text_store_index_and_type():
    tx = TextStore.from_docs([[0, 0, 1], [1, 2]], vocab=4)
    assert tx.type == CorpusT(2, 4, 4)     # (d0,t0) (d0,t1) (d1,t1) (d1,t2)
    assert tx.n_postings == 4
    # term 1 appears in both docs -> lowest idf among used terms
    assert tx.idf[1] < tx.idf[0] and tx.idf[1] < tx.idf[2]


# --------------------------------------------------------------------------
# kernels vs references (deterministic spot checks; property tests live in
# test_stores_properties.py)
# --------------------------------------------------------------------------

def test_hash_join_matches_reference(rng):
    lkeys = rng.randint(0, 50, 64)
    rkeys = rng.permutation(50)[:32]
    idx, matched = hash_join(jnp.asarray(lkeys), jnp.asarray(rkeys))
    ridx, rmatched = R.hash_join_ref(lkeys, rkeys)
    np.testing.assert_array_equal(np.asarray(matched), rmatched)
    np.testing.assert_array_equal(np.asarray(idx)[rmatched], ridx[rmatched])


def _group_agg_loop(values, keys, num_groups, mask, fn):
    out = np.zeros(num_groups, np.float64)
    valid = np.zeros(num_groups, bool)
    for g in range(num_groups):
        sel = (keys == g) & mask
        if fn == "count":
            out[g] = sel.sum()
            continue
        v = np.asarray(values, np.float64)[sel]
        if v.size == 0:
            continue
        out[g] = {"sum": v.sum, "mean": v.mean, "max": v.max}[fn]()
        valid[g] = fn == "max" and np.isfinite(out[g])
    if fn == "max":
        return np.where(valid, out, 0.0).astype(np.float32), valid
    return out.astype(np.float32)


def _tfidf_loop(doc_ids, term_ids, tf, doc_len, idf, query):
    scores = np.zeros(len(doc_len), np.float64)
    q = np.asarray(query, np.float64)
    for d, t, f in zip(doc_ids, term_ids, np.asarray(tf, np.float64)):
        scores[d] += q[t] * idf[t] * f / doc_len[d]
    return scores


def _hash_join_loop(lkeys, rkeys):
    lut = {int(k): i for i, k in enumerate(rkeys)}
    idx = np.zeros(lkeys.shape, np.int64)
    matched = np.zeros(lkeys.shape, bool)
    for i, k in enumerate(lkeys):
        j = lut.get(int(k))
        if j is not None:
            idx[i], matched[i] = j, True
    return idx, matched


@pytest.mark.parametrize("case", ["count", "sum", "mean", "max", "tfidf",
                                  "join", "join_repeated_keys"])
def test_vectorized_refs_match_per_row_loops(rng, case):
    """The references run at deployment size vectorized; the per-row loops
    they replaced stay here as their reference.  Counts, joins and TF-IDF
    (same float64 products, same summation order) match exactly; sums and
    means re-associate in float64, so float32 outputs agree to 1e-6."""
    if case == "tfidf":
        tx = TextStore.from_docs(
            [rng.randint(0, 40, rng.randint(1, 9)) for _ in range(60)], 40)
        q = rng.rand(40)
        got = R.tfidf_scores_ref(tx.doc_ids, tx.term_ids, tx.tf,
                                 tx.doc_len, tx.idf, q)
        want = _tfidf_loop(tx.doc_ids, tx.term_ids, tx.tf, tx.doc_len,
                           tx.idf, q)
        np.testing.assert_array_equal(got, want)
    elif case.startswith("join"):
        rkeys = (rng.randint(0, 30, 40) if case == "join_repeated_keys"
                 else rng.permutation(50)[:32])
        lkeys = rng.randint(-5, 55, 80)
        for got, want in zip(R.hash_join_ref(lkeys, rkeys),
                             _hash_join_loop(lkeys, rkeys)):
            np.testing.assert_array_equal(got, want)
    else:
        keys = rng.randint(-1, 12, 200)
        vals = rng.randn(200).astype(np.float32)
        mask = rng.rand(200) > 0.3
        got = R.group_agg_ref(None if case == "count" else vals, keys, 10,
                              mask, case)
        want = _group_agg_loop(vals, keys, 10, mask, case)
        if case == "max":
            (got, gvalid), (want, wvalid) = got, want
            np.testing.assert_array_equal(gvalid, wvalid)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_group_agg_matches_reference(rng):
    keys = rng.randint(0, 8, 100).astype(np.int32)
    vals = rng.randn(100).astype(np.float32)
    mask = rng.rand(100) > 0.3
    for fn in ("sum", "count", "mean", "max"):
        got = group_agg(jnp.asarray(vals), jnp.asarray(keys), 8,
                        jnp.asarray(mask), fn)
        want = R.group_agg_ref(vals, keys, 8, mask, fn)
        if fn == "max":
            (got, gvalid), (want, wvalid) = got, want
            np.testing.assert_array_equal(np.asarray(gvalid), wvalid)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)


def test_group_agg_max_distinguishes_empty_from_zero():
    """An all-masked group is *invalid*, not a max of 0.0 — and a group
    whose true max is 0.0 is valid (the regression this guards)."""
    vals = jnp.asarray([0.0, -1.0, 5.0], jnp.float32)
    keys = jnp.asarray([0, 0, 1], jnp.int32)
    mask = jnp.asarray([True, True, False])
    got, valid = group_agg(vals, keys, 2, mask, "max")
    np.testing.assert_array_equal(np.asarray(valid), [True, False])
    np.testing.assert_array_equal(np.asarray(got), [0.0, 0.0])


def test_graph_ops_match_reference(rng):
    n, e = 32, 200
    src, dst = rng.randint(0, n, e), rng.randint(0, n, e)
    g = GraphStore.from_edges(src, dst, n, symmetric=True)
    gp = g.payload()
    x = rng.rand(n).astype(np.float32)
    got = expand_frontier(gp, jnp.asarray(x), hops=2)
    want = R.expand_ref(g.src, g.indices, g.weights, n, x, hops=2)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)

    got_pr = pagerank(gp, iters=6, personalization=jnp.asarray(x))
    want_pr = R.pagerank_ref(g.src, g.indices, g.weights, n, iters=6,
                             personalization=x)
    np.testing.assert_allclose(np.asarray(got_pr), want_pr, rtol=1e-4)

    got_t = float(triangle_count(gp))
    assert got_t == pytest.approx(R.triangle_count_ref(g.src, g.indices, n))


@pytest.mark.parametrize("lanes", [1, 8])
def test_lane_segment_sum_matches_float64(rng, lanes):
    n, e = 50, 4000
    seg = rng.randint(-1, n + 1, e).astype(np.int32)   # ids -1, n dropped
    vals = rng.randn(e).astype(np.float32)
    got = lane_segment_sum(jnp.asarray(vals), jnp.asarray(seg), n, lanes)
    keep = (seg >= 0) & (seg < n)
    want = np.bincount(seg[keep], vals[keep].astype(np.float64), n)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)
    if lanes == 1:
        plain = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(seg),
                                    num_segments=n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))


def _hub_graph(rng):
    # half of 2^17 random edges land on node 0: a hub, as a Zipf-popular
    # hashtag is in the co-mention graph
    n, e = 2048, 1 << 17
    src = rng.randint(1, n, e)
    dst = np.where(rng.rand(e) < 0.5, 0, rng.randint(1, n, e))
    return GraphStore.from_edges(src, dst, n, symmetric=True)


def test_hub_sums_stay_near_float64(rng):
    """A hub's in-edges spread over accumulator lanes: one float32
    accumulator loses 3.3e-5 of this expansion, lanes keep it at ~2e-6."""
    g = _hub_graph(rng)
    n = g.n_nodes
    assert accumulator_lanes(g.n_edges, n) == 8
    assert accumulator_lanes(1 << 15, n) == 1
    x = (rng.rand(n) ** 4).astype(np.float32)
    gp = g.payload()
    fr = R.expand_ref(g.src, g.indices, g.weights, n, x, hops=2)
    got = np.asarray(expand_frontier(gp, jnp.asarray(x), hops=2))
    assert np.abs(got - fr).max() / fr.max() < 5e-6
    pr = R.pagerank_ref(g.src, g.indices, g.weights, n, iters=8,
                        personalization=fr)
    got_pr = np.asarray(pagerank(gp, iters=8, personalization=jnp.asarray(got)))
    assert np.abs(got_pr - pr).max() / pr.max() < 5e-6


def test_blockskip_keeps_lanes_bitwise(rng):
    g = _hub_graph(rng)
    gp = g.payload()
    fr = np.where(rng.rand(g.n_nodes) < 0.05, rng.rand(g.n_nodes), 0.0)
    fr = jnp.asarray(fr.astype(np.float32))
    got = expand_frontier_blockskip(gp, fr, hops=2, block=1000)
    want = expand_frontier(gp, fr, hops=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = pagerank(gp, iters=3, personalization=fr, skip_first=True)
    want = pagerank(gp, iters=3, personalization=fr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_add_pallas_matches_segment_sum(rng):
    n, e = 100, 500
    dst = rng.randint(0, n, e).astype(np.int32)
    vals = rng.randn(e).astype(np.float32)
    got = scatter_add_pallas(jnp.asarray(vals), jnp.asarray(dst),
                             num_nodes=n, interpret=True)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(dst),
                               num_segments=n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_empty_edge_and_build_sides():
    """Degenerate stores must degrade, not crash: a zero-edge graph scatters
    to zeros on both backends, and an empty join build side leaves every
    probe row unmatched."""
    z = scatter_add_pallas(jnp.zeros((0,)), jnp.zeros((0,), jnp.int32),
                           num_nodes=7, interpret=True)
    np.testing.assert_array_equal(np.asarray(z), np.zeros(7))
    g = GraphStore.from_edges(np.zeros(0, int), np.zeros(0, int), 5)
    got = expand_frontier(g.payload(), jnp.ones(5), hops=1, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(5))
    idx, matched = hash_join(jnp.asarray([1, 2, 3]),
                             jnp.asarray([], dtype=jnp.int32))
    assert not bool(np.asarray(matched).any())
    assert idx.shape == (3,)


def test_tfidf_matches_reference(rng):
    docs = [rng.randint(0, 16, rng.randint(2, 8)) for _ in range(20)]
    tx = TextStore.from_docs(docs, 16)
    q = tx.query_vector([1, 3, 5])
    got = tfidf_scores(tx.payload(), jnp.asarray(q))
    want = R.tfidf_scores_ref(tx.doc_ids, tx.term_ids, tx.tf, tx.doc_len,
                              tx.idf, q)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)
    ids, scores, valid = tfidf_topk(tx.payload(), jnp.asarray(q), 5)
    np.testing.assert_allclose(np.asarray(scores),
                               np.sort(want)[::-1][:5], rtol=1e-5)
    assert bool(np.asarray(valid).all())


@pytest.mark.parametrize("batched_corpus", [False, True])
def test_tfidf_batched_matches_per_query(rng, batched_corpus):
    docs = [rng.randint(0, 16, rng.randint(2, 8)) for _ in range(20)]
    tx = TextStore.from_docs(docs, 16)
    qs = jnp.asarray(np.stack([tx.query_vector(rng.randint(0, 16, 3))
                               for _ in range(3)]))
    corpus = tx.payload()
    if batched_corpus:
        stacked = jax.tree.map(lambda x: jnp.stack([x] * 3), corpus)
        got = jax.vmap(tfidf_scores)(stacked, qs)
    else:
        got = jax.vmap(tfidf_scores, in_axes=(None, 0))(corpus, qs)
    for i in range(3):
        want = tfidf_scores(corpus, qs[i])
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


# --------------------------------------------------------------------------
# doc-major padded postings (ELL) and the query-term select path
# --------------------------------------------------------------------------

ELL_VOCAB = 40


def _ell_store(rng, layout):
    """A corpus whose ELL the select path scores, built four ways: from
    docs (postings sorted by doc), from shuffled postings, with docs that
    hold no postings (one of length 0), and grown by ``append``.  Several
    docs sit at the widest width."""
    docs = [rng.choice(ELL_VOCAB, rng.randint(1, 7), replace=False)
            for _ in range(30)]
    docs += [rng.choice(ELL_VOCAB, 9, replace=False) for _ in range(3)]
    if layout == "appended":
        tx = TextStore.from_docs(docs[:20], ELL_VOCAB)
        tx.payload()
        return tx.append(docs[20:])
    tx = TextStore.from_docs(docs, ELL_VOCAB)
    if layout == "sorted":
        return tx
    perm = rng.permutation(tx.n_postings)
    doc_ids, doc_len = tx.doc_ids[perm], tx.doc_len
    if layout == "empty_docs":
        # four more docs with no postings, one of them of length 0
        doc_len = np.concatenate([doc_len, [3, 0, 1, 5]]).astype(np.float32)
    return TextStore.from_postings(doc_ids, tx.term_ids[perm], tx.tf[perm],
                                   doc_len, ELL_VOCAB)


def _query(rng, n_terms):
    """A query of exactly ``n_terms`` distinct terms, some repeated."""
    terms = rng.choice(ELL_VOCAB, n_terms, replace=False)
    q = np.zeros(ELL_VOCAB, np.float32)
    q[terms] = rng.randint(1, 3, n_terms)
    return jnp.asarray(q)


@pytest.mark.parametrize("n_terms", [0, 1, QUERY_TERMS, QUERY_TERMS + 1])
@pytest.mark.parametrize("layout",
                         ["sorted", "unsorted", "empty_docs", "appended"])
def test_ell_select_bitwise_equals_coo(rng, layout, n_terms):
    tx = _ell_store(rng, layout)
    pay = tx.payload()
    width = int(np.bincount(tx.doc_ids, minlength=tx.n_docs).max())
    assert pay["ell_term"].shape == pay["ell_tf"].shape == (width, tx.n_docs)
    assert width == 9
    q = _query(rng, n_terms)
    want = np.asarray(_tfidf_scores_coo(pay, q))
    assert bool(ell_select_applies(pay, q)) == (n_terms <= QUERY_TERMS)
    np.testing.assert_array_equal(np.asarray(tfidf_scores(pay, q)), want)
    if n_terms <= QUERY_TERMS:
        np.testing.assert_array_equal(
            np.asarray(_tfidf_scores_ell(pay, q)), want)
    np.testing.assert_allclose(
        want, R.tfidf_scores_ref(tx.doc_ids, tx.term_ids, tx.tf, tx.doc_len,
                                 tx.idf, np.asarray(q)), rtol=1e-5)


def test_ell_keeps_each_docs_postings_in_stored_order(rng):
    tx = _ell_store(rng, "unsorted")
    term, tf = tx.ell()
    for d in range(tx.n_docs):
        mine = tx.doc_ids == d
        n = int(mine.sum())
        np.testing.assert_array_equal(term[:n, d], tx.term_ids[mine])
        np.testing.assert_array_equal(tf[:n, d], tx.tf[mine])
        assert (term[n:, d] < 0).all() and (tf[n:, d] == 0).all()


def test_ell_select_under_the_batched_map_rule(rng):
    tx = _ell_store(rng, "unsorted")
    pay = tx.payload()
    qs = jnp.stack([_query(rng, n) for n in (3, QUERY_TERMS + 2, 0, 6)])
    got = np.asarray(jax.vmap(tfidf_scores, in_axes=(None, 0))(pay, qs))
    for i in range(qs.shape[0]):
        np.testing.assert_array_equal(
            got[i], np.asarray(_tfidf_scores_coo(pay, qs[i])))


def test_wide_skewed_corpus_builds_no_ell(rng):
    # one doc of 40 distinct terms among 30 one-term docs: 40 slots for
    # each of 31 docs over 70 postings fails the 4-slots-a-posting rule
    docs = [np.arange(ELL_VOCAB)] + [[t] for t in rng.randint(0, 40, 30)]
    tx = TextStore.from_docs(docs, ELL_VOCAB)
    pay = tx.payload()
    assert tx.ell() is None and "ell_term" not in pay
    q = _query(rng, 3)
    assert not bool(ell_select_applies(pay, q))
    np.testing.assert_array_equal(np.asarray(tfidf_scores(pay, q)),
                                  np.asarray(_tfidf_scores_coo(pay, q)))


def test_sharded_store_keeps_its_block_payload_and_no_ell(rng):
    pay = _ell_store(rng, "sorted").with_shards(2).payload()
    assert "blk_doc_local" in pay and "ell_term" not in pay


def test_explain_analyze_reports_scoring_layout_and_path(rng):
    table, graph, corpus = _small_social(rng)
    fn = _tri_analysis(table, graph, corpus).compile(
        SYS, engines=store_engines(), cache=False)
    inputs = {"tweets": table.payload(), "g": graph.payload(),
              "cx": corpus.payload()}
    for terms, path in (([1, 2, 3], 1), (np.arange(QUERY_TERMS + 1), 0)):
        q = jnp.asarray(corpus.query_vector(terms))
        fn.analyze({}, {**inputs, "q": q})
        spans = [s for s in fn.last_run_trace.op_spans()
                 if "layout" in s.attrs]
        assert len(spans) == 1
        assert spans[0].attrs["layout"] == "ell"
        assert spans[0].attrs["ell_select"] == path


# --------------------------------------------------------------------------
# xfer placement
# --------------------------------------------------------------------------

def _attn_plan():
    from repro.core.ir import Plan
    p = Plan("ap")
    p.add_input("h", TensorT((2, 16, 32), "float32",
                             ("batch", "seq", "embed")))
    a = p.add("attention", ["h"], {"heads": 4, "kv_heads": 2, "head_dim": 8,
                                   "embed": 32, "pp": ("attn",)})
    p.set_outputs(a)
    return p


def test_place_xfers_noop_on_tensor_plans():
    out = rewrite(_attn_plan(), CAT)
    assert not any(n.op == "xfer" for n in out.topo())


def _tri_analysis(table, graph, corpus):
    with Analysis("tri", CAT) as a:
        tw = a.bind("tweets", table)
        gr = a.bind("g", graph)
        cx = a.bind("cx", corpus)
        q = a.input("q", TensorT((corpus.vocab,), "float32", ("vocab",)))
        t = a.op("rel_scan", tw)
        hot = a.op("rel_filter", t, col="engagement", cmp="ge", value=20.0)
        seeds = a.op("rel_group_agg", hot, key="hashtag", num_groups=graph.n_nodes,
                     aggs=(("seed", "count", None),))
        sv = a.op("col_tensor", seeds, col="seed", dim="nodes")
        fr = a.op("graph_expand", gr, sv, hops=2)
        pr = a.op("graph_pagerank", gr, fr, iters=4)
        hits = a.op("text_topk", cx, q, k=8)
        j = a.op("rel_join", t, hits, left_on="doc", right_on="doc")
        trel = a.op("rel_group_agg", j, key="hashtag",
                    num_groups=graph.n_nodes,
                    aggs=(("textrel", "sum", "score"),))
        tv = a.op("col_tensor", trel, col="textrel", dim="nodes")
        comb = a.op("residual_add", pr, tv)
        a.store(comb)
    return a


def _small_social(rng):
    rows, nodes, vocab, docs = 300, 24, 32, 300
    table = ColumnStore({
        "user": rng.randint(0, 30, rows).astype(np.int32),
        "hashtag": rng.randint(0, nodes, rows).astype(np.int32),
        "doc": np.arange(rows, dtype=np.int32),
        "engagement": (rng.rand(rows) * 50).astype(np.float32),
    })
    e = rng.randint(0, nodes, (2, 200))
    graph = GraphStore.from_edges(e[0], e[1], nodes, symmetric=True)
    corpus = TextStore.from_docs(
        [rng.randint(0, vocab, rng.randint(2, 8)) for _ in range(docs)],
        vocab)
    return table, graph, corpus


def test_place_xfers_marks_engine_boundaries(rng):
    a = _tri_analysis(*_small_social(rng))
    placed = place_xfers(a.plan, CAT)
    xfers = [n for n in placed.topo() if n.op == "xfer"]
    assert len(xfers) >= 4
    crossings = {(n.attrs["src_engine"], n.attrs["dst_engine"])
                 for n in xfers}
    # rel -> graph (frontier seed), text -> rel (topk relation), and the
    # store-engine -> xla boundaries of the final ranking
    assert ("rel", "graph") in crossings
    assert ("text", "rel") in crossings
    assert not any(n.attrs.get("spill_only") for n in xfers)
    naive = place_xfers_naive(a.plan, CAT)
    spills = [n for n in naive.topo() if n.op == "xfer"]
    assert all(n.attrs["spill_only"] for n in spills)
    n_store_ops = sum(1 for n in a.plan.topo()
                      if CAT.get(n.op).engine != "xla")
    assert len(spills) == n_store_ops


# --------------------------------------------------------------------------
# end-to-end tri-model planning + execution
# --------------------------------------------------------------------------

def test_store_engines_registered():
    assert set(engine_names()) >= {"xla", "pallas", "rel", "graph", "text"}
    assert store_engines() == ("xla", "rel", "graph", "text")
    assert store_engines(pallas=True)[-1] == "pallas"


def test_tri_model_end_to_end_matches_numpy(rng):
    table, graph, corpus = _small_social(rng)
    a = _tri_analysis(table, graph, corpus)
    fn = a.compile(SYS, engines=store_engines(), cache=False)

    # planner pins every cross-engine boundary in device memory
    xfer_choices = [r for r in fn.report if r["pattern"] == "xfer_op"]
    assert xfer_choices and all(r["chosen"] == "xfer_pin"
                                for r in xfer_choices)

    q = corpus.query_vector([1, 2, 3])
    inputs = {"tweets": table.payload(), "g": graph.payload(),
              "cx": corpus.payload(), "q": jnp.asarray(q)}
    got = np.asarray(fn({}, inputs))

    # pure-NumPy reference pipeline
    eng = table.column("engagement")
    tags = table.column("hashtag")
    mask = eng >= 20.0
    seeds = R.group_agg_ref(None, tags, graph.n_nodes, mask, "count")
    fr = R.expand_ref(graph.src, graph.indices, graph.weights,
                      graph.n_nodes, seeds, hops=2)
    pr = R.pagerank_ref(graph.src, graph.indices, graph.weights,
                        graph.n_nodes, iters=4, personalization=fr)
    scores = R.tfidf_scores_ref(corpus.doc_ids, corpus.term_ids, corpus.tf,
                                corpus.doc_len, corpus.idf, q)
    top = np.argsort(-scores, kind="stable")[:8]
    trel = np.zeros(graph.n_nodes)
    for d in top:
        trel[tags[d]] += scores[d]          # doc id == row id here
    np.testing.assert_allclose(got, pr + trel, rtol=1e-4, atol=1e-6)


def test_naive_and_planned_placement_agree_bitwise(rng):
    table, graph, corpus = _small_social(rng)
    a = _tri_analysis(table, graph, corpus)
    naive_pipeline = tuple(p for p in DEFAULT_PIPELINE
                           if p != "place_xfers") + ("place_xfers_naive",)
    planned = a.compile(SYS, engines=store_engines(), cache=False)
    naive = a.compile(SYS, engines=store_engines(), cache=False,
                      rewrite_pipeline=naive_pipeline)
    assert any(n.impl == "xfer_spill" for n in naive.concrete.topo())
    inputs = {"tweets": table.payload(), "g": graph.payload(),
              "cx": corpus.payload(),
              "q": jnp.asarray(corpus.query_vector([4, 5]))}
    out_p = np.asarray(jax.jit(lambda i: planned({}, i))(inputs))
    out_n = np.asarray(jax.jit(lambda i: naive({}, i))(inputs))
    np.testing.assert_array_equal(out_p, out_n)


def test_pallas_graph_candidates_selected_and_close(rng):
    table, graph, corpus = _small_social(rng)
    a = _tri_analysis(table, graph, corpus)
    fn = a.compile(SYS, engines=store_engines(pallas=True), cache=False)
    chosen = {r["pattern"]: r["chosen"] for r in fn.report}
    assert chosen["graph_expand_op"] == "expand_pallas"
    assert chosen["graph_pagerank_op"] == "pagerank_pallas"
    fb = a.compile(SYS, engines=store_engines(), cache=False)
    inputs = {"tweets": table.payload(), "g": graph.payload(),
              "cx": corpus.payload(),
              "q": jnp.asarray(corpus.query_vector([4, 5]))}
    np.testing.assert_allclose(np.asarray(fn({}, inputs)),
                               np.asarray(fb({}, inputs)), rtol=1e-4,
                               atol=1e-6)


def test_onehot_kernels_priced_by_grid_work():
    """The one-hot Pallas kernels visit out-blocks x in-blocks: at the
    deployment size (2^23 tweets, 2^18 hashtags, 2^24 edges, 59M postings)
    that grid loses to the CSR / columnar paths, which the small graph of
    the test above does not; a grid past int32 steps cannot compile."""
    from types import SimpleNamespace as NS

    from repro.core.cost_model import op_cost
    tweets, tags = 1 << 23, 1 << 18
    table = NS(type=TableT((("user", "int32"), ("hashtag", "int32"),
                            ("doc", "int32"), ("engagement", "float32")),
                           tweets))
    graph = NS(type=GraphT(tags, 1 << 24), n_nodes=tags)
    corpus = NS(type=CorpusT(tweets, 1 << 16, 59_000_000), vocab=1 << 16)
    fn = _tri_analysis(table, graph, corpus).compile(
        SYS, engines=store_engines(pallas=True), cache=False)
    chosen = {r["pattern"]: r["chosen"] for r in fn.report}
    assert chosen["graph_expand_op"] == "expand_csr"
    assert chosen["graph_pagerank_op"] == "pagerank_csr"
    # the 2^23-row hot-tweet group-by stays columnar
    assert "rel_fused_col" in [r["chosen"] for r in fn.report
                               if r["pattern"] == "rel_fused_op"]
    c = op_cost("text_topk_masked_pallas", [corpus.type],
                {"k": 64, "selectivity": 0.1}, SYS)
    assert c.flops == float("inf")


# --------------------------------------------------------------------------
# ADIL front ends: native table/graph/corpus declarations
# --------------------------------------------------------------------------

TRI_SRC = """
USE socialDB;
create analysis tiny_tri as {
  tweets := table(rows=100, cols=[[hashtag, int32], [engagement, float32]]);
  g      := graph(nodes=16, edges=64);
  cx     := corpus(docs=100, vocab=32, postings=400);
  q      := input([32], float32, dims=[vocab]);
  t      := rel_scan(tweets);
  hot    := rel_filter(t, col=engagement, cmp=ge, value=10.0);
  seeds  := rel_group_agg(hot, key=hashtag, num_groups=16,
                          aggs=[[seed, count, hashtag]]);
  sv     := col_tensor(seeds, col=seed, dim=nodes);
  pr     := graph_pagerank(g, sv, iters=3);
  hits   := text_topk(cx, q, k=5);
  store(pr);
  store(hits);
}
"""


def test_parser_store_declarations_match_builder():
    parsed = parse_adil(TRI_SRC, CAT)
    assert parsed.plan.inputs["tweets"] == TableT(
        (("hashtag", "int32"), ("engagement", "float32")), 100)
    assert parsed.plan.inputs["g"] == GraphT(16, 64)
    assert parsed.plan.inputs["cx"] == CorpusT(100, 32, 400)

    with Analysis("tiny_tri", CAT) as b:
        tw = b.table("tweets", 100, (("hashtag", "int32"),
                                     ("engagement", "float32")))
        gr = b.graph("g", 16, 64)
        cx = b.corpus("cx", 100, 32, 400)
        q = b.input("q", TensorT((32,), "float32", ("vocab",)))
        t = b.op("rel_scan", tw)
        hot = b.op("rel_filter", t, col="engagement", cmp="ge", value=10.0)
        seeds = b.op("rel_group_agg", hot, key="hashtag", num_groups=16,
                     aggs=(("seed", "count", "hashtag"),))
        sv = b.op("col_tensor", seeds, col="seed", dim="nodes")
        pr = b.op("graph_pagerank", gr, sv, iters=3)
        hits = b.op("text_topk", cx, q, k=5)
        b.store(pr)
        b.store(hits)
    assert plan_id(parsed.plan, CAT, SYS) == plan_id(b.plan, CAT, SYS)


def _tri_src_stores(rng):
    """Stores matching TRI_SRC's declarations exactly."""
    table = ColumnStore({"hashtag": rng.randint(0, 16, 100).astype(np.int32),
                         "engagement": rng.rand(100).astype(np.float32)})
    graph = GraphStore.from_edges(rng.randint(0, 16, 64),
                                  rng.randint(0, 16, 64), 16)
    docs = np.repeat(np.arange(100), 4)
    terms = np.tile(np.arange(4), 100) + 4 * (docs % 8)
    corpus = TextStore.from_postings(docs, terms, np.ones(400), np.full(100, 4),
                                     32)
    return table, graph, corpus


def test_bind_attaches_stores_to_declared_inputs(rng):
    table, graph, corpus = _tri_src_stores(rng)
    parsed = parse_adil(TRI_SRC, CAT)
    for name, store in (("tweets", table), ("g", graph), ("cx", corpus)):
        parsed.bind(name, store)
    assert parsed.store_versions() == (("cx", 0), ("g", 0), ("tweets", 0))
    fn = parsed.compile(SYS, engines=store_engines(), cache=False)
    inputs = {"tweets": table.payload(), "g": graph.payload(),
              "cx": corpus.payload(),
              "q": jnp.asarray(corpus.query_vector([1, 2]))}
    pr, hits = fn({}, inputs)
    assert pr.shape == (16,) and int(hits.count) == 5

    # a sharded store keeps the declaration and brings its own placement
    sharded = parse_adil(TRI_SRC, CAT)
    sharded.bind("tweets", table.with_shards(2))
    sharded.compile(SYS, engines=store_engines(), cache=False)
    assert sharded.plan.inputs["tweets"].partitioning == "row"

    short = ColumnStore({"hashtag": np.zeros(99, np.int32),
                         "engagement": np.zeros(99, np.float32)})
    with pytest.raises(ValidationError, match="does not match"):
        parse_adil(TRI_SRC, CAT).bind("tweets", short)


def test_tri_store_type_validation():
    with pytest.raises(ValidationError):        # filter on missing column
        with Analysis("bad", CAT) as a:
            tw = a.table("t", 10, (("x", "int32"),))
            a.store(a.op("rel_filter", tw, col="nope", cmp="ge", value=1))
    with pytest.raises(ValidationError):        # frontier shape mismatch
        with Analysis("bad2", CAT) as a:
            g = a.graph("g", 8, 16)
            f = a.input("f", TensorT((4,), "float32", ("nodes",)))
            a.store(a.op("graph_expand", g, f))
    with pytest.raises(ValidationError):        # query vocab mismatch
        with Analysis("bad3", CAT) as a:
            cx = a.corpus("c", 10, 32, 50)
            q = a.input("q", TensorT((16,), "float32", ("vocab",)))
            a.store(a.op("text_topk", cx, q, k=3))
    with pytest.raises(ValidationError):        # float group key
        with Analysis("bad4", CAT) as a:
            tw = a.table("t", 10, (("x", "float32"),))
            a.store(a.op("rel_group_agg", tw, key="x", num_groups=4,
                         aggs=(("n", "count", None),)))
    with pytest.raises(ValidationError):        # weights/edges mismatch
        GraphStore.from_edges([0, 1], [1, 0], 2, weights=[1.0, 2.0, 3.0])
