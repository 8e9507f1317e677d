"""Tri-model analysis: one ADIL program over table + graph + corpus.

The paper's headline scenario (PoliSci, Fig. 1): a single analysis scans a
tweet relation, walks a graph, and ranks by text relevance, and the
optimizer plans the cross-engine movement.  This example is the literal
reproduction — a *textual* ADIL script declaring the three native store
types and piping them through one `PlanPipeline` plan:

  1. relational: scan the tweet table, filter on engagement, aggregate
     hashtag counts (the frontier seed);
  2. graph: 2-hop expansion over the hashtag co-mention graph, then
     personalized PageRank (topic authority);
  3. text: top-k TF-IDF docs for a query, joined back to tweets and
     aggregated per hashtag (text relevance);
  4. fused ranking = PageRank + text relevance.

Every engine boundary is an explicit ``xfer`` node whose materialization
the cost model decides (pin = stay in device memory — the AWESOME
in-memory optimization; spill = host round-trip, what a naive federated
mediator would do).  Run it and read the EXPLAIN report: the planner pins
every boundary and picks the Pallas frontier kernels over the segment_sum
fallback.

    PYTHONPATH=src python examples/tri_model_analysis.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core.adil_parser import parse_adil
from repro.core.ir import SystemCatalog, standard_catalog
from repro.stores import ColumnStore, GraphStore, TextStore, store_engines


def build_social_data(rng, *, users=500, hashtags=128, tweets=20_000,
                      vocab=256, doc_tokens=(3, 12)):
    """Synthetic social-media slice: a tweet table, a hashtag co-mention
    graph, and the tweet-text corpus (one doc per tweet).  ``rng`` is a
    ``numpy.random.Generator``.  Every step is vectorized, so the same
    builder makes deployment-size stores (``chip_smoke.py``)."""
    user = rng.integers(0, users, tweets, dtype=np.int32)
    tag = (rng.zipf(1.3, tweets) % hashtags).astype(np.int32)
    doc = np.arange(tweets, dtype=np.int32)
    engagement = rng.gamma(2.0, 12.0, tweets).astype(np.float32)
    table = ColumnStore({"user": user, "hashtag": tag, "doc": doc,
                         "engagement": engagement})

    # co-mention edges: tweets by the same user mentioning different tags
    order = np.argsort(user, kind="stable")
    u_sorted, t_sorted = user[order], tag[order]
    same_user = u_sorted[1:] == u_sorted[:-1]
    diff_tag = t_sorted[1:] != t_sorted[:-1]
    sel = same_user & diff_tag
    graph = GraphStore.from_edges(t_sorted[:-1][sel], t_sorted[1:][sel],
                                  hashtags, symmetric=True)

    # tweet d holds lens[d] Zipf-distributed tokens; one posting per
    # distinct (doc, term), its count the term frequency
    lens = rng.integers(doc_tokens[0], doc_tokens[1], tweets)
    terms = rng.zipf(1.4, int(lens.sum())) % vocab
    key = np.repeat(np.arange(tweets, dtype=np.int64), lens) * vocab + terms
    key, tf = np.unique(key, return_counts=True)
    corpus = TextStore.from_postings(key // vocab, key % vocab, tf,
                                     np.maximum(lens, 1), vocab)
    return table, graph, corpus


def adil_script(table, graph, corpus):
    t = table.type
    cols = ", ".join(f"[{n}, {d}]" for n, d in t.columns)
    return f"""
USE socialDB;
create analysis hashtag_pulse as {{
  tweets := table(rows={t.rows}, cols=[{cols}]);
  g      := graph(nodes={graph.type.nodes}, edges={graph.type.edges});
  cx     := corpus(docs={corpus.type.docs}, vocab={corpus.type.vocab},
                   postings={corpus.type.postings});
  q      := input([{corpus.type.vocab}], float32, dims=[vocab]);

  t      := rel_scan(tweets);
  hot    := rel_filter(t, col=engagement, cmp=ge, value=30.0);
  seeds  := rel_group_agg(hot, key=hashtag, num_groups={graph.type.nodes},
                          aggs=[[seed, count, hashtag]]);
  sv     := col_tensor(seeds, col=seed, dim=nodes);

  fr     := graph_expand(g, sv, hops=2);
  pr     := graph_pagerank(g, fr, iters=8, damping=0.85);

  hits   := text_topk(cx, q, k=64);
  j      := rel_join(hits, tweets, left_on=doc, right_on=doc);
  trel   := rel_group_agg(j, key=hashtag, num_groups={graph.type.nodes},
                          aggs=[[textrel, sum, score]]);
  tv     := col_tensor(trel, col=textrel, dim=nodes);

  score  := residual_add(pr, tv);
  store(score);
}}
"""


def main():
    rng = np.random.default_rng(0)
    table, graph, corpus = build_social_data(rng)
    cat = standard_catalog()
    analysis = parse_adil(adil_script(table, graph, corpus), cat)

    fn = analysis.compile(SystemCatalog(), engines=store_engines(pallas=True))
    print(fn.explain())
    print()

    query = jnp.asarray(corpus.query_vector(rng.integers(0, 256, 6)))
    score = fn({}, {"tweets": table.payload(), "g": graph.payload(),
                    "cx": corpus.payload(), "q": query})
    top = np.argsort(-np.asarray(score))[:10]
    print("top hashtags (pagerank + text relevance):")
    for h in top:
        print(f"  #{h:<6} score={float(score[h]):.4f}")
    xfers = [r for r in fn.report if r["pattern"] == "xfer_op"]
    pins = sum(1 for r in xfers if r["chosen"] == "xfer_pin")
    print(f"\ncross-engine boundaries: {len(xfers)}, pinned in device "
          f"memory: {pins} (planned placement; the naive baseline would "
          f"spill each through the host)")


if __name__ == "__main__":
    main()
