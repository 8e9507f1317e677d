"""The tri-model analysis's data and ADIL program, kept with the benchmark.

A copy of ``examples/tri_model_analysis.py``'s ``build_social_data`` and
``adil_script``, so that the yardstick does not move when the example is
edited.  The generator returns the raw arrays (:class:`SocialData`); the
reference reads only those, and :func:`build_stores` hands them to the
program's store constructors.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np


@dataclass
class SocialData:
    user: np.ndarray          # (tweets,) int32
    hashtag: np.ndarray       # (tweets,) int32
    doc: np.ndarray           # (tweets,) int32, tweet d's document
    engagement: np.ndarray    # (tweets,) float32
    edge_a: np.ndarray        # co-mention edges, one direction (int32)
    edge_b: np.ndarray
    post_doc: np.ndarray      # postings: one per distinct (doc, term)
    post_term: np.ndarray
    post_tf: np.ndarray       # the term's count in the doc
    doc_len: np.ndarray       # tokens per doc (>= 1)
    hashtags: int
    vocab: int

    @property
    def tweets(self) -> int:
        return int(self.hashtag.shape[0])

    def cut(self, *, postings: int, edges: int) -> "SocialData":
        """The same data held to exactly ``postings`` postings and
        ``edges`` co-mention edges (the first ones, in the order they were
        drawn), so that every seed gives stores of the same shapes and
        every program compiled for one seed serves the next.  A seed draws
        a few thousand more or fewer; a count below the cut is an error."""
        if self.post_doc.shape[0] < postings or self.edge_a.shape[0] < edges:
            raise ValueError(
                f"{self.post_doc.shape[0]} postings and {self.edge_a.shape[0]}"
                f" edges drawn, fewer than the cut {postings} and {edges}")
        return replace(self, edge_a=self.edge_a[:edges],
                       edge_b=self.edge_b[:edges],
                       post_doc=self.post_doc[:postings],
                       post_term=self.post_term[:postings],
                       post_tf=self.post_tf[:postings])

    def term_postings(self) -> np.ndarray:
        """Postings per term (document frequency), from the raw postings."""
        return np.bincount(self.post_term, minlength=self.vocab)


def build_social_data(rng, *, users=500, hashtags=128, tweets=20_000,
                      vocab=256, doc_tokens=(3, 12)) -> SocialData:
    """Synthetic social-media slice: a tweet table, a hashtag co-mention
    graph and the tweet-text corpus (one doc per tweet), drawn from ``rng``
    (a ``numpy.random.Generator``) in the example's order, so one seed
    gives the example's data."""
    user = rng.integers(0, users, tweets, dtype=np.int32)
    tag = (rng.zipf(1.3, tweets) % hashtags).astype(np.int32)
    doc = np.arange(tweets, dtype=np.int32)
    engagement = rng.gamma(2.0, 12.0, tweets).astype(np.float32)

    # co-mention edges: tweets by the same user mentioning different tags
    order = np.argsort(user, kind="stable")
    u_sorted, t_sorted = user[order], tag[order]
    sel = (u_sorted[1:] == u_sorted[:-1]) & (t_sorted[1:] != t_sorted[:-1])

    # tweet d holds lens[d] Zipf-distributed tokens; one posting per
    # distinct (doc, term), its count the term frequency
    lens = rng.integers(doc_tokens[0], doc_tokens[1], tweets)
    terms = rng.zipf(1.4, int(lens.sum())) % vocab
    key = np.repeat(np.arange(tweets, dtype=np.int64), lens) * vocab + terms
    key, tf = np.unique(key, return_counts=True)
    return SocialData(
        user=user, hashtag=tag, doc=doc, engagement=engagement,
        edge_a=t_sorted[:-1][sel], edge_b=t_sorted[1:][sel],
        post_doc=(key // vocab).astype(np.int32),
        post_term=(key % vocab).astype(np.int32), post_tf=tf,
        doc_len=np.maximum(lens, 1), hashtags=hashtags, vocab=vocab)


def build_stores(data: SocialData):
    """The program's three stores over ``data``: (table, graph, corpus)."""
    from repro.stores import ColumnStore, GraphStore, TextStore
    table = ColumnStore({"user": data.user, "hashtag": data.hashtag,
                         "doc": data.doc, "engagement": data.engagement})
    graph = GraphStore.from_edges(data.edge_a, data.edge_b, data.hashtags,
                                  symmetric=True)
    corpus = TextStore.from_postings(data.post_doc, data.post_term,
                                     data.post_tf, data.doc_len, data.vocab)
    return table, graph, corpus


def query_vector(terms, vocab: int) -> np.ndarray:
    """Dense (vocab,) query term-count vector."""
    return np.bincount(np.asarray(terms) % vocab,
                       minlength=vocab).astype(np.float32)


def adil_script(table, graph, corpus) -> str:
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
