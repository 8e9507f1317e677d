"""The plain reference of the ``hashtag_pulse`` analysis, in NumPy.

It reads only the generator's raw arrays (:class:`bench.social.SocialData`)
and the query vector, never a store, payload or table the program made.
The composition follows the program's semantics as ``stores/ref.py`` and
``chip_smoke.py`` state them; the tests hold it to both at a small size.

  seeds  = per-hashtag count of tweets with engagement >= 30
  fr     = 2 hops of y[v] = sum over edges u->v of x[u] (co-mention edges,
           both directions, weight 1)
  pr     = 8 iterations of personalized PageRank from fr, damping 0.85
  scores = TF-IDF of every doc: sum over its postings (d, t) of
           q[t] * idf[t] * tf / len[d], idf = log((1+docs)/(1+df)) + 1
  hits   = the 64 best docs (ties: lowest doc id), joined to their tweets
  trel   = per-hashtag sum of the hits' scores
  result = pr + trel

``rounding`` rounds every intermediate array; the control passes a
bfloat16 rounding (:func:`bf16`), the nearest precision below the float32
the configuration states.
"""
from __future__ import annotations

import numpy as np

HOT_ENGAGEMENT, HOPS, PR_ITERS, DAMPING, TOP_K = 30.0, 2, 8, 0.85, 64
# float32 stores against float64: the hub hashtag sums ~3M in-edges in
# each of 10 SpMV passes, and each hashtag sums <= 64 text scores
SCORE_RTOL, SCORE_ATOL_REL = 1e-4, 1e-6


def exact(x):
    return np.asarray(x, np.float64)


def bf16(x):
    """Round to bfloat16 and back: the control's precision."""
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class Reference:
    """The query-independent half, computed once per data set, and the
    text half per query."""

    def __init__(self, data, rounding=exact):
        self.data = data
        self.r = rounding
        n = data.hashtags
        self.src = np.concatenate([data.edge_a, data.edge_b]).astype(np.int64)
        self.dst = np.concatenate([data.edge_b, data.edge_a]).astype(np.int64)
        self.n = n
        df = data.term_postings().astype(np.float64)
        self.idf = self.r(np.log((1.0 + data.tweets) / (1.0 + df)) + 1.0)
        self.doc_index = JoinIndex(data.doc)
        self.pr = self._graph_half()

    def _spmv(self, x):
        return self.r(np.bincount(self.dst, weights=self.r(x)[self.src],
                                  minlength=self.n))

    def _graph_half(self):
        d, n = self.data, self.n
        hot = d.engagement >= HOT_ENGAGEMENT
        x = self.r(np.bincount(d.hashtag[hot], minlength=n))
        for _ in range(HOPS):
            x = self._spmv(x)
        out_deg = np.maximum(np.bincount(self.src, minlength=n), 1)
        p0 = self.r(x / max(x.sum(), 1e-30))
        r = p0.copy()
        for _ in range(PR_ITERS):
            r = self.r((1 - DAMPING) * p0
                       + DAMPING * self._spmv(self.r(r / out_deg)))
        return r

    def scores(self, q):
        """TF-IDF of every doc, over the postings of the query's terms."""
        d = self.data
        q = np.asarray(q, np.float64)
        sel = q[d.post_term] != 0
        t, doc = d.post_term[sel], d.post_doc[sel]
        contrib = self.r(q[t] * self.idf[t] * d.post_tf[sel]
                         / d.doc_len[doc])
        return self.r(np.bincount(doc, weights=contrib,
                                  minlength=d.doc_len.shape[0]))

    def text_half(self, q):
        """(per-hashtag text relevance, k-th score, spare tied docs)."""
        scores = self.scores(q)
        s32 = scores.astype(np.float32)
        k = min(TOP_K, s32.shape[0])
        kth = np.partition(s32, s32.shape[0] - k)[s32.shape[0] - k]
        cand = np.flatnonzero(s32 >= kth)
        ids = cand[np.lexsort((cand, -s32[cand]))][:k]
        top = s32[ids]
        idx, matched = self.doc_index.probe(ids)
        keys = self.data.hashtag[idx][matched]
        trel = self.r(np.bincount(keys, weights=top[matched].astype(
            np.float64), minlength=self.n).astype(np.float32))
        kth = float(top[-1])
        spare = int((s32 == kth).sum() - (top == kth).sum())
        return trel, kth, spare

    def result(self, q):
        """(pr + trel, k-th score, spare tied docs)."""
        trel, kth, spare = self.text_half(q)
        return self.pr + trel, kth, spare


class JoinIndex:
    """The build side of an equi-join on a unique key (a repeated key
    resolves to its last row), probed once per query."""

    def __init__(self, rkeys):
        rkeys = np.asarray(rkeys, np.int64)
        self.uniq, first_rev = np.unique(rkeys[::-1], return_index=True)
        self.last = rkeys.size - 1 - first_rev

    def probe(self, lkeys):
        lkeys = np.asarray(lkeys, np.int64)
        pos = np.minimum(np.searchsorted(self.uniq, lkeys),
                         self.uniq.size - 1)
        matched = self.uniq[pos] == lkeys
        return np.where(matched, self.last[pos], 0).astype(np.int64), \
            matched


def score_error(got, want) -> float:
    """Max |got - want| over the tolerance; inf for a malformed vector."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    tol = SCORE_RTOL * np.abs(want) + SCORE_ATOL_REL * np.abs(want).max()
    return float((np.abs(got - want) / tol).max())


def analysis_error(got, want, kth, spare):
    """``score_error`` of one result, allowing the one freedom the
    reference cannot pin down: documents tied at the k-th TF-IDF score
    may be kept in another order when float32 sums round them apart.  Such
    exchanges move whole multiples of the k-th score between hashtags, net
    zero, and draw on at most ``spare`` tied documents.  Returns (error,
    documents exchanged)."""
    err = score_error(got, want)
    if err <= 1.0 or kth <= 0.0 or not np.isfinite(err):
        return err, 0
    moved = np.rint((np.asarray(got, np.float64) - want) / kth)
    swapped = int(moved[moved > 0].sum())
    if moved.sum() != 0 or swapped > spare:
        return err, 0
    return score_error(np.asarray(got, np.float64) - moved * kth,
                       want), swapped
