"""Inverted-index text store: tokenized corpus + top-k TF-IDF scoring.

The corpus is stored as the COO of its term-document matrix — one
``(doc_id, term_id, tf)`` triple per posting — plus per-document lengths
and the idf table.  Scoring a dense query vector is one gather + one
segment-sum over the postings (static shapes, jittable):

    score[d] = Σ_{postings (d, t)}  q[t] · idf[t] · tf[d,t] / len[d]

followed by ``lax.top_k`` over documents.  An unsharded corpus whose
documents are about equally wide also keeps its postings doc-major, padded
to the widest document (ELL, ``[width, n_docs]``): a keyword query of at
most :data:`QUERY_TERMS` distinct terms then scores every slot by comparing
its term with the query's terms, with no postings-long gather and no
scatter (:func:`_tfidf_scores_ell`).  The result is handed back as a
*relation* (a (k,)-row table of ``doc``/``score``) — cross-engine by
construction, which is what the planner's ``xfer`` placement operates on.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ir import CorpusT, ValidationError
from ..core.ledger import register_store_payload

# the keyword-query width the ELL select path serves: queries with at most
# this many distinct terms (the benchmark's and the paper's have <= 6) score
# by selects; wider ones take the COO gather + scatter
QUERY_TERMS = 8
# the ELL is built only while its slots stay within this many per posting
# (a corpus of long, skewed documents keeps the COO path alone)
ELL_MAX_SLOTS_PER_POSTING = 4
# term id of an empty ELL slot, and of an unused query slot: neither matches
# a real term (>= 0) nor the other
_ELL_PAD_TERM = -1
_NO_QUERY_TERM = -2


class TextStore:
    """Host-side container: tokenized documents -> inverted-index COO."""

    def __init__(self, doc_ids, term_ids, tf, doc_len, idf, vocab: int,
                 shards: int = 1):
        self.doc_ids = np.asarray(doc_ids, np.int32)
        self.term_ids = np.asarray(term_ids, np.int32)
        self.tf = np.asarray(tf, np.float32)
        self.doc_len = np.asarray(doc_len, np.float32)
        self.idf = np.asarray(idf, np.float32)
        self.vocab = int(vocab)
        self.shards = int(shards)
        if self.shards < 1:
            raise ValidationError(f"shards {self.shards} < 1")
        if self.shards > 1 and self.doc_len.shape[0] % self.shards:
            # document-range partitioning needs equal doc blocks: pad with
            # empty docs (doc_len 1, no postings -> score exactly 0.0)
            pad = (-self.doc_len.shape[0]) % self.shards
            self.doc_len = np.concatenate(
                [self.doc_len, np.ones(pad, np.float32)])
        self.n_docs = int(self.doc_len.shape[0])
        self.n_postings = int(self.doc_ids.shape[0])
        # document frequency per term — kept so incremental appends can
        # *reindex* (recompute the idf table) without replaying the corpus
        self._df = (np.bincount(self.term_ids, minlength=self.vocab)
                    .astype(np.int64) if self.n_postings
                    else np.zeros(self.vocab, np.int64))
        self.version = 0

    @staticmethod
    def _index_docs(docs, vocab: int, first_doc: int):
        doc_ids, term_ids, tfs = [], [], []
        doc_len = np.zeros(len(docs), np.float32)
        df = np.zeros(vocab, np.int64)
        for d, terms in enumerate(docs):
            terms = np.asarray(list(terms), np.int64)
            if terms.size and (terms.min() < 0 or terms.max() >= vocab):
                raise ValidationError(
                    f"doc {first_doc + d}: term id out of range")
            doc_len[d] = max(terms.size, 1)
            uniq, counts = np.unique(terms, return_counts=True)
            doc_ids.append(np.full(uniq.shape, first_doc + d, np.int64))
            term_ids.append(uniq)
            tfs.append(counts)
            df[uniq] += 1
        cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0, np.int64))
        return cat(doc_ids), cat(term_ids), cat(tfs), doc_len, df

    @staticmethod
    def _idf(n_docs: int, df: np.ndarray) -> np.ndarray:
        return (np.log((1.0 + n_docs) / (1.0 + df)) + 1.0)  # smoothed idf

    @classmethod
    def from_docs(cls, docs: Sequence[Iterable[int]], vocab: int,
                  shards: int = 1) -> "TextStore":
        """``docs``: one iterable of term ids per document."""
        doc_ids, term_ids, tfs, doc_len, df = cls._index_docs(docs, vocab, 0)
        return cls(doc_ids, term_ids, tfs, doc_len, cls._idf(len(docs), df),
                   vocab, shards=shards)

    @classmethod
    def from_postings(cls, doc_ids, term_ids, tf, doc_len,
                      vocab: int) -> "TextStore":
        """An index given directly as COO postings (one per distinct
        ``(doc, term)``, ``tf`` its count) plus per-doc token counts — the
        vectorized ingest for corpora too large to list doc by doc."""
        df = np.bincount(np.asarray(term_ids, np.int64), minlength=vocab)
        return cls(doc_ids, term_ids, tf, doc_len,
                   cls._idf(len(doc_len), df), vocab)

    def with_shards(self, shards: int) -> "TextStore":
        """This corpus re-declared as document-partitioned over ``shards``
        mesh slices (pads the doc domain to a shard multiple)."""
        out = TextStore(self.doc_ids, self.term_ids, self.tf,
                        self.doc_len, self.idf, self.vocab, shards=shards)
        out.version = self.version
        return out

    def append(self, docs: Sequence[Iterable[int]]) -> "TextStore":
        """Append documents and reindex: postings extend (doc ids continue
        from ``n_docs``), document frequencies accumulate, and the idf
        table is recomputed over the grown corpus — identical to a fresh
        ``from_docs`` over the concatenated document list.  Bumps the
        monotonic ``version`` so cached plans priced against the old corpus
        statistics invalidate."""
        d_ids, t_ids, tfs, d_len, df = self._index_docs(
            docs, self.vocab, self.n_docs)
        self.doc_ids = np.concatenate([self.doc_ids,
                                       d_ids.astype(np.int32)])
        self.term_ids = np.concatenate([self.term_ids,
                                        t_ids.astype(np.int32)])
        self.tf = np.concatenate([self.tf, tfs.astype(np.float32)])
        self.doc_len = np.concatenate([self.doc_len, d_len])
        self._df += df
        self.n_docs += len(docs)
        self.n_postings = int(self.doc_ids.shape[0])
        self.idf = self._idf(self.n_docs, self._df).astype(np.float32)
        self.version += 1
        return self

    @property
    def type(self) -> CorpusT:
        return CorpusT(self.n_docs, self.vocab, self.n_postings,
                       "doc" if self.shards > 1 else None)

    def payload(self) -> dict:
        out = {
            "doc_ids": jnp.asarray(self.doc_ids),
            "term_ids": jnp.asarray(self.term_ids),
            "tf": jnp.asarray(self.tf),
            "doc_len": jnp.asarray(self.doc_len),
            "idf": jnp.asarray(self.idf),
        }
        if self.shards > 1:
            out.update(self._block_payload())
        else:
            ell = self.ell()
            if ell is not None:
                out["ell_term"] = jnp.asarray(ell[0])
                out["ell_tf"] = jnp.asarray(ell[1])
        register_store_payload(self, out, "text_store")
        return out

    def ell(self) -> Optional[tuple]:
        """Doc-major padded postings ``(ell_term, ell_tf)``, each
        ``[width, n_docs]`` (docs in the minor dimension: a TPU tiles the
        width to 16 rows, where ``[n_docs, width]`` would pad it to 128
        lanes), or None when the widest document's ``width`` would take
        more than ``ELL_MAX_SLOTS_PER_POSTING`` slots per posting.  Doc
        ``d``'s postings fill ``[0:count_d, d]`` in their stored order (a
        stable sort by doc), so summing the slots in order adds exactly
        what the COO segment-sum adds, in the same order; empty slots hold
        ``tf`` 0 and a term that matches no query term."""
        n, p = self.n_docs, self.n_postings
        if p == 0:
            return None
        counts = np.bincount(self.doc_ids, minlength=n)
        width = int(counts.max())
        if width * n > ELL_MAX_SLOTS_PER_POSTING * p:
            return None
        if np.all(self.doc_ids[1:] >= self.doc_ids[:-1]):
            order = slice(None)                 # already doc-major
        else:
            order = np.argsort(self.doc_ids, kind="stable")
        docs = self.doc_ids[order]
        starts = np.cumsum(counts) - counts
        slot = np.arange(p) - starts[docs]
        term = np.full((width, n), _ELL_PAD_TERM, np.int32)
        tf = np.zeros((width, n), np.float32)
        term[slot, docs] = self.term_ids[order]
        tf[slot, docs] = self.tf[order]
        return term, tf

    def _block_payload(self) -> dict:
        """Doc-block posting partition for shard-local scoring: shard d owns
        docs ``[d*n/s, (d+1)*n/s)`` and their postings, padded per block to
        the max block posting count.  Pad slots carry ``doc_local =
        n_local`` (dropped by the scatter) and tf=0, and the stable
        selection preserves per-doc posting order, so shard-local
        segment sums stay bitwise equal to the dense scoring."""
        s, n = self.shards, self.n_docs
        n_local = n // s
        block = self.doc_ids // n_local
        counts = np.bincount(block, minlength=s)
        p_max = max(int(counts.max()) if counts.size else 0, 1)
        docl_b = np.full((s, p_max), n_local, np.int32)    # pad -> dropped
        term_b = np.zeros((s, p_max), np.int32)
        tf_b = np.zeros((s, p_max), np.float32)
        order = np.argsort(block, kind="stable")
        starts = np.concatenate([[0], np.cumsum(counts)])
        for d in range(s):
            sel = order[starts[d]:starts[d + 1]]
            docl_b[d, :sel.size] = self.doc_ids[sel] - d * n_local
            term_b[d, :sel.size] = self.term_ids[sel]
            tf_b[d, :sel.size] = self.tf[sel]
        return {
            "blk_doc_local": jnp.asarray(docl_b.reshape(-1)),
            "blk_term_ids": jnp.asarray(term_b.reshape(-1)),
            "blk_tf": jnp.asarray(tf_b.reshape(-1)),
        }

    def query_vector(self, terms: Iterable[int]) -> np.ndarray:
        """Dense (vocab,) query term-count vector for :func:`tfidf_scores`."""
        q = np.zeros(self.vocab, np.float32)
        for t in terms:
            q[int(t)] += 1.0
        return q


# --------------------------------------------------------------------------
# scoring kernels (pure functions over the payload)
# --------------------------------------------------------------------------


def _tfidf_scores_coo(corpus: dict, query):
    """Scores from the COO postings: a gather per posting, a scatter-add
    into the docs."""
    w = query.astype(jnp.float32) * corpus["idf"]
    contrib = (w[corpus["term_ids"]] * corpus["tf"]
               / corpus["doc_len"][corpus["doc_ids"]])
    n_docs = corpus["doc_len"].shape[0]
    return jax.ops.segment_sum(contrib, corpus["doc_ids"],
                               num_segments=n_docs)


def _tfidf_scores_ell(corpus: dict, query):
    """Scores from the ELL postings, exact for a query of at most
    ``QUERY_TERMS`` distinct terms: each slot's weight is picked by
    comparing its term with the query's terms (distinct, so at most one
    matches and the pick is ``w[term]``), and each doc's slots are summed.
    No postings-long gather, no scatter."""
    # the first QUERY_TERMS nonzero entries, in term order (top_k keeps
    # the lower index first among equals; jnp.nonzero would scatter)
    present, terms = jax.lax.top_k((query != 0).astype(jnp.int32),
                                   min(QUERY_TERMS, query.shape[-1]))
    w = query.astype(jnp.float32) * corpus["idf"]
    weights = w[terms]
    terms = jnp.where(present > 0, terms, _NO_QUERY_TERM)
    ell_term = corpus["ell_term"]
    # a slot whose term is not in the query weighs 0, as its COO posting
    # does; an empty slot adds 0 x 0 / doc_len
    wt = jnp.zeros(ell_term.shape, jnp.float32)
    for i in range(terms.shape[0]):
        wt = jnp.where(ell_term == terms[i], weights[i], wt)
    # one reduce over the slot axis: XLA reads each (8, 128) tile of the
    # ELL once (a slice per slot would read it once per row of the tile),
    # and the CPU adds the slots in order, as the segment-sum adds postings
    scores = jnp.sum(wt * corpus["ell_tf"] / corpus["doc_len"], axis=0)
    # a doc with no postings scores 0, as in the COO path, even where its
    # length is 0 and its empty slots added 0 / 0
    return jnp.where(ell_term[0] >= 0, scores, 0.0)


def ell_select_applies(corpus: dict, query):
    """Whether :func:`_tfidf_scores` takes the ELL select path for
    ``query``: a traced bool (False when the corpus has no ELL)."""
    if "ell_term" not in corpus:
        return jnp.zeros((), jnp.bool_)
    return jnp.count_nonzero(query) <= QUERY_TERMS


@jax.jit
def _tfidf_scores_adaptive(corpus: dict, query):
    return jax.lax.cond(ell_select_applies(corpus, query), _tfidf_scores_ell,
                        _tfidf_scores_coo, corpus, query)


def _tfidf_scores(corpus: dict, query):
    """The ELL select path where the corpus has an ELL and the query is a
    keyword query (decided at run time, both branches compiled together),
    else the COO path."""
    if "ell_term" not in corpus:
        return _tfidf_scores_coo(corpus, query)
    return _tfidf_scores_adaptive(corpus, query)


@jax.custom_batching.custom_vmap
def tfidf_scores(corpus: dict, query):
    """TF-IDF score of every document against a dense query vector."""
    return _tfidf_scores(corpus, query)


@tfidf_scores.def_vmap
def _tfidf_scores_batched(axis_size, in_batched, corpus, query):
    """A batch of queries over one corpus scores one query at a time.

    Plain vmap would carry the batch as the minor dimension of every
    postings-long intermediate (the scatter-add's window), which a TPU
    pads from 4 lanes to 128: 32x the postings bytes, beyond the chip at
    tens of millions of postings.  Mapping keeps each query's footprint
    and bits identical to the unbatched call."""
    corpus_b, query_b = in_batched
    if not any(jax.tree.leaves(corpus_b)):
        if not query_b:
            return _tfidf_scores(corpus, query), False
        return jax.lax.map(lambda q: _tfidf_scores(corpus, q), query), True
    axes = jax.tree.map(lambda b: 0 if b else None, corpus_b)
    return jax.vmap(_tfidf_scores, in_axes=(axes, 0 if query_b else None))(
        corpus, query), True


def tfidf_topk(corpus: dict, query, k: int):
    """Top-k documents by TF-IDF: ``(doc ids, scores, valid)``, each of
    length ``min(k, n_docs)`` — ``k`` is clamped to the document count (the
    true result size) instead of crashing inside ``lax.top_k``."""
    scores = tfidf_scores(corpus, query)
    k = min(int(k), int(scores.shape[0]))
    vals, ids = jax.lax.top_k(scores, k)
    return ids.astype(jnp.int32), vals, jnp.ones((k,), jnp.bool_)


def masked_topk(scores, doc_mask, k: int):
    """Top-k over ``scores`` restricted to ``doc_mask``: masked docs score
    ``-inf`` before the top-k, and result rows whose slot holds a masked
    doc (k exceeds the unmasked count) come back with ``valid=False`` and
    score 0.0 (never ``-inf`` — a downstream mask-weighted aggregate would
    turn ``-inf * 0`` into NaN)."""
    k = min(int(k), int(scores.shape[0]))
    neg = jnp.where(doc_mask, scores, -jnp.inf)
    vals, ids = jax.lax.top_k(neg, k)
    valid = jnp.isfinite(vals)
    return ids.astype(jnp.int32), jnp.where(valid, vals, 0.0), valid


def tfidf_topk_masked(corpus: dict, query, doc_mask, k: int):
    """Dense masked scoring: score the whole corpus, then mask + top-k.
    The always-available realization of a pushed candidate-doc mask (and
    the bitwise reference the block-skipping path must reproduce)."""
    return masked_topk(tfidf_scores(corpus, query), doc_mask, k)


def tfidf_topk_blockskip(corpus: dict, query, doc_mask, k: int,
                         block: int = 8192):
    """Masked scoring that **skips posting blocks whose docs are all
    masked**.  Postings are doc-ordered, so a candidate mask over a
    clustered doc range (recency windows, popularity prefixes) leaves most
    blocks with zero unmasked docs; a prefix-sum over the mask turns each
    block's (first doc, last doc) span into an O(1) activity test, and
    ``lax.cond`` skips the gather + scatter-add for inactive blocks at run
    time.  Active blocks add the *same contributions in the same order* as
    the dense path, so results are bitwise identical.
    """
    n_docs = int(corpus["doc_len"].shape[0])
    doc_ids = corpus["doc_ids"]
    e = int(doc_ids.shape[0])
    if e == 0:
        return masked_topk(jnp.zeros((n_docs,), jnp.float32), doc_mask, k)
    w = query.astype(jnp.float32) * corpus["idf"]
    doc_len = corpus["doc_len"]

    b = max(8, min(int(block), e))
    pad = (-e) % b
    # padded postings carry tf=0 -> contribute exactly +0.0 to doc 0, and
    # pad doc_ids replicate the last (largest) doc id so block spans stay
    # sorted for the prefix-sum activity test
    d_p = jnp.pad(doc_ids, (0, pad), constant_values=int(n_docs - 1))
    t_p = jnp.pad(corpus["term_ids"], (0, pad))
    f_p = jnp.pad(corpus["tf"], (0, pad))
    nb = (e + pad) // b
    d_b = d_p.reshape(nb, b)
    t_b = t_p.reshape(nb, b)
    f_b = f_p.reshape(nb, b)

    # block activity: any unmasked doc inside the block's doc-id span.  The
    # span comes from a per-block min/max (one cheap int pass), so the test
    # stays sound even for corpora whose postings are not doc-sorted; the
    # mask prefix-sum makes each span query O(1).
    prefix = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(doc_mask.astype(jnp.int32))])
    active = (prefix[d_b.max(axis=1) + 1] - prefix[d_b.min(axis=1)]) > 0

    def body(acc, xs):
        d, t, f, act = xs

        def do(a):
            return a.at[d].add(w[t] * f / doc_len[d])

        return jax.lax.cond(act, do, lambda a: a, acc), None

    scores, _ = jax.lax.scan(body, jnp.zeros((n_docs,), jnp.float32),
                             (d_b, t_b, f_b, active))
    return masked_topk(scores, doc_mask, k)
