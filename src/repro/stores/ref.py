"""Pure-NumPy references for the store kernels.

Used by the property tests and by ``chip_smoke.py``: every JAX/Pallas store
kernel must agree with the straightforward NumPy computation below.  The
references that run at deployment size (tens of millions of rows) are
vectorized; ``tests/test_stores.py`` holds them to per-row loops.
"""
from __future__ import annotations

import numpy as np


def hash_join_ref(lkeys, rkeys):
    """Reference equi-join probe against a unique-key build side (a
    repeated build key resolves to its last row)."""
    lkeys = np.asarray(lkeys, np.int64)
    rkeys = np.asarray(rkeys, np.int64)
    if rkeys.size == 0:
        return np.zeros(lkeys.shape, np.int64), np.zeros(lkeys.shape, bool)
    uniq, first_rev = np.unique(rkeys[::-1], return_index=True)
    last = rkeys.size - 1 - first_rev
    pos = np.minimum(np.searchsorted(uniq, lkeys), uniq.size - 1)
    matched = uniq[pos] == lkeys
    return np.where(matched, last[pos], 0).astype(np.int64), matched


def join_probe_ref(lkeys, rkeys, rvalid):
    """Reference unique-build probe that excludes invalid build rows:
    matches :func:`repro.stores.masked_kernels.join_probe_pallas`
    (unmatched probe rows report index 0)."""
    lkeys = np.asarray(lkeys)
    lut = {int(k): i for i, k in enumerate(np.asarray(rkeys))
           if bool(np.asarray(rvalid)[i])}
    idx = np.zeros(lkeys.shape, np.int64)
    matched = np.zeros(lkeys.shape, bool)
    for i, k in enumerate(lkeys):
        j = lut.get(int(k))
        if j is not None:
            idx[i] = j
            matched[i] = True
    return idx, matched


def bounded_join_ref(lkeys, lmask, rkeys, rmask, capacity):
    """Reference non-unique-build equi-join into a capacity-bounded,
    validity-prefixed output.

    Output slots enumerate matches by probe row and, within one probe row,
    by the build side's stable (key, original index) order — exactly the
    order :func:`repro.stores.column_store.hash_join_nonunique` produces.
    Returns ``(lidx, ridx, valid, count, overflow)``.
    """
    lkeys = np.asarray(lkeys)
    rkeys = np.asarray(rkeys)
    lmask = np.asarray(lmask, bool)
    rmask = np.asarray(rmask, bool)
    order = np.argsort(rkeys, kind="stable")
    pairs = []
    for i in range(lkeys.shape[0]):
        if not lmask[i]:
            continue
        for r in order:
            if rmask[r] and int(rkeys[r]) == int(lkeys[i]):
                pairs.append((i, int(r)))
    total = len(pairs)
    count = min(total, capacity)
    lidx = np.zeros(capacity, np.int64)
    ridx = np.zeros(capacity, np.int64)
    valid = np.zeros(capacity, bool)
    for j, (i, r) in enumerate(pairs[:capacity]):
        lidx[j], ridx[j], valid[j] = i, r, True
    return lidx, ridx, valid, count, total > capacity


def compact_ref(cols, valid, capacity):
    """Reference stable prefix compaction of a column dict: valid rows in
    original order, truncated to ``capacity`` (overflow flagged).  Invalid
    output slots replicate row 0, mirroring the gather realization."""
    valid = np.asarray(valid, bool)
    idx = np.flatnonzero(valid)
    overflow = idx.shape[0] > capacity
    idx = idx[:capacity]
    count = idx.shape[0]
    pad = np.zeros(capacity - count, np.int64)
    take = np.concatenate([idx, pad]).astype(np.int64)
    out = {k: np.asarray(v)[take] for k, v in cols.items()}
    out_valid = np.arange(capacity) < count
    return out, out_valid, count, overflow


def group_agg_ref(values, keys, num_groups, mask, fn):
    """Reference mask-respecting groupby aggregate.

    ``fn="max"`` returns ``(values, valid)`` — an all-masked group is
    *invalid* (value slot 0.0), never conflated with a true max of 0.0;
    mirrors :func:`repro.stores.column_store.group_agg`.
    """
    if fn not in ("count", "sum", "mean", "max"):
        raise ValueError(fn)
    keys = np.asarray(keys)
    sel = np.asarray(mask, bool) & (keys >= 0) & (keys < num_groups)
    k = keys[sel].astype(np.int64)
    counts = np.bincount(k, minlength=num_groups).astype(np.float64)
    if fn == "count":
        return counts.astype(np.float32)
    v = np.asarray(values, np.float64)[sel]
    if fn == "max":
        out = np.full(num_groups, -np.inf)
        np.maximum.at(out, k, v)
        valid = (counts > 0) & np.isfinite(out)
        return np.where(valid, out, 0.0).astype(np.float32), valid
    sums = np.bincount(k, weights=v, minlength=num_groups)
    if fn == "mean":
        sums = np.where(counts > 0, sums / np.maximum(counts, 1.0), 0.0)
    return sums.astype(np.float32)


def spmv_ref(src, dst, weights, n_nodes, x):
    """y[v] = sum over edges (u -> v) of x[u] * w."""
    y = np.zeros(n_nodes, np.float64)
    np.add.at(y, np.asarray(dst), np.asarray(x, np.float64)[src]
              * np.asarray(weights, np.float64))
    return y


def expand_ref(src, dst, weights, n_nodes, frontier, hops=1):
    x = np.asarray(frontier, np.float64)
    for _ in range(hops):
        x = spmv_ref(src, dst, weights, n_nodes, x)
    return x


def pagerank_ref(src, dst, weights, n_nodes, iters=10, damping=0.85,
                 personalization=None):
    counts = np.bincount(np.asarray(src), minlength=n_nodes)
    out_deg = np.maximum(counts, 1).astype(np.float64)
    if personalization is None:
        p0 = np.full(n_nodes, 1.0 / n_nodes)
    else:
        p = np.asarray(personalization, np.float64)
        p0 = p / max(p.sum(), 1e-30)
    r = p0.copy()
    for _ in range(iters):
        r = (1 - damping) * p0 + damping * spmv_ref(
            src, dst, weights, n_nodes, r / out_deg)
    return r


def triangle_count_ref(src, dst, n_nodes):
    a = np.zeros((n_nodes, n_nodes))
    a[np.asarray(src), np.asarray(dst)] = 1.0
    return float((a * (a @ a)).sum() / 6.0)


def tfidf_scores_ref(doc_ids, term_ids, tf, doc_len, idf, query):
    d = np.asarray(doc_ids)
    t = np.asarray(term_ids)
    contrib = (np.asarray(query, np.float64)[t]
               * np.asarray(idf, np.float64)[t]
               * np.asarray(tf, np.float64)
               / np.asarray(doc_len, np.float64)[d])
    return np.bincount(d, weights=contrib, minlength=len(doc_len))


def masked_tfidf_scores_ref(doc_ids, term_ids, tf, doc_len, idf, query,
                            doc_mask):
    """Masked scoring: only unmasked docs accumulate (masked stay 0)."""
    scores = tfidf_scores_ref(doc_ids, term_ids, tf, doc_len, idf, query)
    return np.where(np.asarray(doc_mask, bool), scores, 0.0)


def masked_topk_ref(scores, doc_mask, k):
    """Reference masked top-k: ``(doc ids, scores, valid)`` of length
    ``min(k, n)``; slots past the unmasked count are invalid with score 0,
    ties broken by lowest doc id (matches ``lax.top_k``)."""
    scores = np.asarray(scores, np.float32)
    m = np.asarray(doc_mask, bool)
    k = min(int(k), scores.shape[0])
    neg = np.where(m, scores, -np.inf).astype(np.float32)
    ids = np.argsort(-neg, kind="stable")[:k]
    vals = neg[ids]
    valid = np.isfinite(vals)
    return (ids.astype(np.int32), np.where(valid, vals, 0.0).astype(
        np.float32), valid)


def masked_segment_agg_ref(vals, keys, maskw, num_groups):
    """Reference mask-weighted group-by ``(sums, counts)``."""
    sums = np.zeros(num_groups, np.float64)
    counts = np.zeros(num_groups, np.float64)
    for v, g, w in zip(np.asarray(vals, np.float64), np.asarray(keys),
                       np.asarray(maskw, np.float64)):
        sums[g] += v * w
        counts[g] += w
    return sums.astype(np.float32), counts.astype(np.float32)
