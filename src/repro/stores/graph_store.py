"""CSR graph store: adjacency + frontier ops.

The store keeps the graph in CSR (``indptr``/``indices`` over source
vertices) plus the per-edge source expansion (``src``) so one sparse
matrix-vector product — the core of every frontier op — is

    y[v] = Σ_{e: dst[e]=v} x[src[e]] · w[e]

i.e. an XLA gather followed by a scatter-add.  Two scatter-add backends
exist: ``jax.ops.segment_sum`` (the portable fallback, any engine) and the
Pallas one-hot-matmul kernel (:mod:`.graph_kernels`), which the planner
offers as a candidate when the ``pallas`` engine is enabled.

Frontier ops built on the SpMV:

  * :func:`expand_frontier` — k-hop expansion of a weighted frontier;
  * :func:`pagerank`        — damped (optionally personalized) power
    iteration with out-degree normalization;
  * :func:`triangle_count`  — Σ(A ∘ A²)/6 over the densified adjacency
    (small-graph realization; the CSR stays the source of truth).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ir import GraphT, ValidationError
from ..core.ledger import register_store_payload
from .graph_kernels import scatter_add_pallas


class GraphStore:
    """Host-side CSR container built from an edge list."""

    def __init__(self, indptr, indices, src, weights, n_nodes: int,
                 shards: int = 1):
        self.indptr = np.asarray(indptr, np.int32)
        self.indices = np.asarray(indices, np.int32)
        self.src = np.asarray(src, np.int32)
        self.weights = np.asarray(weights, np.float32)
        self.n_nodes = int(n_nodes)
        self.n_edges = int(self.indices.shape[0])
        self.shards = int(shards)
        if self.shards < 1:
            raise ValidationError(f"shards {self.shards} < 1")
        if self.n_nodes % self.shards:
            raise ValidationError(
                f"shards {self.shards} must divide n_nodes {self.n_nodes}; "
                f"pad the node domain (with_shards pads automatically)")
        # monotonic content version (parity with Column/Text stores): bumped
        # by any future mutation; the ledger snapshots it per payload so
        # consumers pinning stale payloads are flagged as leaks
        self.version = 0

    def with_shards(self, shards: int) -> "GraphStore":
        """This graph re-declared as dst-block partitioned over ``shards``
        mesh slices.  The node domain pads up to a shard multiple with
        isolated (edgeless) vertices; `payload()` then additionally carries
        the dst-block edge arrays the block-partitioned SpMV runs on."""
        n = self.n_nodes + (-self.n_nodes) % int(shards)
        indptr = self.indptr
        if n != self.n_nodes:
            pad = np.full(n - self.n_nodes, self.indptr[-1], np.int32)
            indptr = np.concatenate([self.indptr, pad])
        out = GraphStore(indptr, self.indices, self.src, self.weights, n,
                         shards=int(shards))
        out.version = self.version
        return out

    @classmethod
    def from_edges(cls, src, dst, n_nodes: int, weights=None,
                   symmetric: bool = False) -> "GraphStore":
        """Build CSR from COO edges.  ``symmetric=True`` mirrors every edge
        (undirected graphs — what triangle counting expects)."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        if src.shape != dst.shape:
            raise ValidationError(f"edge arrays differ: {src.shape} vs "
                                  f"{dst.shape}")
        w = (np.ones(src.shape, np.float32) if weights is None
             else np.asarray(weights, np.float32))
        if w.shape != src.shape:
            raise ValidationError(
                f"weights shape {w.shape} != edges {src.shape}")
        if symmetric:
            src, dst, w = (np.concatenate([src, dst]),
                           np.concatenate([dst, src]),
                           np.concatenate([w, w]))
        if src.size and (src.min() < 0 or src.max() >= n_nodes
                         or dst.min() < 0 or dst.max() >= n_nodes):
            raise ValidationError("edge endpoint out of range")
        order = np.argsort(src, kind="stable")
        src, dst, w = src[order], dst[order], w[order]
        counts = np.bincount(src, minlength=n_nodes)
        indptr = np.concatenate([[0], np.cumsum(counts)])
        return cls(indptr, dst, src, w, n_nodes)

    @property
    def type(self) -> GraphT:
        return GraphT(self.n_nodes, self.n_edges,
                      weighted=bool((self.weights != 1.0).any()),
                      partitioning="block" if self.shards > 1 else None)

    def payload(self) -> dict:
        out_deg = np.maximum(np.diff(self.indptr), 1).astype(np.float32)
        out = {
            "indptr": jnp.asarray(self.indptr),
            "indices": jnp.asarray(self.indices),   # dst per edge
            "src": jnp.asarray(self.src),           # src per edge
            "weights": jnp.asarray(self.weights),
            "out_deg": jnp.asarray(out_deg),
        }
        if self.shards > 1:
            out.update(self._block_payload())
        register_store_payload(self, out, "graph_store")
        return out

    def _block_payload(self) -> dict:
        """Dst-block edge partition for the block-partitioned SpMV: shard d
        owns dst nodes ``[d*n/s, (d+1)*n/s)`` and exactly the edges landing
        there.  The selection is *stable* over the CSR (src-sorted) edge
        order, so within every dst segment the contribution order matches
        the dense SpMV — block-partitioned segment sums stay bitwise equal.
        ``blk_pos`` keeps each edge's CSR position, which picks its
        accumulator lane as in the dense SpMV.  Blocks pad to the max block
        edge count; pad slots carry ``dst_local = n_local`` (an out-of-range
        segment id: scatters drop it) and weight 0."""
        s, n = self.shards, self.n_nodes
        n_local = n // s
        block = self.indices // n_local                # dst block per edge
        counts = np.bincount(block, minlength=s)
        e_max = max(int(counts.max()) if counts.size else 0, 1)
        src_b = np.zeros((s, e_max), np.int32)
        dstl_b = np.full((s, e_max), n_local, np.int32)    # pad -> dropped
        w_b = np.zeros((s, e_max), np.float32)
        pos_b = np.zeros((s, e_max), np.int32)
        order = np.argsort(block, kind="stable")       # dst-block grouping
        starts = np.concatenate([[0], np.cumsum(counts)])
        for d in range(s):
            sel = order[starts[d]:starts[d + 1]]
            src_b[d, :sel.size] = self.src[sel]
            dstl_b[d, :sel.size] = self.indices[sel] - d * n_local
            w_b[d, :sel.size] = self.weights[sel]
            pos_b[d, :sel.size] = sel
        return {
            "blk_src": jnp.asarray(src_b.reshape(-1)),
            "blk_dst_local": jnp.asarray(dstl_b.reshape(-1)),
            "blk_weights": jnp.asarray(w_b.reshape(-1)),
            "blk_pos": jnp.asarray(pos_b.reshape(-1)),
        }


# --------------------------------------------------------------------------
# frontier kernels (pure functions over the payload)
# --------------------------------------------------------------------------


# A hub's in-edges number in the millions at deployment size.  Summed into
# one float32 accumulator, its small addends round away: at 13.4M edges the
# hub's PageRank came out 0.8% low against float64 (TPU v5e; the CPU's
# sequential scatter loses as much).  Segment sums therefore spread every
# node over ``accumulator_lanes`` accumulators, one per edge position mod
# lanes, and add the lanes at the end.  Graphs under 2^16 edges keep one
# accumulator: the plain scatter, bit for bit.
_EDGES_PER_LANE = 1 << 15
_MAX_LANES = 128
_MAX_LANE_SLOTS = 1 << 26     # nodes x lanes float32 accumulators: 256 MB


def accumulator_lanes(n_edges: int, n_nodes: int) -> int:
    """Accumulators per node for an SpMV over ``n_edges`` edges: a power
    of two, about one per 2^15 edges, at most 128."""
    lanes = 1
    while (lanes < _MAX_LANES and 2 * lanes * _EDGES_PER_LANE <= n_edges
           and 2 * lanes * n_nodes <= _MAX_LANE_SLOTS):
        lanes *= 2
    return lanes


def lane_segment_sum(vals, seg, n: int, lanes: int, pos=None):
    """``segment_sum(vals, seg, n)`` accumulated in ``lanes`` partial sums
    per segment, value ``i`` into lane ``pos[i] % lanes`` (``pos`` defaults
    to the value's index).  Out-of-range segment ids are dropped."""
    if lanes == 1:
        return jax.ops.segment_sum(vals, seg, num_segments=n)
    if pos is None:
        pos = jnp.arange(vals.shape[0], dtype=jnp.int32)
    acc = jax.ops.segment_sum(vals, seg * lanes + pos % lanes,
                              num_segments=n * lanes)
    return acc.reshape(n, lanes).sum(axis=1)


def _spmv(g: dict, x, scatter: Optional[Callable] = None):
    n = g["indptr"].shape[0] - 1
    vals = x[g["src"]] * g["weights"]
    if scatter is not None:
        return scatter(vals, g["indices"], n)
    lanes = accumulator_lanes(int(vals.shape[0]), n)
    return lane_segment_sum(vals, g["indices"], n, lanes)


def _pallas_scatter(vals, dst, n):
    return scatter_add_pallas(vals, dst, num_nodes=n)


def expand_frontier(g: dict, frontier, hops: int = 1,
                    use_pallas: bool = False):
    """k-hop expansion: propagate frontier weight along edges ``hops``
    times.  One hop is exactly one SpMV."""
    scatter = _pallas_scatter if use_pallas else None
    x = frontier.astype(jnp.float32)
    for _ in range(int(hops)):
        x = _spmv(g, x, scatter)
    return x


def _spmv_blockskip(src_b, dst_b, w_b, n: int, lanes: int, x, active_of):
    """One SpMV that skips edge blocks whose source nodes are all zero in
    ``x``.  Skipped edges would contribute exactly ``x[src]*w == +0.0``, so
    the result is bitwise identical to the dense SpMV (same contributions
    into the same accumulator lanes, same scatter order); the activity test
    is recomputed from the *current* frontier, so later hops skip less as
    the frontier densifies."""
    active = active_of(x)
    nb, b = src_b.shape

    def body(acc, xs):
        s, d, w, act, blk = xs

        def do(a):
            if lanes == 1:
                return a.at[d].add(x[s] * w)
            pos = blk * b + jnp.arange(b, dtype=jnp.int32)
            return a.at[d * lanes + pos % lanes].add(x[s] * w)

        return jax.lax.cond(act, do, lambda a: a, acc), None

    y, _ = jax.lax.scan(body, jnp.zeros((n * lanes,), jnp.float32),
                        (src_b, dst_b, w_b, active,
                         jnp.arange(nb, dtype=jnp.int32)))
    return y if lanes == 1 else y.reshape(n, lanes).sum(axis=1)


def _blockskip_env(g: dict, block: int):
    """Edge-blocked CSR view + the O(1) per-block activity test shared by
    every block-skipping SpMV (frontier expansion, first-iteration
    PageRank).  Returns ``(src_b, dst_b, w_b, n, lanes, active_of)`` or
    None for an edgeless graph."""
    n = int(g["indptr"].shape[0]) - 1
    src, dst, w = g["src"], g["indices"], g["weights"]
    e = int(src.shape[0])
    if e == 0:
        return None
    b = max(8, min(int(block), e))
    pad = (-e) % b
    # padded edges carry weight 0 -> contribute exactly +0.0
    src_p = jnp.pad(src, (0, pad), constant_values=int(n - 1))
    dst_p = jnp.pad(dst, (0, pad))
    w_p = jnp.pad(w, (0, pad))
    nb = (e + pad) // b
    src_b = src_p.reshape(nb, b)
    dst_b = dst_p.reshape(nb, b)
    w_b = w_p.reshape(nb, b)
    lo = src_b.min(axis=1)
    hi = src_b.max(axis=1)

    def active_of(xc):
        nz = (xc != 0).astype(jnp.int32)
        prefix = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(nz)])
        return (prefix[hi + 1] - prefix[lo]) > 0

    return src_b, dst_b, w_b, n, accumulator_lanes(e, n), active_of


def expand_frontier_blockskip(g: dict, frontier, hops: int = 1,
                              block: int = 2048):
    """Frontier expansion under a pushed selection mask: per-hop SpMV with
    edge-block skipping.  Edges are CSR-sorted by source, so a frontier
    whose support clusters (popular low-id hashtags, recent suffixes)
    leaves most blocks with no active source; a prefix-sum over the
    frontier's nonzero mask turns each block's source span into an O(1)
    activity test."""
    x = frontier.astype(jnp.float32)
    env = _blockskip_env(g, block)
    if env is None:
        n = int(g["indptr"].shape[0]) - 1
        return jnp.zeros((n,), jnp.float32) if hops else x
    src_b, dst_b, w_b, n, lanes, active_of = env
    for _ in range(int(hops)):
        x = _spmv_blockskip(src_b, dst_b, w_b, n, lanes, x, active_of)
    return x


def pagerank(g: dict, iters: int = 10, damping: float = 0.85,
             personalization=None, use_pallas: bool = False,
             skip_first: bool = False,
             block: int = 2048):
    """Damped power iteration with out-degree normalization.

    ``skip_first=True`` is the personalization-sparsity pushdown: iteration
    0's SpMV input is exactly the (normalized) personalization vector, so
    when a pushed selection mask makes it sparse, the first iteration runs
    as a block-skipping SpMV driven by its nonzero support.  Skipped edges
    would contribute exactly ``+0.0``, so the result is bitwise identical
    to the dense iteration; later iterations (whose rank vector is dense
    after one propagation) stay on the dense SpMV."""
    scatter = _pallas_scatter if use_pallas else None
    n = g["indptr"].shape[0] - 1
    if personalization is None:
        p0 = jnp.full((n,), 1.0 / n, jnp.float32)
    else:
        p = personalization.astype(jnp.float32)
        p0 = p / jnp.maximum(jnp.sum(p), 1e-30)
    env = (_blockskip_env(g, block)
           if skip_first and personalization is not None else None)
    r = p0
    for it in range(int(iters)):
        xs = r / g["out_deg"]
        if it == 0 and env is not None:
            src_b, dst_b, w_b, _n, lanes, active_of = env
            y = _spmv_blockskip(src_b, dst_b, w_b, n, lanes, xs, active_of)
        else:
            y = _spmv(g, xs, scatter)
        r = (1.0 - damping) * p0 + damping * y
    return r


def triangle_count(g: dict):
    """Triangles in the (symmetric, simple) graph: Σ(A ∘ A²)/6."""
    n = g["indptr"].shape[0] - 1
    a = jnp.zeros((n, n), jnp.float32).at[g["src"], g["indices"]].set(1.0)
    return jnp.sum(a * (a @ a)) / 6.0
