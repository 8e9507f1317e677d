"""The one traffic generator: reads a traffic file's parameters and draws
a run's arrivals, queries and lengths.

Two random streams.  The *shape* stream, seeded by the traffic file's
``shape_seed``, draws the schedule: the gaps between arrivals, the
popularity rank, batch flag and tenant of each analysis, the lengths of
each LM request, in order.  The *run* stream, seeded by ``--seed``, draws
the content: the query terms and token ids (and the data, in the
drivers).  So every seed gets the same work in the same order: on the
chip a seed's order alone moved a cell's throughput by a fifth while
repeats of one seed agreed exactly.  A traffic file holds only data; the
keys it may use are read below.
"""
from __future__ import annotations

import numpy as np


def run_rng(seed: int, stream: int = 0):
    return np.random.default_rng([int(seed), stream])


def shape_rng(traffic: dict):
    return np.random.default_rng(int(traffic["shape_seed"]))


def seed32(seed: int) -> int:
    """A 32-bit key for JAX's generator from any whole-number seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def poisson_gaps(traffic: dict, seconds: float, shape) -> np.ndarray:
    """Gaps of a Poisson process at ``rate_per_s`` whose arrivals all fall
    inside ``seconds``."""
    rate = float(traffic["rate_per_s"])
    n = int(rate * seconds * 2 + 64)
    gaps = shape.exponential(1.0 / rate, n)
    keep = np.cumsum(gaps) < seconds
    return gaps[keep]


def open_loop_offsets(traffic: dict, seconds: float):
    """Arrival offsets (seconds from the window's start), and the shape
    generator for the draws that follow."""
    shape = shape_rng(traffic)
    return np.cumsum(poisson_gaps(traffic, seconds, shape)), shape


def zipf_terms(rng, n_terms: int, exponent: float, vocab: int):
    return rng.zipf(exponent, n_terms) % vocab


def ranks(shape, n: int, pool: int, exponent: float) -> np.ndarray:
    """``n`` popularity ranks in [0, pool), rank r drawn with weight
    (r + 1) ** -exponent."""
    w = (np.arange(pool) + 1.0) ** -float(exponent)
    return shape.choice(pool, n, p=w / w.sum())


def analysis_arrivals(traffic: dict, seconds: float) -> list:
    """Open-loop analysis requests: one dict per arrival with ``offset``,
    ``query`` (index into the pool, whose queries the run's seed draws),
    ``batch`` (declares the query ``batch_param``) and ``tenant``."""
    offs, shape = open_loop_offsets(traffic, seconds)
    n = len(offs)
    pool = traffic["query_pool"]
    q = ranks(shape, n, pool["size"], pool["popularity_zipf"])
    batch = shape.permutation(np.arange(n) < round(n * traffic["batch_share"]))
    tenant = shape.permutation(np.arange(n) % traffic["tenants"])
    return [{"offset": float(o), "query": int(k), "batch": bool(b),
             "tenant": int(t)} for o, k, b, t in zip(offs, q, batch, tenant)]


def query_terms(traffic: dict, rng, vocab: int) -> np.ndarray:
    spec = traffic["query"]
    return zipf_terms(rng, spec["terms"], spec["term_zipf"], vocab)


def _lognormal_lengths(shape, n: int, spec: dict) -> np.ndarray:
    x = shape.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def lm_lengths(traffic: dict, seconds: float) -> list:
    """(offset, prompt length, gen) of each LM request, in order,
    prompt + gen <= ``max_total``."""
    offs, shape = open_loop_offsets(traffic, seconds)
    n = len(offs)
    prompt = _lognormal_lengths(shape, n, traffic["prompt"])
    gen = _lognormal_lengths(shape, n, traffic["output"])
    gen = np.minimum(gen, traffic["max_total"] - prompt)
    return list(zip(offs.tolist(), prompt.tolist(), gen.tolist()))


def lm_arrivals(traffic: dict, seconds: float, seed: int,
                vocab: int) -> list:
    """Open-loop LM requests: ``offset``, ``prompt`` (token ids from the
    seed) and ``gen`` (tokens to generate)."""
    toks = run_rng(seed, 2)
    return [{"offset": o, "prompt": toks.integers(0, vocab, p).tolist(),
             "gen": g} for o, p, g in lm_lengths(traffic, seconds)]
