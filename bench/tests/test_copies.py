"""The benchmark's copies agree with the originals they were taken from
(``examples/tri_model_analysis.py``, ``stores/ref.py``, ``chip_smoke.py``),
and its generator is deterministic in the seed."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, generator, ref_lm, social
from bench.drivers.analysis import TINY_DATA, hidden_failures
from bench.drivers.lm import TINY_LM
from bench.ref_analysis import Reference, analysis_error, score_error

SIZES = dict(TINY_DATA, doc_tokens=tuple(TINY_DATA["doc_tokens"]))


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(common.ROOT))
    import chip_smoke
    return chip_smoke


@pytest.fixture(scope="module")
def data():
    return social.build_social_data(np.random.default_rng(3), **SIZES)


def test_generator_is_the_examples(smoke, data):
    from tri_model_analysis import build_social_data
    table, graph, corpus = build_social_data(np.random.default_rng(3),
                                             **SIZES)
    t2, g2, c2 = social.build_stores(data)
    for col in ("user", "hashtag", "doc", "engagement"):
        np.testing.assert_array_equal(table.column(col), t2.column(col))
    for a in ("indptr", "indices", "src", "weights"):
        np.testing.assert_array_equal(getattr(graph, a), getattr(g2, a))
    for a in ("doc_ids", "term_ids", "tf", "doc_len", "idf"):
        np.testing.assert_array_equal(getattr(corpus, a), getattr(c2, a))


def test_generator_is_deterministic_in_the_seed():
    a = social.build_social_data(generator.run_rng(2**31 + 7), **SIZES)
    b = social.build_social_data(generator.run_rng(2**31 + 7), **SIZES)
    c = social.build_social_data(generator.run_rng(2**31 + 8), **SIZES)
    for f in ("hashtag", "engagement", "edge_a", "post_term", "post_tf"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.post_term[:1000], c.post_term[:1000])


def test_traffic_schedule_is_the_same_for_every_seed():
    t = common.traffic_of({"name": "pulse-8m.mixed"})
    a = generator.analysis_arrivals(t, 30)
    assert a == generator.analysis_arrivals(t, 30)
    assert all(0 < x["offset"] < 30 for x in a)
    assert sum(x["batch"] for x in a) == round(len(a) * t["batch_share"])
    lm = common.traffic_of({"name": "qwen3-0.6b.chat"})
    la = generator.lm_arrivals(lm, 30, 5, 151936)
    lb = generator.lm_arrivals(lm, 30, 6, 151936)
    # the seed draws the tokens; offsets and lengths are the schedule's
    assert [(x["offset"], len(x["prompt"]), x["gen"]) for x in la] == \
        [(x["offset"], len(x["prompt"]), x["gen"]) for x in lb] == \
        [tuple(x) for x in generator.lm_lengths(lm, 30)]
    assert la != lb
    assert la == generator.lm_arrivals(lm, 30, 5, 151936)
    assert all(len(x["prompt"]) + x["gen"] <= lm["max_total"] for x in la)


def test_reference_is_the_smokes_composition(smoke, data):
    table, graph, corpus = social.build_stores(data)
    ref = Reference(data)
    pr = smoke.graph_reference(table, graph)
    np.testing.assert_allclose(ref.pr, pr, rtol=1e-9, atol=1e-15)
    rng = np.random.default_rng(0)
    for _ in range(3):
        terms = rng.zipf(1.4, 6) % data.vocab
        q = social.query_vector(terms, data.vocab)
        np.testing.assert_array_equal(q, corpus.query_vector(terms))
        trel, kth, spare = smoke.text_reference(table, corpus,
                                                graph.n_nodes, q)
        got, kth2, spare2 = ref.text_half(q)
        np.testing.assert_allclose(got, trel, rtol=1e-5)
        assert (kth2, spare2) == pytest.approx((kth, spare), rel=1e-6)


def test_error_measure_is_the_smokes(smoke):
    rng = np.random.default_rng(1)
    want = rng.random(50) + 1.0
    for scale in (1e-6, 1e-5, 1e-3):
        got = want * (1 + scale * rng.standard_normal(50))
        assert score_error(got, want) == pytest.approx(
            smoke.score_error(got, want))
    kth = 0.5
    moved = want.copy()
    moved[3] += kth
    moved[7] -= kth
    assert analysis_error(moved, want, kth, 2) == \
        smoke.analysis_error(moved, want, kth, 2)
    assert analysis_error(moved, want, kth, 2)[0] < 1.0
    assert analysis_error(moved, want, kth, 0)[0] > 1.0


def test_lm_reference_is_the_smokes(smoke):
    from repro.configs.base import get_smoke_config
    from repro.models import build_model
    model = build_model(get_smoke_config("qwen3-0.6b"))
    cfg = dict(TINY_LM, rms_norm_eps=1e-6, rope_theta=1e6,
               deployment={"param_dtype": "float32"})
    params = ref_lm.make_params(cfg, 5)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, 24),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = smoke.reference_logits(model.cfg, params, toks)
        got = ref_lm.forward(cfg, params, toks) @ \
            params["embed"]["table"][:512].T
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_hidden_failures_are_the_smokes(smoke):
    from repro.core.ledger import FlightRecorder
    rec = FlightRecorder(capacity=16)
    assert hidden_failures(rec) == []
    smoke.check_no_hidden_failures(rec)
    rec.record("batch_fallback", {"n": 2, "error": "boom"})
    assert hidden_failures(rec) == [("batch_fallback", "boom")]
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_no_hidden_failures(rec)


def test_every_seed_gives_the_same_shapes_after_the_cut():
    from bench.drivers.analysis import TINY_CUT
    types = []
    for seed in (2**31 + 1, 2**32 + 3):
        d = social.build_social_data(generator.run_rng(seed), **SIZES)
        types.append([s.type for s in social.build_stores(d.cut(**TINY_CUT))])
    assert types[0] == types[1]
    d = social.build_social_data(generator.run_rng(5), **SIZES)
    with pytest.raises(ValueError):
        d.cut(postings=d.post_doc.shape[0] + 1, edges=1)


def test_weights_are_made_in_the_served_type():
    cfg = dict(TINY_LM, deployment={"param_dtype": "bfloat16"})
    low = ref_lm.make_params(cfg, 7)
    full = ref_lm.make_params(dict(cfg, deployment={"param_dtype":
                                                    "float32"}), 7)
    assert {x.dtype for x in jax.tree.leaves(low)} == {jnp.dtype("bfloat16")}
    # the same draw, rounded once; the reference reads it exactly
    for a, b in zip(jax.tree.leaves(ref_lm._f32(low)),
                    jax.tree.leaves(full)):
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b.astype(jnp.bfloat16)
                                      .astype(jnp.float32)))
