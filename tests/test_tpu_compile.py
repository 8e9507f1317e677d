"""Compile the tri-store Pallas kernels for a described TPU v5e, at the
widths ``chip_smoke.py`` serves, and pin the backend decisions.

Nothing runs here: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned tiles, too much VMEM, an unlowerable kernel).  The topology is
described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library.
"""
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import backend
from repro.core.ir import HARDWARE_BY_KIND, HardwareSpec, hardware_for
from repro.core.physical import JOIN_PROBE_MAX_BUILD
from repro.stores.graph_kernels import scatter_add_pallas
from repro.stores.masked_kernels import (compact_prefix_pallas,
                                         join_probe_pallas,
                                         masked_segment_agg_pallas,
                                         masked_tfidf_pallas)
from repro.stores.text_store import _tfidf_scores_ell, tfidf_scores

# the deployment widths of chip_smoke.py's tri-model analysis
TWEETS = 1 << 23
HASHTAGS = 1 << 18
EDGES = 1 << 24
POSTINGS = 59_000_000
VOCAB = 1 << 16
ELL_WIDTH = 14          # 7-14 tokens a doc: at most 14 distinct terms


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_for_chip(fn, *args, **static):
    lowered = fn.lower(*args, interpret=False, **static)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


# --------------------------------------------------------------------------
# backend decisions
# --------------------------------------------------------------------------

def test_pallas_interprets_on_cpu():
    assert jax.devices()[0].platform == "cpu"
    assert backend.pallas_interpret() is True


def test_pallas_compiles_for_described_tpu(topo):
    assert backend.pallas_interpret(topo.devices[0]) is False


def test_hardware_table_keys_described_v5e(topo):
    dev = topo.devices[0]
    assert dev.device_kind in HARDWARE_BY_KIND
    assert hardware_for(dev) == HARDWARE_BY_KIND[dev.device_kind]
    assert hardware_for(dev).peak_flops == 197e12


def test_unknown_tpu_kind_raises():
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99 lite")
    with pytest.raises(ValueError, match="TPU v99 lite"):
        hardware_for(dev)


def test_cpu_plans_for_the_v5e():
    assert hardware_for() == HardwareSpec()


def test_compile_cache_dir_follows_env_else_repo(monkeypatch):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert backend.enable_compile_cache() == "/elsewhere/cache"
        # JAX reads the variable itself; the helper sets no other directory
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = backend.enable_compile_cache()
        assert path.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert backend.enable_compile_cache() == path   # fixed, not per run
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


# --------------------------------------------------------------------------
# kernels at the smoke's widths
# --------------------------------------------------------------------------

def test_scatter_add_compiles_for_v5e(one_chip):
    _compile_for_chip(scatter_add_pallas,
                      _spec(one_chip, (EDGES,), jnp.float32),
                      _spec(one_chip, (EDGES,), jnp.int32),
                      num_nodes=HASHTAGS)


def _tfidf_args(one_chip, postings):
    f32 = _spec(one_chip, (postings,), jnp.float32)
    return (_spec(one_chip, (postings,), jnp.int32), f32, f32, f32, f32)


def test_masked_tfidf_compiles_for_v5e(one_chip):
    # half the smoke's corpus at its ~7 postings per doc: the largest
    # power-of-two corpus whose doc-block x posting-block grid the compiler
    # can index
    docs = TWEETS // 2
    _compile_for_chip(masked_tfidf_pallas, *_tfidf_args(one_chip, 7 * docs),
                      n_docs=docs)


def test_masked_tfidf_refuses_grid_beyond_int32(one_chip):
    # the smoke's full corpus: 32768 doc blocks x 115235 posting blocks
    with pytest.raises(ValueError, match="masked_tfidf"):
        masked_tfidf_pallas.lower(*_tfidf_args(one_chip, POSTINGS),
                                  n_docs=TWEETS, interpret=False)


# every tweet, and the k=64 text hits joined back to tweets (the group-by
# the planner hands this kernel at the smoke's sizes)
@pytest.mark.parametrize("rows", [TWEETS, 64])
def test_masked_segment_agg_compiles_for_v5e(one_chip, rows):
    f32 = _spec(one_chip, (rows,), jnp.float32)
    _compile_for_chip(masked_segment_agg_pallas, f32,
                      _spec(one_chip, (rows,), jnp.int32), f32,
                      num_groups=HASHTAGS)


def test_compact_prefix_compiles_for_v5e(one_chip):
    _compile_for_chip(compact_prefix_pallas,
                      _spec(one_chip, (4, TWEETS), jnp.float32),
                      _spec(one_chip, (TWEETS,), jnp.int32),
                      _spec(one_chip, (TWEETS,), jnp.float32),
                      out_capacity=1 << 16)


def test_join_probe_compiles_at_the_planner_gate(one_chip):
    _compile_for_chip(join_probe_pallas,
                      _spec(one_chip, (1 << 16,), jnp.int32),
                      _spec(one_chip, (JOIN_PROBE_MAX_BUILD,), jnp.int32),
                      _spec(one_chip, (JOIN_PROBE_MAX_BUILD,), jnp.bool_))


def test_batched_tfidf_stays_query_major(one_chip):
    # four queries of a batch_param group over an eighth of the smoke's
    # corpus: the batched scorer must stay near one query's footprint (a
    # batch carried as the minor dim of the postings pads 4 lanes to 128
    # on a TPU: ~30 GB at the full corpus)
    postings, docs = POSTINGS // 8, TWEETS // 8
    corpus = {"doc_ids": _spec(one_chip, (postings,), jnp.int32),
              "term_ids": _spec(one_chip, (postings,), jnp.int32),
              "tf": _spec(one_chip, (postings,), jnp.float32),
              "doc_len": _spec(one_chip, (docs,), jnp.float32),
              "idf": _spec(one_chip, (VOCAB,), jnp.float32)}
    one = jax.jit(tfidf_scores).lower(
        corpus, _spec(one_chip, (VOCAB,), jnp.float32)).compile()
    four = jax.jit(jax.vmap(tfidf_scores, in_axes=(None, 0))).lower(
        corpus, _spec(one_chip, (4, VOCAB), jnp.float32)).compile()
    temp1 = one.memory_analysis().temp_size_in_bytes
    temp4 = four.memory_analysis().temp_size_in_bytes
    assert temp4 <= 2 * temp1 + 4 * 4 * docs, (temp1, temp4)


def _deployment_corpus(one_chip):
    """The smoke's whole corpus: COO postings, doc lengths, idf and the
    doc-major padded postings (ELL)."""
    i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)   # noqa: E731
    f32 = lambda *shape: _spec(one_chip, shape, jnp.float32)  # noqa: E731
    return {"doc_ids": i32(POSTINGS), "term_ids": i32(POSTINGS),
            "tf": f32(POSTINGS), "doc_len": f32(TWEETS), "idf": f32(VOCAB),
            "ell_term": i32(ELL_WIDTH, TWEETS),
            "ell_tf": f32(ELL_WIDTH, TWEETS)}


def test_unmasked_tfidf_compiles_for_v5e_at_deployment_width(one_chip):
    # both paths of the adaptive scoring, the select and the COO fallback
    jax.jit(tfidf_scores).lower(
        _deployment_corpus(one_chip),
        _spec(one_chip, (VOCAB,), jnp.float32)).compile()


def test_ell_select_holds_no_scatter_nor_long_gather(one_chip):
    hlo = jax.jit(_tfidf_scores_ell).lower(
        _deployment_corpus(one_chip),
        _spec(one_chip, (VOCAB,), jnp.float32)).compile().as_text()
    assert not re.search(r"\sscatter\(", hlo)
    gathers = [ln for ln in hlo.splitlines() if re.search(r"\sgather\(", ln)]
    assert gathers, "the query weights' gather is gone: check the reading"
    for ln in gathers:
        # no operand or index as long as the docs (or the postings)
        for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", ln):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            assert n < TWEETS, ln
