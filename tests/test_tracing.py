"""EXPLAIN ANALYZE observability: the span tracer (nesting, thread
safety, off-by-default zero-overhead path), the merged predicted-vs-
observed report, the single-transfer count sink, the trace exporters
(Chrome-trace / JSON-lines), cost-model refitting from traces, the shared
serving metrics registry, and the guard on what tracing adds to the
served path."""
import io
import json
import threading

import numpy as np
import jax
import pytest

from repro.core.adil import Analysis
from repro.core.feedback import SelectivityFeedback, fit_weights
from repro.core.ir import SystemCatalog, standard_catalog
from repro.core.tracing import (RunTrace, Tracer, resolve_counts,
                                tree_bytes, validate_chrome_trace,
                                xfer_wire_bytes)
from repro.serving.metrics import (MetricsRegistry, ServingMetrics, Summary)
from repro.stores import ColumnStore, store_engines

CAT = standard_catalog()


# --------------------------------------------------------------------------
# workload: a small windowed rollup (filter -> join -> group -> tensor)
# --------------------------------------------------------------------------


def build_rollup(tweets=20_000, hashtags=256, selectivity=0.1, metrics=2):
    rng = np.random.RandomState(0)
    cols = {"hashtag": (rng.zipf(1.3, tweets) % hashtags).astype(np.int32),
            "doc": np.arange(tweets, dtype=np.int32),
            "ts": np.arange(tweets, dtype=np.int32)}
    for i in range(metrics):
        cols[f"m{i}"] = rng.rand(tweets).astype(np.float32)
    table = ColumnStore(cols)
    dims = ColumnStore({"hashtag": np.arange(hashtags, dtype=np.int32),
                        "weight": rng.rand(hashtags).astype(np.float32)})
    cut = int(tweets * (1.0 - selectivity))
    with Analysis(f"trace_rollup_{tweets}_{selectivity}", CAT) as a:
        tw = a.bind("tweets", table)
        dm = a.bind("dims", dims)
        t = a.op("rel_scan", tw)
        recent = a.op("rel_filter", t, col="ts", cmp="ge", value=cut,
                      selectivity=selectivity)
        j = a.op("rel_join", recent, dm, left_on="hashtag",
                 right_on="hashtag")
        aggs = tuple((f"s{i}", "sum", f"m{i}") for i in range(metrics))
        roll = a.op("rel_group_agg", j, key="hashtag", num_groups=hashtags,
                    aggs=aggs)
        out = a.op("col_tensor", roll, col="s0", dim="nodes")
        a.store(out)
    inputs = {"tweets": table.payload(), "dims": dims.payload()}
    return a, inputs


def compile_rollup(**kw):
    a, inputs = build_rollup(**kw)
    planned = a.compile(SystemCatalog(), engines=store_engines(),
                        cache=False)
    return planned, inputs


# --------------------------------------------------------------------------
# the tracer itself
# --------------------------------------------------------------------------


def test_span_nesting_parent_ids():
    tr = Tracer()
    with tr.span("outer") as o:
        with tr.span("mid") as m:
            with tr.span("inner") as i:
                pass
    by = {s.name: s for s in tr.spans}
    assert by["inner"].parent_id == by["mid"].span_id
    assert by["mid"].parent_id == by["outer"].span_id
    assert by["outer"].parent_id is None
    # completion order: innermost closes first
    assert [s.name for s in tr.spans] == ["inner", "mid", "outer"]
    assert all(s.dur >= 0 for s in tr.spans)


def test_annotate_targets_innermost_open_span():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            tr.annotate(dist="row", coll_bytes=42.0)
    by = {s.name: s for s in tr.spans}
    assert by["inner"].attrs["dist"] == "row"
    assert "dist" not in by["outer"].attrs


def test_tracer_thread_safety():
    tr = Tracer()
    n_threads, per_thread = 8, 50

    def work(tid):
        for i in range(per_thread):
            with tr.span(f"t{tid}_outer{i}"):
                with tr.span(f"t{tid}_inner{i}"):
                    pass

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans) == n_threads * per_thread * 2
    # span ids unique; nesting resolved per-thread (inner's parent is its
    # own thread's outer, never another thread's span)
    ids = [s.span_id for s in tr.spans]
    assert len(set(ids)) == len(ids)
    by_id = {s.span_id: s for s in tr.spans}
    for s in tr.spans:
        if "inner" in s.name:
            parent = by_id[s.parent_id]
            assert parent.tid == s.tid
            assert parent.name.replace("outer", "inner") == s.name


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        tr.annotate(a=1)
        tr.defer("count", 3)
        assert sp is None
    tr.resolve()
    assert tr.spans == [] and tr._deferred == []


def test_defer_resolves_in_one_transfer():
    import jax.numpy as jnp
    tr = Tracer()
    with tr.span("op1"):
        tr.defer("count", jnp.int32(7))
    with tr.span("op2"):
        tr.defer("count", jnp.int32(9))
        tr.defer("overflow", jnp.bool_(False))
    tr.resolve()
    by = {s.name: s for s in tr.spans}
    assert by["op1"].attrs["count"] == 7
    assert by["op2"].attrs["count"] == 9
    assert by["op2"].attrs["overflow"] is False


def test_xfer_wire_bytes_formulas():
    assert xfer_wire_bytes("pin", 1000, 4) == 0.0
    assert xfer_wire_bytes("local", 1000, 4) == 0.0
    assert xfer_wire_bytes("replicate", 1000, 4) == pytest.approx(750.0)
    assert xfer_wire_bytes("repartition", 1600, 4) == pytest.approx(300.0)
    assert xfer_wire_bytes("spill", 1000, 4) == pytest.approx(2000.0)
    assert xfer_wire_bytes("replicate", 1000, 1) == 0.0


def test_tree_bytes_counts_leaves():
    import jax.numpy as jnp
    v = {"a": jnp.zeros((10,), jnp.float32), "b": jnp.zeros((4,), jnp.int32)}
    assert tree_bytes(v) == 40 + 16


# --------------------------------------------------------------------------
# EXPLAIN ANALYZE end to end
# --------------------------------------------------------------------------


def test_analyze_matches_untraced_outputs():
    planned, inputs = compile_rollup()
    plain = np.asarray(planned({}, inputs))
    traced = np.asarray(planned.analyze({}, inputs))
    np.testing.assert_array_equal(plain, traced)


def test_explain_analyze_golden_shape():
    planned, inputs = compile_rollup()
    planned.analyze({}, inputs)
    rep = planned.explain(analyze=True)
    # plan-time section still present
    assert "StagedPhysicalPlan" in rep and "choice [" in rep
    # runtime section: wall/sync header + one predicted~/observed= row per
    # executed physical node
    assert "EXPLAIN ANALYZE wall=" in rep
    trace = planned.last_run_trace
    assert trace.op_spans(), "no op spans recorded"
    for sp in trace.op_spans():
        assert f"analyze {sp.name}" in rep
    assert rep.count("predicted~") >= len(trace.op_spans())
    assert rep.count("observed=") >= len(trace.op_spans())
    # BoundedRel ops report observed cardinality; the filter's count sink
    # row renders too
    assert "count=" in rep
    assert "observed ('rel_filter'" in rep


def test_explain_analyze_requires_a_run():
    planned, _ = compile_rollup()
    with pytest.raises(ValueError):
        planned.explain(analyze=True)
    # plain explain still fine (and unchanged signature for old callers)
    assert "StagedPhysicalPlan" in planned.explain()


def test_analyze_xfer_attribution():
    planned, inputs = compile_rollup()
    planned.analyze({}, inputs)
    spans = {s.name: s for s in planned.last_run_trace.op_spans()}
    xfers = [s for s in spans.values() if "xfer_kind" in s.attrs]
    assert xfers, "no xfer nodes traced"
    for s in xfers:
        assert s.attrs["payload_bytes"] > 0
        assert s.attrs["xfer_kind"] in ("pin", "local", "replicate",
                                        "repartition", "spill")
        # device-resident kinds move nothing on the wire off-mesh
        if s.attrs["xfer_kind"] in ("pin", "local"):
            assert s.attrs["wire_bytes"] == 0.0


def test_analyze_drains_feedback_like_observe():
    planned, inputs = compile_rollup()
    fb_obs, fb_ana = SelectivityFeedback(), SelectivityFeedback()
    planned.observe({}, inputs, fb_obs)
    planned.analyze({}, inputs, feedback=fb_ana)
    assert len(fb_obs) == len(fb_ana) > 0
    assert fb_obs.fingerprint() == fb_ana.fingerprint()


def test_resolve_counts_single_transfer_semantics():
    import jax.numpy as jnp
    sink = [(("site", "a"), jnp.float32(12.0), jnp.int32(100)),
            (("compact_overflow", ("site", "a")), jnp.bool_(True), 1)]
    out = resolve_counts(sink)
    assert out[0] == (("site", "a"), 12.0, 100)
    assert out[1][0][0] == "compact_overflow" and out[1][1] == 1.0
    assert resolve_counts([]) == []


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------


def test_chrome_trace_export_validates(tmp_path):
    planned, inputs = compile_rollup()
    planned.analyze({}, inputs)
    path = tmp_path / "trace.json"
    planned.last_run_trace.to_chrome(path)
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == []
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert "run" in names and "device_sync" in names
    # microsecond complete events with args carried through
    assert all(isinstance(e["ts"], float) and e["dur"] >= 0 for e in evs)
    op = next(e for e in evs if e["cat"] == "op")
    assert "impl" in op["args"]


def test_jsonl_export_round_trips(tmp_path):
    planned, inputs = compile_rollup()
    planned.analyze({}, inputs)
    path = tmp_path / "trace.jsonl"
    planned.last_run_trace.to_jsonl(path)
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    kinds = [r["record"] for r in recs]
    assert kinds[0] == "run"
    assert kinds.count("span") == len(planned.last_run_trace.spans)
    assert "count" in kinds
    run = recs[0]
    assert run["wall_ms"] > 0 and run["spans"] == kinds.count("span")


def test_validate_chrome_trace_catches_violations():
    assert validate_chrome_trace({}) == ["missing traceEvents"]
    assert validate_chrome_trace({"traceEvents": []})
    bad = {"traceEvents": [{"ph": "X", "pid": 1, "tid": 0, "name": "x",
                            "ts": "oops", "dur": 1.0}]}
    assert any("ts" in e for e in validate_chrome_trace(bad))


# --------------------------------------------------------------------------
# fit_weights: traces as the calibration dataset
# --------------------------------------------------------------------------


def test_fit_weights_from_traces():
    planned, inputs = compile_rollup()
    traces = []
    for _ in range(3):
        planned.analyze({}, inputs)
        traces.append(planned.last_run_trace)
    model = fit_weights(traces, min_samples=3)
    assert model.weights, "no impl got enough samples to fit"
    assert model.fingerprint() != "analytic"
    # the refit model predicts finite times for the ops it saw
    for impl, feats, _sec in traces[0].samples:
        if impl in model.weights:
            x = {k: feats[k] for k in model.feature_names}
            import numpy as _np
            from repro.core.cost_model import poly2
            xv = _np.array([x[k] for k in model.feature_names])
            pred = float(poly2(xv[None, :])[0] @ model.weights[impl])
            assert _np.isfinite(pred)


def test_fit_weights_min_samples_gate():
    t = RunTrace(samples=[("some_impl",
                           {"f_compute": 0.0, "f_memory": 0.0,
                            "f_network": 0.0, "tokens_m": 0.0,
                            "width_k": 0.0}, 1e-3)])
    model = fit_weights([t], min_samples=3)
    assert model.weights == {}          # one sample: gated out


# --------------------------------------------------------------------------
# serving metrics: summaries, registry, shared LM + analytics reporting
# --------------------------------------------------------------------------


def test_summary_percentiles_nearest_rank():
    s = Summary("x")
    for v in range(1, 101):             # 1..100
        s.observe(v)
    assert s.count == 100 and s.min == 1 and s.max == 100
    assert s.percentile(50) == 50
    assert s.percentile(95) == 95
    assert s.percentile(99) == 99
    snap = s.snapshot()
    assert snap["p50"] == 50 and snap["p95"] == 95 and snap["p99"] == 99


def test_summary_bounded_ring_without_keep_samples():
    s = Summary("x", keep_samples=False, cap=8)
    for v in range(100):
        s.observe(v)
    assert len(s.samples) == 8          # bounded memory
    assert s.count == 100 and s.max == 99   # running stats stay exact


def test_serving_metrics_summary_keys_and_percentiles():
    from repro.serving.metrics import RequestMetrics
    m = ServingMetrics()
    for i in range(20):
        rm = RequestMetrics(i, gen=4, submitted_at=0.0, joined_at=0.01,
                            first_token_at=0.02 + i * 0.001,
                            finished_at=0.08 + i * 0.001)
        m.finish(rm)
        m.observe_tick(queue_depth=i % 3, pool_fill=0.5)
    m.observe_plan(hit=True)
    m.observe_plan(hit=False)
    s = m.summary()
    # the legacy keys tests/benchmarks consume
    for k in ("completed", "rejected", "ticks", "mean_ttft_s",
              "mean_tpot_s", "mean_queue_wait_s", "mean_queue_depth",
              "max_queue_depth", "mean_pool_fill", "plan_hits",
              "plan_misses", "plan_hit_rate", "generated_tokens"):
        assert k in s
    assert s["completed"] == 20 and s["generated_tokens"] == 80
    # the new percentile keys
    assert s["p50_ttft_s"] <= s["p95_ttft_s"] <= s["p99_ttft_s"]
    assert "p50" in m.report() and "p95" in m.report()
    # legacy raw-list views stay live
    assert len(m.queue_depth_samples) == 20


def test_registry_shared_between_lm_and_analytics():
    reg = MetricsRegistry()
    m = ServingMetrics(registry=reg)
    m.observe_tick(1, 0.5)
    reg.summary("analytics.run_ms").observe(12.5)
    reg.count("analytics.requests")
    assert "lm.queue_depth" in reg.summaries
    assert "analytics.run_ms" in reg.summaries
    rep = reg.report()
    assert "lm.queue_depth" in rep and "analytics.run_ms" in rep
    assert reg.counters["analytics.requests"] == 1


# --------------------------------------------------------------------------
# overhead guard: what tracing adds, counted rather than timed
# --------------------------------------------------------------------------


def test_traced_overhead_within_5_percent(monkeypatch, rng):
    """What tracing adds to the served path, counted: a CPU wall-clock
    ratio says nothing of the chip and swings when test workers share the
    cores (the chip measures the cost).  With no profiler and no Tracer a
    served analysis and a decode tick enter only the served path's spans,
    each once; plain execution keeps ``run_plan``'s fast path and enters
    none; ``analyze()`` still makes exactly one device-to-host transfer."""
    from repro.configs import get_smoke_config
    from repro.core import executor, tracing
    from repro.core.plan_cache import PlanCache
    from repro.models import build_model
    from repro.serving import (AnalysisRequest, AsyncServingRuntime,
                               ServeRequest)

    entered = []
    real_span = tracing.span

    def counting_span(name, **attrs):
        entered.append(name)
        return real_span(name, **attrs)

    def slow_path(*a, **k):
        raise AssertionError("run_plan left its fast path")

    monkeypatch.setattr(tracing, "span", counting_span)
    monkeypatch.setattr(executor, "_run_plan_traced", slow_path)
    monkeypatch.setattr(executor, "_run_plan_faulted", slow_path)

    cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(1))
    rt = AsyncServingRuntime(model, params, max_batch=2, max_seq=32,
                             plan_cache=PlanCache(), subplan_budget=16 << 20)
    planned, inputs = compile_rollup(tweets=2_000, hashtags=64)
    jax.block_until_ready(planned({}, inputs))
    assert entered == []

    res, = rt.serve_analyses([AnalysisRequest(
        rid="q", planned=planned, inputs=inputs, params={})])
    assert res.ok
    assert entered == ["analysis.tick", "analysis.exec", "mqo.frontier",
                       "mqo.dispatch", "mqo.insert", "analysis.device_wait"]

    rt.warmup([5])
    rt.submit(ServeRequest("r", (1, 2, 3, 4, 5), gen=3))
    del entered[:]
    assert rt._try_join()
    assert entered == ["lm.prefill"]
    del entered[:]
    assert rt._decode_tick()
    assert entered == ["lm.decode_step", "lm.decode.dispatch",
                       "lm.decode.fetch", "lm.decode.sample"]

    monkeypatch.undo()
    transfers = []
    real_get = jax.device_get

    def counting_get(x):
        transfers.append(1)
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    traced = planned.analyze({}, inputs)
    assert len(transfers) == 1
    np.testing.assert_array_equal(np.asarray(planned({}, inputs)),
                                  np.asarray(traced))
