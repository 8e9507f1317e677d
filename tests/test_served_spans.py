"""The served path's spans and stamps: under ``jax.profiler`` the runtime's
``repro.*`` annotations land in the host plane of the profile with their
attributes, nested as the layers are; the stamps on ``AnalysisResult`` and
``RequestMetrics`` are ordered and say which execution produced a value."""
import asyncio
import glob
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.adil import Analysis
from repro.core.ir import SystemCatalog, TensorT, standard_catalog
from repro.core.plan_cache import PlanCache
from repro.models import build_model
from repro.serving import AnalysisRequest, AsyncServingRuntime, ServeRequest
from repro.stores import ColumnStore, store_engines

SYS = SystemCatalog()

ANALYSIS_SPANS = {"repro.analysis.tick", "repro.analysis.exec",
                  "repro.mqo.frontier", "repro.mqo.dispatch",
                  "repro.mqo.insert", "repro.analysis.device_wait",
                  "repro.analysis.batch"}
LM_SPANS = {"repro.lm.prefill", "repro.lm.decode_step",
            "repro.lm.decode.dispatch", "repro.lm.decode.fetch",
            "repro.lm.decode.sample"}


def host_spans(logdir) -> list:
    """(name, start_ns, end_ns, stats) of every ``repro.*`` event in the
    host plane of the profile written under ``logdir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        out.append((ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
    return out


def inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def smoke_lm():
    cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(1))
    return cfg, model, params


def seeded_analysis(rows=64):
    """rel_scan -> group_agg -> col_tensor + a per-query ``seed`` vector."""
    rng = np.random.RandomState(0)
    table = ColumnStore({"k": (np.arange(rows) % 16).astype(np.int32),
                         "v": rng.rand(rows).astype(np.float32)})
    with Analysis("spans_q", standard_catalog()) as a:
        t = a.op("rel_scan", a.bind("t", table))
        g = a.op("rel_group_agg", t, key="k", num_groups=16,
                 aggs=(("s", "sum", "v"),))
        vec = a.op("col_tensor", g, col="s", dim="nodes")
        seed = a.input("seed", TensorT((16,), "float32", ("nodes",)))
        a.store(a.op("residual_add", vec, seed))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    return a, fn, {"t": table.payload()}


@pytest.fixture(scope="module")
def served_analyses(tmp_path_factory):
    """One miss, one repeat (every node from the subplan cache), a twin
    riding a leader in the same tick, and a vmapped batch of two, served
    under the profiler."""
    _, model, params = smoke_lm()
    rt = AsyncServingRuntime(model, params, max_batch=2, max_seq=32,
                             plan_cache=PlanCache(), subplan_budget=16 << 20)
    a, fn, base = seeded_analysis()
    seeds = [jnp.asarray(np.full(16, i, np.float32)) for i in range(3)]

    def req(rid, seed, batch=False):
        return AnalysisRequest(rid=rid, planned=fn,
                               inputs={**base, "seed": seed}, params={},
                               batch_param="seed" if batch else None,
                               store_versions=a.store_versions())

    rt.serve_analyses([req("warm", seeds[2])])          # compile eagerly
    logdir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(logdir):
        miss, = rt.serve_analyses([req("miss", seeds[0])])
        repeat, = rt.serve_analyses([req("repeat", seeds[0])])
        leader, twin = rt.serve_analyses([req("leader", seeds[1]),
                                          req("twin", seeds[1])])
        batch = rt.serve_analyses([req("b0", seeds[0], True),
                                   req("b1", seeds[2], True)])
    results = {r.rid: r for r in [miss, repeat, leader, twin, *batch]}
    return results, host_spans(logdir)


def test_served_analysis_spans_land_in_the_profile(served_analyses):
    results, spans = served_analyses
    assert ANALYSIS_SPANS <= {s[0] for s in spans}
    execs = [s for s in spans if s[0] == "repro.analysis.exec"]
    assert sorted(s[3]["rid"] for s in execs) == ["leader", "miss",
                                                  "repeat"]
    by_rid = {s[3]["rid"]: s[3] for s in execs}
    assert by_rid["miss"]["executed"] == results["miss"].executed > 0
    assert by_rid["repeat"]["executed"] == results["repeat"].executed \
        < results["miss"].executed
    assert by_rid["repeat"]["shared_hits"] == results["repeat"].shared_hits
    for s in spans:
        if s[0].startswith("repro.mqo.") or \
                s[0] == "repro.analysis.device_wait":
            assert inside(s, execs), s[0]
    batch, = [s for s in spans if s[0] == "repro.analysis.batch"]
    assert batch[3]["n"] == 2 and batch[3]["rids"] == "b0 b1"
    ticks = [s[3] for s in spans if s[0] == "repro.analysis.tick"]
    assert {"n": 2, "groups": 1, "twins": 1, "batches": 0} in ticks
    assert {"n": 2, "groups": 2, "twins": 0, "batches": 1} in ticks


def test_analysis_stamps_are_ordered_and_twins_carry_the_leaders(
        served_analyses):
    results, _ = served_analyses
    for r in results.values():
        assert r.ok
        assert r.enqueued_at <= r.started_at <= r.finished_at \
            <= r.settled_at, r.rid
        assert r.ttfr_ms == pytest.approx(
            (r.settled_at - r.enqueued_at) * 1e3)
    twin, leader = results["twin"], results["leader"]
    assert twin.deduped and not leader.deduped
    assert (twin.started_at, twin.finished_at) == \
        (leader.started_at, leader.finished_at)
    b0, b1 = results["b0"], results["b1"]
    assert b0.batched and b1.batched
    assert (b0.started_at, b0.finished_at) == (b1.started_at, b1.finished_at)


def test_cross_tick_twin_carries_the_leaders_stamps():
    """A twin that finds its query in flight (queued behind another query
    in a concurrent run_analyses call's tick) awaits the leader's future
    and carries its execution stamps."""
    _, model, params = smoke_lm()
    rt = AsyncServingRuntime(model, params, max_batch=2, max_seq=32,
                             plan_cache=PlanCache(), subplan_budget=16 << 20)
    a, fn, base = seeded_analysis()

    def req(rid, i):
        return AnalysisRequest(
            rid=rid, planned=fn, params={},
            inputs={**base, "seed": jnp.full((16,), i, jnp.float32)},
            store_versions=a.store_versions())

    async def both():
        # the first call's tick runs "a", then yields while "b" is in
        # flight: the second call's tick finds "b" there
        return await asyncio.gather(
            rt.run_analyses([req("a", 0), req("b", 1)]),
            rt.run_analyses([req("b2", 1)]))

    (_, leader), (twin,) = asyncio.run(both())
    assert leader.ok and twin.ok
    assert twin.deduped and not leader.deduped
    assert twin.started_at == leader.started_at
    assert twin.finished_at == leader.finished_at
    assert twin.enqueued_at <= twin.started_at
    assert twin.finished_at <= twin.settled_at


@pytest.fixture(scope="module")
def served_lm(tmp_path_factory):
    """Two same-bucket requests prefilled as one batch of 2, then decoded
    for a few steps, under the profiler."""
    cfg, model, params = smoke_lm()
    rt = AsyncServingRuntime(model, params, max_batch=2, max_seq=32,
                             plan_cache=PlanCache(), prefill_batch=2)
    rt.warmup([6, 7])
    rng = np.random.RandomState(3)
    reqs = [ServeRequest(i, tuple(rng.randint(0, cfg.vocab, n).tolist()), 4)
            for i, n in enumerate((6, 7))]
    logdir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(logdir):
        results = rt.serve(reqs, timeout_s=120)
    return results, host_spans(logdir)


def test_served_lm_spans_land_in_the_profile(served_lm):
    results, spans = served_lm
    assert all(r.status == "ok" for r in results)
    assert LM_SPANS <= {s[0] for s in spans}
    prefill, = [s for s in spans if s[0] == "repro.lm.prefill"]
    assert prefill[3] == {"rids": "0 1", "bucket": 8, "members": 2,
                          "width": 2}
    steps = [s for s in spans if s[0] == "repro.lm.decode_step"]
    assert len(steps) == 3                   # 4 tokens: 1 prefill + 3 steps
    assert all(s[3]["active"] == 2 for s in steps)
    for s in spans:
        if s[0].startswith("repro.lm.decode."):
            assert inside(s, steps), s[0]
    assert not inside(prefill, steps)


def test_batched_prefill_metrics_are_the_whole_call(served_lm):
    results, spans = served_lm
    rms = [r.metrics for r in results]
    assert [(m.prefill_width, m.prefill_members) for m in rms] == \
        [(2, 2), (2, 2)]
    # every member waited for the whole call, not a share of it
    assert rms[0].prefill_ms == rms[1].prefill_ms > 0
    assert rms[0].prefill_started_at == rms[1].prefill_started_at
    for m in rms:
        assert m.submitted_at <= m.prefill_started_at <= m.joined_at
        assert m.prefill_ms <= (m.joined_at - m.prefill_started_at) * 1e3


def test_explain_analyze_spans_land_in_the_profile(tmp_path):
    """EXPLAIN ANALYZE's Tracer spans enter the same entry point, so an
    analyzed run taken under the profiler shows its per-node spans."""
    _, fn, base = seeded_analysis()
    ins = {**base, "seed": jnp.zeros((16,), jnp.float32)}
    with jax.profiler.trace(str(tmp_path)):
        fn.analyze({}, ins)
    spans = host_spans(str(tmp_path))
    ops = [s for s in spans if s[0] == "repro.executor.op"]
    assert sorted(s[3]["node"] for s in ops) == \
        sorted(sp.name for sp in fn.last_run_trace.op_spans())
    run, = [s for s in spans if s[0] == "repro.executor.run"]
    assert run[3]["plan_id"] == fn.plan_id
    assert all(inside(s, [run]) for s in ops)
    assert [s[0] for s in spans].count("repro.executor.sync") == 1
