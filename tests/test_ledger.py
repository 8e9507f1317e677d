"""Resource ledger + continuous telemetry: byte accounting with
predicted-vs-actual deltas, leak detection over lifetime anchors, the
byte-budget plan-cache eviction order, the flight recorder's ring bounds
and dump triggers, gauge/counter registry semantics, KV-pool occupancy /
fragmentation gauges, and the recorder overhead guard."""
import json
import os
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.adil import Analysis
from repro.core.cost_model import predicted_resident_bytes
from repro.core.ir import SystemCatalog, standard_catalog
from repro.core.ledger import (FlightRecorder, MemoryLedger, default_ledger,
                               register_store_payload)
from repro.core.plan_cache import PlanCache, staged_bytes
from repro.models import build_model
from repro.serving.kv_pool import PagedKVPool
from repro.serving.metrics import MetricsRegistry
from repro.stores import ColumnStore, GraphStore, TextStore, store_engines

CAT = standard_catalog()
SYS = SystemCatalog()


# --------------------------------------------------------------------------
# MemoryLedger: register / replace / release / transient accounting
# --------------------------------------------------------------------------


def test_register_release_totals():
    led = MemoryLedger()
    led.register(("a", "1"), nbytes=100, kind="x")
    led.register(("b", "1"), nbytes=50, kind="y")
    assert led.total_bytes() == 150
    assert led.bytes_for_kind("x") == 100
    assert led.bytes_for_kind("y") == 50
    assert led.release(("a", "1")) == 100
    assert led.total_bytes() == 50
    assert led.release(("a", "1")) == 0          # double release is a no-op


def test_register_value_uses_tree_bytes():
    led = MemoryLedger()
    arr = jnp.zeros(256, jnp.float32)
    e = led.register("arr", {"x": arr})
    assert e.nbytes == 1024
    assert led.total_bytes() == 1024


def test_same_owner_reregistration_replaces():
    led = MemoryLedger()
    led.register(("store", "s1"), nbytes=1000, kind="col")
    led.register(("store", "s1"), nbytes=400, kind="col")   # append→rebuild
    assert led.total_bytes() == 400                         # old bytes freed
    assert led.bytes_for_kind("col") == 400
    assert len(led.entries()) == 1
    assert led.peak_bytes == 1000                           # high-water mark


def test_transient_counts_toward_peak_not_resident():
    led = MemoryLedger()
    led.register("resident", nbytes=100)
    led.note_transient("shuffle", 900, kind="shuffle_buckets")
    assert led.total_bytes() == 100          # scratch is not resident
    assert led.peak_bytes == 1000            # but it is part of the peak
    assert led.transient_bytes == 900
    snap = led.snapshot()
    assert snap["total_bytes"] == 100 and snap["peak_bytes"] == 1000


def test_predicted_vs_actual_ratio():
    led = MemoryLedger()
    led.register("p", nbytes=150, predicted=100)
    led.register("q", nbytes=80)             # no prediction -> not listed
    rows = led.predicted_vs_actual()
    assert len(rows) == 1
    entry, pred, act, ratio = rows[0]
    assert (pred, act) == (100, 150) and ratio == pytest.approx(1.5)
    assert "predicted 0.00 MB" in led.report()


# --------------------------------------------------------------------------
# leak detection: tied_to + version anchors
# --------------------------------------------------------------------------


def test_leak_on_evicted_anchor():
    led = MemoryLedger()
    led.register(("plan_cache", "p1"), nbytes=10, kind="plan_cache")
    led.register(("plan_jit", "p1"), nbytes=0, kind="plan_jit",
                 tied_to=("plan_cache", "p1"))
    assert led.leaks() == []
    led.release(("plan_cache", "p1"))        # cache evicts, jit entry stays
    leaks = led.leaks()
    assert len(leaks) == 1
    reason, entry = leaks[0]
    assert reason == "evicted" and entry.owner == ("plan_jit", "p1")


def test_leak_on_superseded_version():
    led = MemoryLedger()
    led.register(("col", "s"), nbytes=100, kind="col", version=3)
    led.register(("pin", "c"), nbytes=100, kind="pin",
                 tied_to=("col", "s"), version=3)
    assert led.leaks() == []
    # store appends: same owner re-registers at a newer version
    led.register(("col", "s"), nbytes=120, kind="col", version=4)
    leaks = led.leaks()
    assert len(leaks) == 1
    reason, entry = leaks[0]
    assert reason == "superseded" and entry.owner == ("pin", "c")
    assert "LEAK (superseded)" in led.report()
    assert led.snapshot()["leaks"] == 1


def test_publish_sets_registry_gauges():
    led = MemoryLedger()
    led.register("a", nbytes=300, kind="col")
    reg = MetricsRegistry()
    led.publish(reg)
    assert reg.gauges["ledger.total_bytes"].value == 300
    assert reg.gauges["ledger.col_bytes"].value == 300


# --------------------------------------------------------------------------
# store payload() registration + cost-model predictions
# --------------------------------------------------------------------------


def test_store_payloads_register_with_predictions():
    rng = np.random.RandomState(0)
    table = ColumnStore({"a": np.arange(100, dtype=np.int32),
                         "v": rng.rand(100).astype(np.float32)})
    e = rng.randint(0, 64, (2, 500))
    graph = GraphStore.from_edges(e[0], e[1], 64)
    corpus = TextStore.from_docs(
        [rng.randint(0, 32, 5) for _ in range(20)], 32)
    led = default_ledger()
    for store, kind in ((table, "column_store"), (graph, "graph_store"),
                        (corpus, "text_store")):
        store.payload()
        entry = led.get((kind, f"{id(store):#x}"))
        assert entry is not None and entry.nbytes > 0
        assert entry.predicted and entry.predicted > 0
        assert entry.version == getattr(store, "version", 0)


def test_store_append_reregisters_same_owner():
    led = default_ledger()
    cs = ColumnStore({"a": np.arange(64, dtype=np.int32)})
    cs.payload()
    owner = ("column_store", f"{id(cs):#x}")
    before = led.get(owner).nbytes
    cs.append({"a": np.arange(64, dtype=np.int32)})
    cs.payload()
    after = led.get(owner).nbytes
    assert after > before
    # one entry per store: replaced, not accumulated
    assert sum(1 for e in led.entries("column_store")
               if e.owner == owner) == 1


def test_predicted_resident_bytes_shapes():
    rng = np.random.RandomState(0)
    table = ColumnStore({"a": np.arange(100, dtype=np.int32)})
    graph = GraphStore.from_edges(*rng.randint(0, 64, (2, 500)), 64)
    corpus = TextStore.from_docs(
        [rng.randint(0, 32, 5) for _ in range(20)], 32)
    for store in (table, graph, corpus):
        pred = predicted_resident_bytes(store.type)
        assert isinstance(pred, int) and pred > 0


def _corpus_bytes(case):
    """(predicted, actual) resident bytes of a corpus: built at the test's
    size, or reckoned at the deployment's (2^23 docs of 7-14 tokens,
    59,000,000 postings, 2^16 terms, an ELL 14 slots wide)."""
    from repro.core.ir import CorpusT
    from repro.core.tracing import tree_bytes
    rng = np.random.RandomState(1)
    if case == "deployment":
        docs, vocab, postings, width = 1 << 23, 1 << 16, 59_000_000, 14
        actual = postings * 12 + docs * 4 + vocab * 4 + width * docs * 8
        return predicted_resident_bytes(CorpusT(docs, vocab, postings)), \
            actual
    if case == "ell":
        tx = TextStore.from_docs(
            [rng.randint(0, 4096, rng.randint(7, 15)) for _ in range(3000)],
            4096)
    else:   # one wide doc: fails the ELL's width rule
        tx = TextStore.from_docs(
            [np.arange(500)] + [[t] for t in rng.randint(0, 500, 300)], 512)
    pay = tx.payload()
    assert ("ell_term" in pay) == (case == "ell")
    return predicted_resident_bytes(tx.type), tree_bytes(pay)


@pytest.mark.parametrize("case", ["ell", "no_ell", "deployment"])
def test_corpus_prediction_within_2x_with_and_without_ell(case):
    pred, actual = _corpus_bytes(case)
    assert 0.5 <= actual / pred <= 2.0, (case, pred, actual)


# --------------------------------------------------------------------------
# plan cache: byte budget + stale-first-then-largest eviction
# --------------------------------------------------------------------------


def _staged(nbytes):
    return SimpleNamespace(nbytes=nbytes)


def test_staged_bytes_honors_explicit_nbytes():
    assert staged_bytes(_staged(12345)) == 12345
    assert staged_bytes("opaque") == 1024        # unwalkable -> fallback


def test_byte_budget_evicts_largest_first():
    led = MemoryLedger()
    pc = PlanCache(maxsize=10, byte_budget=500, ledger=led)
    pc.insert("a", _staged(400))
    pc.insert("b", _staged(90))
    assert pc.bytes_in_cache == 490 and led.total_bytes() == 490
    pc.insert("c", _staged(300))                 # 790 > 500
    # largest entry sheds first (not the coldest): a(400), not b(90)
    assert "a" not in pc and "b" in pc and "c" in pc
    assert pc.bytes_in_cache == 390
    assert pc.byte_evictions == 1
    assert led.get(("plan_cache", "a")) is None  # ledger entry released
    assert led.total_bytes() == 390


def test_byte_budget_evicts_stale_before_largest():
    pc = PlanCache(maxsize=10, byte_budget=600, ledger=MemoryLedger())
    pc.insert("old", _staged(50), fingerprint="fit1")
    pc.note_fingerprint("fit2")                  # calibration moved on
    pc.insert("big", _staged(400), fingerprint="fit2")
    pc.insert("new", _staged(200), fingerprint="fit2")   # 650 > 600
    # the stale entry goes first even though it is the smallest
    assert "old" not in pc and "big" in pc and "new" in pc
    assert pc.stale_evictions == 1 and pc.byte_evictions == 1


def test_byte_budget_never_evicts_the_just_inserted_entry():
    pc = PlanCache(maxsize=10, byte_budget=100, ledger=MemoryLedger())
    pc.insert("huge", _staged(1000))             # alone over budget: kept
    assert "huge" in pc and len(pc) == 1
    pc.insert("huge2", _staged(900))             # newest survives instead
    assert "huge2" in pc and "huge" not in pc
    assert len(pc) == 1


def test_plan_cache_clear_releases_ledger():
    led = MemoryLedger()
    pc = PlanCache(maxsize=4, byte_budget=None, ledger=led)
    pc.insert("a", _staged(100))
    pc.insert("b", _staged(200))
    assert led.total_bytes() == 300
    st = pc.stats()
    assert st["bytes"] == 300 and st["byte_budget"] is None
    pc.clear()
    assert led.total_bytes() == 0 and pc.bytes_in_cache == 0


def test_reinsert_same_plan_does_not_double_count():
    led = MemoryLedger()
    pc = PlanCache(maxsize=4, ledger=led)
    pc.insert("a", _staged(100))
    pc.insert("a", _staged(250))
    assert pc.bytes_in_cache == 250 and led.total_bytes() == 250


# --------------------------------------------------------------------------
# flight recorder: ring bounds + dump triggers
# --------------------------------------------------------------------------


def test_ring_bounds_and_drop_count():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("tick", {"i": i})
    assert len(rec) == 4
    assert rec.dropped == 6
    assert [ev.payload["i"] for ev in rec.events()] == [6, 7, 8, 9]
    assert [ev.seq for ev in rec.events()] == [7, 8, 9, 10]


def test_capacity_must_be_positive():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_trip_without_dump_dir_returns_records():
    rec = FlightRecorder(capacity=8)
    rec.record("tick", {"i": 1})
    records = rec.trip("overflow", {"site": "x"})
    assert records[0]["record"] == "flight_dump"
    assert records[0]["reason"] == "overflow"
    assert records[0]["events"] == 1
    assert rec.trips == [("overflow", None)]
    # the trip itself lands in the ring so a later dump shows it
    assert rec.events()[-1].kind == "trip"


def test_trip_with_dump_dir_writes_jsonl(tmp_path):
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    for i in range(3):
        rec.record("tick", {"i": i})
    path = rec.trip("executor_error", {"error": "boom"})
    assert os.path.basename(path) == "flight_000_executor_error.jsonl"
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["record"] == "flight_dump"
    assert lines[0]["detail"] == {"error": "boom"}
    assert [ln["payload"]["i"] for ln in lines[1:]] == [0, 1, 2]
    # second trip gets its own numbered file
    path2 = rec.trip("overflow")
    assert os.path.basename(path2) == "flight_001_overflow.jsonl"


def test_forced_overflow_trips_the_recorder(tmp_path):
    """A bounded join whose capacity cannot hold the matches must trip the
    recorder through PlannedFunction.analyze."""
    rng = np.random.RandomState(0)
    nodes, rows = 8, 64
    dims = ColumnStore({"tag": np.arange(nodes, dtype=np.int32)})
    facts = ColumnStore({"tag": rng.randint(0, nodes, rows).astype(np.int32),
                         "v": rng.rand(rows).astype(np.float32)})
    with Analysis("flight_ovf", CAT) as a:
        dm = a.bind("dims", dims)
        fc = a.bind("facts", facts)
        bj = a.op("bounded_join", dm, fc, left_on="tag", right_on="tag",
                  capacity=8)                    # 64 matches cannot fit
        a.store(bj)
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
    fn.analyze({}, {"dims": dims.payload(), "facts": facts.payload()},
               recorder=rec)
    reasons = [r for r, _ in rec.trips]
    assert "overflow" in reasons
    dumps = sorted(os.listdir(tmp_path))
    assert any("overflow" in d for d in dumps)
    # the ring holds the run-trace summary that preceded the trip
    kinds = [ev.kind for ev in rec.events()]
    assert "run_trace" in kinds


def test_executor_error_trips_the_recorder():
    cs = ColumnStore({"a": np.arange(16, dtype=np.int32)})
    with Analysis("flight_err", CAT) as a:
        t = a.op("rel_scan", a.bind("t", cs))
        a.store(a.op("col_tensor",
                     a.op("rel_group_agg", t, key="a", num_groups=16,
                          aggs=(("s", "sum", "a"),)),
                     col="s", dim="nodes"))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    rec = FlightRecorder(capacity=8)
    with pytest.raises(Exception):
        fn.analyze({}, {"t": None}, recorder=rec)    # unusable input payload
    assert [r for r, _ in rec.trips] == ["executor_error"]


def test_record_trace_summarizes_run(tmp_path):
    cs = ColumnStore({"a": np.arange(32, dtype=np.int32)})
    with Analysis("flight_trace", CAT) as a:
        t = a.op("rel_scan", a.bind("t", cs))
        a.store(a.op("col_tensor",
                     a.op("rel_group_agg", t, key="a", num_groups=32,
                          aggs=(("s", "count", None),)),
                     col="s", dim="nodes"))
    fn = a.compile(SYS, engines=store_engines(), cache=False)
    rec = FlightRecorder(capacity=8)
    fn.analyze({}, {"t": cs.payload()}, recorder=rec)
    ev = next(e for e in rec.events() if e.kind == "run_trace")
    assert ev.payload["plan_id"] == fn.plan_id
    assert ev.payload["wall_ms"] >= 0.0
    assert ev.payload["spans"] > 0


def test_recorder_overhead_within_5_percent():
    """The recorder rides on an already-traced run: its marginal cost (one
    ring append per run) must stay inside the tracing suite's 5% bar."""
    import time

    from test_tracing import compile_rollup
    planned, inputs = compile_rollup(tweets=200_000, hashtags=1024,
                                     metrics=4)
    rec = FlightRecorder(capacity=16)
    planned.analyze({}, inputs)
    planned.analyze({}, inputs, recorder=rec)
    t_plain = t_rec = float("inf")
    for _ in range(10):                      # interleaved min-of-N
        t0 = time.perf_counter()
        jax.block_until_ready(planned.analyze({}, inputs))
        t_plain = min(t_plain, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(planned.analyze({}, inputs, recorder=rec))
        t_rec = min(t_rec, time.perf_counter() - t0)
    overhead = t_rec / t_plain - 1.0
    assert overhead <= 0.05, (
        f"recorded run {t_rec * 1e3:.2f} ms vs plain traced "
        f"{t_plain * 1e3:.2f} ms: overhead {overhead:+.1%} > 5%")


# --------------------------------------------------------------------------
# gauge / counter semantics in the shared registry
# --------------------------------------------------------------------------


def test_gauge_set_inc_dec_peak_trough():
    reg = MetricsRegistry()
    g = reg.gauge("queue_depth")
    assert reg.gauge("queue_depth") is g         # stable identity
    g.set(5)
    g.inc(3)
    g.dec(6)
    assert g.value == 2.0
    assert g.peak == 8.0 and g.trough == 2.0
    snap = g.snapshot()
    assert snap == {"value": 2.0, "peak": 8.0, "trough": 2.0, "updates": 3}


def test_fresh_gauge_snapshot_is_zeroed():
    snap = MetricsRegistry().gauge("x").snapshot()
    assert snap["value"] == 0.0 and snap["peak"] == 0.0
    assert snap["trough"] == 0.0 and snap["updates"] == 0


def test_counter_is_monotone_and_shares_the_plain_dict():
    reg = MetricsRegistry()
    c = reg.counter("joins")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counters["joins"] == 5            # back-compat plain dict
    reg.count("joins")                           # legacy path still works
    assert c.value == 6
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_snapshot_and_report_cover_gauges():
    reg = MetricsRegistry()
    reg.gauge("ledger.total_bytes").set(1234)
    reg.counter("evictions").inc(2)
    snap = reg.snapshot()
    assert snap["gauges"]["ledger.total_bytes"]["value"] == 1234.0
    assert snap["counters"]["evictions"] == 2
    rep = reg.report()
    assert "ledger.total_bytes" in rep and "evictions" in rep


# --------------------------------------------------------------------------
# KV pool: occupancy / fragmentation gauges + ledger registration
# --------------------------------------------------------------------------


def _smoke_model():
    cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32")
    model = build_model(cfg)
    return model


def test_kv_pool_fragmentation_and_gauges():
    reg = MetricsRegistry()
    pool = PagedKVPool(_smoke_model(), n_slots=4, max_seq=32, page_size=8,
                       registry=reg)
    assert pool.pages_per_slot == 4
    frag = pool.fragmentation()
    assert frag == {"free_pages": 16, "free_slots": 4,
                    "max_contig_free_run": 16}
    pool.alloc("r1", 10)                         # slot 0, 2 pages
    pool.alloc("r2", 32)                         # slot 1, 4 pages (full)
    frag = pool.fragmentation()
    assert frag["free_pages"] == 10
    assert frag["free_slots"] == 2
    # slot 0's free tail (2) is walled off by slot 1's full occupancy;
    # slots 2+3 form the longest free run
    assert frag["max_contig_free_run"] == 8
    assert reg.gauges["kv.free_pages"].value == 10
    assert reg.gauges["kv.free_slots"].value == 2
    assert reg.gauges["kv.max_contig_free_run"].value == 8
    assert reg.gauges["kv.fill"].value == pytest.approx(6 / 16)
    pool.free("r2")
    # freeing restores run contiguity and records the lifetime footprint
    assert pool.fragmentation()["max_contig_free_run"] == 14
    assert reg.summary("kv.pages_per_request").count == 1
    assert reg.summary("kv.pages_per_request").max == 4.0


def test_kv_pool_budget_caps_the_free_run():
    pool = PagedKVPool(_smoke_model(), n_slots=4, max_seq=32, page_size=8,
                       page_budget=6)
    # geometric free space is 16 pages but the budget admits only 6
    assert pool.fragmentation() == {"free_pages": 6, "free_slots": 4,
                                    "max_contig_free_run": 6}


def test_kv_pool_registers_its_one_allocation():
    led = MemoryLedger()
    pool = PagedKVPool(_smoke_model(), n_slots=2, max_seq=32, page_size=8,
                       ledger=led)
    entry = led.get(("kv_pool", f"{id(pool):#x}"))
    assert entry is not None and entry.kind == "kv_pool"
    assert entry.nbytes > 0
    assert led.bytes_for_kind("kv_pool") == entry.nbytes
