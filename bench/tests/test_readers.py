"""The per-layer readers on a run at the CPU's tiny size, with the trace
reduction replaced by a fixed summary (the CPU has no TPU plane)."""
import pytest

from bench import common, trace
from bench.tests import tiny

BENCH = common.manifest()


@pytest.fixture
def fake_trace(monkeypatch):
    summary = trace.TraceSummary(busy_s=0.5, window_s=0.8, chips=1,
                                 device_ops=[["fusion f32[8]", 0.5]],
                                 idle_gaps=[["host.none", 0.3]])
    monkeypatch.setattr(trace, "reduce_dir", lambda d, chips: summary)
    return summary


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_listed_metric_is_read_in_a_traced_run(cell, fake_trace):
    run = tiny.drive(cell, seconds=2.0, trace=True)
    lo, hi = run.extra["traced"]
    assert lo is not None and hi is not None and hi > lo
    spec = common.workload(BENCH, cell)
    for m in common.metrics_of(BENCH, spec, "per_layer"):
        value = common.reader(m["name"])(run)
        assert value is not None, m["name"]
        if m["unit"] == "%" and value is not None:
            assert 0 <= value <= 100, (m["name"], value)
    assert common.reader("idle_share.mixed")(run) == pytest.approx(37.5)
