"""The per-layer readers on a run at the CPU's tiny size, with the trace
reduction replaced by a fixed summary (the CPU has no TPU plane)."""
import pytest

from bench import common, trace
from bench.tests import tiny

BENCH = common.manifest()
# and a cell the manifest does not hold: the first analysis cell's
# configuration on four chips, read with that cell's metrics
CELLS = [w["name"] for w in BENCH["workloads"]] + [
    (next(w["name"] for w in BENCH["workloads"]
          if common.config_of(BENCH, w)["driver"] == "analysis"), 4)]


@pytest.fixture
def fake_trace(monkeypatch, cell):
    """A summary of the cell's device planes, with one collective among
    its operations."""
    ops = [["fusion f32[8]", 0.4], ["all-reduce f32[1024]", 0.1]]
    summary = trace.TraceSummary(busy_s=0.5, window_s=0.8,
                                 chips=tiny.files(cell)[0]["chips"],
                                 device_ops=ops, all_ops=ops,
                                 idle_gaps=[["host.none", 0.3]])
    monkeypatch.setattr(trace, "reduce_dir", lambda d, chips: summary)
    return summary


@pytest.mark.parametrize("cell", CELLS, ids=tiny.label)
def test_every_listed_metric_is_read_in_a_traced_run(cell, fake_trace,
                                                     request):
    if tiny.elsewhere(request, cell):
        return
    run = tiny.drive(cell, seconds=2.0, trace=True)
    lo, hi = run.extra["traced"]
    assert lo is not None and hi is not None and hi > lo
    assert run.trace.chips == fake_trace.chips == run.cell["chips"]
    spec = common.workload(BENCH, cell if isinstance(cell, str) else cell[0])
    for m in common.metrics_of(BENCH, spec, "per_layer"):
        value = common.reader(m["name"])(run)
        assert value is not None, m["name"]
        if m["unit"] == "%" and value is not None:
            assert 0 <= value <= 100, (m["name"], value)
    assert common.reader("idle_share.mixed")(run) == pytest.approx(37.5)
