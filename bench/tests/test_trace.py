"""The reduction from a profiler trace to busy time, op times and idle
gaps (``bench/trace.py``)."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _event(mid, off_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {off_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _plane(pid, name, line, events, names):
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in names.items())
    evs = " ".join(_event(*e) for e in events)
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')


def synthetic(two_chips=False):
    """A 100 us window: chip 0 busy 10-30 (fusion.1), 25-40 (overlapping
    scatter.7) and 70-80 (fusion.2); an op at 95-120 runs past the window.
    The host submits at 40-60 and executes 60-100."""
    ops = {1: "fusion.1", 2: "scatter.7", 3: "fusion.2", 4: "copy.3"}
    dev0 = [(1, 10, 20), (2, 25, 15), (3, 70, 10), (4, 95, 25)]
    host = {1: "bench.traced_window", 2: "bench.submit",
            3: "bench.analysis_exec", 4: "python-stuff"}
    planes = [_plane(1, "/device:TPU:0", "XLA Ops", dev0, ops),
              _plane(3, "/host:CPU", "main",
                     [(1, 0, 100), (2, 40, 20), (3, 60, 40), (4, 0, 5)],
                     host)]
    if two_chips:
        planes.insert(1, _plane(2, "/device:TPU:1", "XLA Ops",
                                [(1, 0, 50)], ops))
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("\n".join(planes))


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_ops_and_gaps_on_a_synthetic_trace():
    s = trace.reduce(synthetic(), chips=1)
    us = 1e-6
    # busy: 10-40 (30) + 70-80 (10) + 95-100 (5, clipped) = 45 of 100
    assert s.window_s == pytest.approx(100 * us)
    assert s.busy_s == pytest.approx(45 * us)
    assert s.idle_share == pytest.approx(0.55)
    ops = dict(s.device_ops)
    assert ops["fusion"] == pytest.approx(30 * us)
    assert ops["scatter"] == pytest.approx(15 * us)
    assert ops["copy"] == pytest.approx(5 * us)
    # gaps: 0-10 and 80-95 (none / exec), 40-70 (mid 55: submit)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.submit"] == pytest.approx(30 * us)
    assert gaps["bench.analysis_exec"] == pytest.approx(15 * us)
    assert gaps[trace.NO_ANNOTATION] == pytest.approx(10 * us)


def test_busy_is_averaged_over_chips():
    s = trace.reduce(synthetic(two_chips=True), chips=2)
    assert s.busy_s == pytest.approx((45 + 50) / 2 * 1e-6)


def test_a_trace_without_the_window_or_the_chips_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(synthetic(), chips=2)
    from jax.profiler import ProfileData
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(
            _plane(1, "/device:TPU:0", "XLA Ops", [(1, 0, 5)],
                   {1: "fusion.1"})), chips=1)


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three rounds of a jitted 2048^2
    matmul (one fused op of ~0.1 ms), then, under ``bench.submit``, a
    jitted sort of 4 M floats (~7.2 ms), with 10 ms host sleeps between."""
    s = trace.reduce(trace.load(str(DATA / "tpu_v5e_small.xplane.pb")),
                     chips=1)
    assert s.window_s == pytest.approx(0.05837485)
    assert s.busy_s == pytest.approx(0.02191165)
    assert s.device_ops[0][0] == "sort tuple"
    assert s.device_ops[0][1] == pytest.approx(3 * 0.0072, rel=0.01)
    assert dict(s.device_ops)["fusion f32[2048]"] < 0.001
    assert {g for g, _ in s.idle_gaps} == {"bench.submit",
                                           trace.NO_ANNOTATION}


def test_hlo_event_names():
    assert trace.op_name("%fusion.12 = f32[8388608]{0:T(1024)} fusion(f32["
                         "8388608]{0:T(1024)} %copy.2)") == \
        "fusion f32[8388608]"
    assert trace.op_name("%copy-start = (f32[8]{0}, u32[]) copy-start(") == \
        "copy-start tuple"
    assert trace.op_name("scatter.7") == "scatter"
