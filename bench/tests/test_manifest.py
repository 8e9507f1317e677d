"""BENCHMARK.json against the contract its files keep to."""
import re

import pytest

from bench import common

BENCH = common.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
CELLS = BENCH["workloads"]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def reported(metric, cell):
    return cell["name"] in metric.get("workloads", [cell["name"]])


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(TEXT.match(w) for w in BENCH["command"])


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_cell_files_exist(cell):
    cfg = common.config_of(BENCH, cell)
    assert (common.BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
    assert (common.BENCH / "traffic" / f"{cell['name']}.json").is_file()
    assert common.traffic_of(cell)


def test_every_config_file_is_under_paths_and_its_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        assert (common.ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in CELLS)


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in CELLS] + [m["name"] for m in METRICS] + \
        [w["traffic"] for w in CELLS] + \
        [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    for group in (BENCH["configs"], CELLS, METRICS):
        assert len({x["name"] for x in group}) == len(group)
    texts = [c["why"] for c in BENCH["configs"] + CELLS] + \
        [c["source"] for c in BENCH["configs"]] + \
        [m["layer"] for m in BENCH["per_layer"]]
    assert all(TEXT.match(t) for t in texts)


def test_metric_keys_and_bounds():
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_moves_is_reported_by_every_cell_listed(metric):
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1
    for cell in CELLS:
        if reported(metric, cell):
            assert reported(moved[0], cell), (metric["name"], cell["name"])
    assert (common.BENCH / "metrics" / f"{metric['name']}.py").is_file()


DRIVERS = sorted(p.stem for p in (common.BENCH / "drivers").glob("*.py"))


@pytest.mark.parametrize("kind", DRIVERS)
def test_every_driver_supplies_its_hooks(kind):
    """Beside ``run``, each driver kind brings its tiny size, its count of
    requests due in a window and its control, so that tiny runs, the
    manifest's tail check and ``bench/control.py`` name no kind."""
    drv = common.driver({"driver": kind})
    for hook in ("run", "tiny", "due_in_window", "control"):
        assert callable(getattr(drv, hook, None)), (kind, hook)


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_every_cell_reports_setup_another_and_a_layer(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if reported(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reported(m, cell) for m in BENCH["per_layer"])


def test_at_most_half_the_cells_ask_for_four_chips():
    four = sum(1 for w in CELLS if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in CELLS)
    assert four <= max(len(CELLS) // 2, 1)


def test_a_full_check_fits_its_time_with_24_cells():
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_roofline_and_mfu_shares_are_percent():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


TAIL = re.compile(r"_p(\d+)_")


def _due_in_window(cell):
    drv = common.driver(common.config_of(BENCH, cell))
    return drv.due_in_window(common.traffic_of(cell), BENCH["run_seconds"])


@pytest.mark.parametrize(
    "metric", [m for m in BENCH["end_to_end"] if TAIL.search(m["name"])],
    ids=lambda m: m["name"])
def test_a_tail_has_ten_requests_beyond_it(metric):
    q = int(TAIL.search(metric["name"]).group(1))
    for cell in CELLS:
        if reported(metric, cell):
            n = _due_in_window(cell)
            assert n * (100 - q) / 100 >= 10, (cell["name"], n)
