"""A configuration runs on the chips it states: the analysis driver on one
chip builds the program it has always built, and no driver kind runs on
other chips than its cell's.  (A tiny run on four devices:
``test_correct.py``.)"""
import jax
import pytest

from bench import common, social
from bench.tests import tiny

BENCH = common.manifest()
ANALYSIS = next(w["name"] for w in BENCH["workloads"]
                if common.config_of(BENCH, w)["driver"] == "analysis")


def test_one_chip_builds_no_mesh_and_the_unsharded_plan():
    from repro.core.adil_parser import parse_adil
    from repro.core.ir import SystemCatalog, standard_catalog
    from repro.stores import store_engines
    _, _, cfg, traffic = tiny.cell_files(ANALYSIS)
    drv = common.driver(cfg)
    drv.tiny(cfg, traffic)
    cell = drv.Cell(cfg, traffic, 2**31 + 5, jax.devices()[:1])
    assert cell.mesh is None
    assert cell.rt.syscat == SystemCatalog()
    assert all(leaf["devices"] == 1 and not leaf["split"]
               for leaf in cell.placement)
    stores = social.build_stores(cell.data)
    analysis = parse_adil(social.adil_script(*stores), standard_catalog())
    for name, store in zip(("tweets", "g", "cx"), stores):
        analysis.bind(name, store)
    plain = analysis.compile(SystemCatalog(), engines=store_engines(
        pallas="pallas" in cfg["deployment"]["engines"]))
    assert cell.fn.plan_id == plain.plan_id
    assert not any(n.attrs.get("dist") for n in cell.fn.concrete.topo())


@pytest.mark.parametrize("chips", [2, 4])
def test_a_configuration_on_other_chips_than_its_cell_is_refused(chips):
    def on_chips(drv, cfg):
        cfg["deployment"]["chips"] = chips
    with pytest.raises(common.BenchError, match=f"runs on {chips} chip"):
        tiny.drive(ANALYSIS, patch=on_chips)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_driver_kind_is_held_to_its_cells_chips(cell):
    """The harness checks the chips before a driver of any kind runs."""
    def on_four(drv, cfg):
        cfg["deployment"]["chips"] = 4
    with pytest.raises(common.BenchError, match="runs on 4 chip"):
        tiny.drive(cell, patch=on_four)
