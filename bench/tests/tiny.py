"""A cell of the benchmark cut to a size the CPU runs in seconds, driven
through the same driver code as on the chip, with the device check left
out.

Each driver cuts its own configurations and traffic (its ``tiny``).  A
run takes the cell's chips from this process's devices; a test of a cell
on more chips than that runs again, whole, in a subprocess with as many
host-platform CPU devices (:func:`elsewhere`: the device count is fixed
when JAX starts).
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax

from bench import common


def cell_files(name: str):
    """(manifest, cell, configuration, traffic) of cell ``name``, at the
    sizes the manifest gives."""
    bench = common.manifest()
    cell = common.workload(bench, name)
    return bench, cell, common.config_of(bench, cell), \
        common.traffic_of(cell)


def files(cell):
    """(cell, configuration, traffic) of ``cell``: a manifest cell's name,
    or a pair (name, chips), that cell's configuration declared on
    ``chips`` chips, which the manifest does not hold."""
    if isinstance(cell, str):
        return cell_files(cell)[1:]
    name, chips = cell
    _, spec, cfg, traffic = cell_files(name)
    cfg["deployment"]["chips"] = chips
    return dict(spec, name=label(cell), chips=chips), cfg, traffic


def label(cell) -> str:
    return cell if isinstance(cell, str) else "{}.chips{}".format(*cell)


def elsewhere(request, cell) -> bool:
    """Whether the calling test ran in a subprocess: where ``cell`` asks
    for more chips than this process has devices, the test is run again,
    whole, with that many host-platform CPU devices, and has to pass
    there; the caller then returns."""
    chips = files(cell)[0]["chips"]
    if len(jax.devices()) >= chips:
        return False
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={chips}").strip()
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", request.node.nodeid],
        env=env, cwd=common.ROOT, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stdout[-6000:] + out.stderr[-2000:]
    return True


def drive(cell, *, seconds: float = 2.0, seed: int = 2**31 + 11,
          patch=None, trace: bool = False):
    """One run of ``cell`` (as :func:`files` takes it) at the tiny size,
    in this process, on its first ``chips`` devices; ``patch(driver,
    cfg)`` may break the program underneath before it runs.  Returns the
    Run."""
    cell, cfg, traffic = files(cell)
    drv = common.driver(cfg)
    for attr, fn in drv.tiny(cfg, traffic).items():
        setattr(drv, attr, fn)
    if patch is not None:
        patch(drv, cfg)
    devices = jax.devices()[:cell["chips"]]
    common.check_chips(cfg, cell, devices)
    run = common.Run(cell=cell, config=cfg, traffic=traffic, seconds=seconds,
                     peaks=common.peaks("TPU v5 lite"))
    ctx = common.Ctx(seed=seed, seconds=seconds, trace=trace,
                     devices=devices, counter=common.CompileCounter(),
                     t_process=time.perf_counter())
    drv.run(run, ctx)
    return run
