"""A cell of the benchmark cut to a size the CPU runs in seconds, driven
through the same driver code as on the chip, with the device check left
out."""
from __future__ import annotations

import time

import jax

from bench import common

TINY_DATA = dict(tweets=1 << 13, hashtags=1 << 8, users=1 << 10,
                 vocab=1 << 9, doc_tokens=[7, 15])
TINY_CUT = dict(postings=56000, edges=6000)
TINY_LM = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=512)


def cell_files(name: str):
    bench = common.manifest()
    cell = common.workload(bench, name)
    cfg = common.config_of(bench, cell)
    traffic = common.traffic_of(cell)
    if cfg["driver"] == "analysis":
        cfg["deployment"]["data"] = dict(TINY_DATA, cut=TINY_CUT)
        cfg["deployment"]["analysis_tick"] = 4
        if "rate_per_s" in traffic:
            traffic["rate_per_s"] = 40.0
    else:
        cfg.update(TINY_LM)
        cfg["deployment"].update(max_batch=4, max_seq=128)
        traffic.update(rate_per_s=4.0, max_total=128)
        traffic["prompt"].update(median=16, min=4, max=64)
        traffic["output"].update(median=8, min=2, max=32)
    return bench, cell, cfg, traffic


def drive(name: str, *, seconds: float = 2.0, seed: int = 2**31 + 11,
          patch=None, trace: bool = False):
    """One run of cell ``name`` at the tiny size; ``patch(driver, cfg)``
    may break the program underneath before it runs.  Returns the Run."""
    bench, cell, cfg, traffic = cell_files(name)
    drv = common.driver(cfg)
    if cfg["driver"] == "lm":
        from repro.configs.base import get_smoke_config
        from repro.models import build_model
        drv.program_model = lambda c: build_model(drv.with_deployment(
            get_smoke_config(c["deployment"]["arch"]), c))
    if patch is not None:
        patch(drv, cfg)
    run = common.Run(cell=cell, config=cfg, traffic=traffic, seconds=seconds,
                     peaks=common.peaks("TPU v5 lite"))
    ctx = common.Ctx(seed=seed, seconds=seconds, trace=trace,
                     devices=jax.devices()[:1],
                     counter=common.CompileCounter(),
                     t_process=time.perf_counter())
    drv.run(run, ctx)
    return run
