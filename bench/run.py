"""Run one cell of the benchmark once, on the TPU it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/``), its traffic
(``bench/traffic/<cell>.json``) and its metrics come from
``BENCHMARK.json``; the configuration names its driver
(``bench/drivers/<driver>.py``) and each per-layer metric has its reader
(``bench/metrics/<metric>.py``).  With ``--trace 0`` the result holds the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a few seconds of the window.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.  Otherwise the last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
number compared with its limit), and the last lines of standard error
repeat the checks.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the checkout (for ``bench.*``) and the program's sources, in place of
# this script's own directory
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(run, cell, bench, trace: bool, devices) -> dict:
    from bench import common
    metrics = {}
    if trace:
        for m in common.metrics_of(bench, cell, "per_layer"):
            value = common.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**run.end_to_end, "setup_s": run.setup_s}
        for m in common.metrics_of(bench, cell, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.extra["memory_peak_bytes"]}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


def execute(args, bench, cell, devices, counter):
    """Set up, measure and check one run; returns the filled ``Run``."""
    from bench import common
    cfg = common.config_of(bench, cell)
    common.check_chips(cfg, cell, devices)
    run = common.Run(cell=cell, config=cfg, traffic=common.traffic_of(cell),
                     seconds=args.seconds,
                     peaks=common.peaks(devices[0].device_kind))
    ctx = common.Ctx(seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     counter=counter, t_process=T_PROCESS)
    common.driver(cfg).run(run, ctx)
    return run


def main(argv=None) -> int:
    args = parse(argv)
    try:
        from bench import common
        bench = common.manifest()
        cell = common.workload(bench, args.workload)
        devices = common.accelerator(cell["chips"])
        common.peaks(devices[0].device_kind)
        from repro.core.backend import enable_compile_cache
    except Exception as e:  # no device, no program, no manifest: no result
        print(f"[bench] {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    common.log(f"{cell['name']} seed {args.seed}, {args.seconds} s, trace "
               f"{args.trace}; {devices[0].device_kind} x{len(devices)}; "
               f"compile cache {enable_compile_cache()}")
    counter = common.CompileCounter()
    run = execute(args, bench, cell, devices, counter)
    line = result_line(run, cell, bench, bool(args.trace), devices)
    common.log(f"set-up {run.setup_s} s; programs compiled "
               f"{counter.compiled}, loaded from the cache {counter.loaded}")
    print(json.dumps(line), flush=True)
    for c in run.checks:
        print(f"[bench] check {c.name}: {c.value} (limit {c.limit}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
