"""Readings that set a cell's limits: the program's number and the
control's, on several seeds, at the cell's own size.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

The control is the plain reference put in the program's place, computed
in the nearest precision below the one the configuration states.  Each
driver supplies its own (``control`` in ``bench/drivers/<driver>.py``):

  * analysis cells (float32): the NumPy reference with every intermediate
    rounded to bfloat16, over the queries a run of the cell would draw,
    compared with the float64 reference as a run compares the program;
  * LM cells (bfloat16 compute): the reference forward with every matmul
    operand rounded to scaled float8 (e4m3); its gap is read at the
    positions and tokens a run of the cell served.

For an LM cell each seed is a whole run of the cell (its window of
``--seconds``), whose own number is printed beside the control's.  The
benchmark's runs never run this.  One JSON line per seed.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import common
    from repro.core.backend import enable_compile_cache
    bench = common.manifest()
    cell = common.workload(bench, args.workload)
    drv = common.driver(common.config_of(bench, cell))
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = drv.control(bench, cell, seed, args.seconds)
        print(json.dumps({"workload": cell["name"], "seed": seed, **out,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
