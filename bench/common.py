"""What every cell of the benchmark shares: the manifest, the device and its
peaks, the compile counter, the profiler window, percentiles, and the
record a run hands to the per-layer metric readers.

Nothing here knows a configuration, a traffic mix or a metric by name:
those live in ``configs/``, ``traffic/`` and ``metrics/`` and are found by
the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


class BenchError(RuntimeError):
    """A fault of the set-up (device, manifest, files): no result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in {MANIFEST.name}")


def config_of(bench: dict, cell: dict) -> dict:
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            return {**load_json(ROOT / c["file"]), "name": c["name"]}
    raise BenchError(f"no configuration {cell['config']!r}")


def traffic_of(cell: dict) -> dict:
    return load_json(BENCH / "traffic" / f"{cell['name']}.json")


def metrics_of(bench: dict, cell: dict, section: str) -> list:
    """The metrics of ``section`` (``end_to_end`` / ``per_layer``) that
    this cell reports: those listing it, and those listing no cells."""
    return [m for m in bench[section]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cfg: dict):
    """The driver of a configuration's kind: ``drivers/<driver>.py``."""
    return load_module(BENCH / "drivers" / f"{cfg['driver']}.py",
                       f"bench_driver_{cfg['driver']}")


def reader(metric: str):
    """A per-layer metric's reader: ``metrics/<metric>.py``'s ``read``."""
    path = BENCH / "metrics" / f"{metric}.py"
    return load_module(path, "bench_metric_" + metric.replace(".", "_")
                       .replace("-", "_")).read


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (``peaks.json``); a kind
    not in the table is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def accelerator(chips: int):
    """The first ``chips`` TPU devices; raises when JAX finds fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}; nothing runs elsewhere")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} chip(s) attached, {chips} needed")
    return devices[:chips]


def check_chips(cfg: dict, cell: dict, devices) -> None:
    """A configuration states its chips (``deployment.chips``); they have
    to be its cell's, and attached."""
    chips = cfg["deployment"]["chips"]
    if chips != cell["chips"] or len(devices) < chips:
        raise BenchError(
            f"configuration {cfg['name']} runs on {chips} chip(s); the cell "
            f"asks for {cell['chips']}, and {len(devices)} are given")


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# --------------------------------------------------------------------------
# compilations, counted from JAX's own monitoring events
# --------------------------------------------------------------------------

_HIT = "/jax/compilation_cache/cache_hits"
_WRITE = "/jax/compilation_cache/cache_misses"   # recorded as a fresh
# compile is written to the persistent cache


class CompileCounter:
    """New executables in this process: those loaded from JAX's
    persistent compilation cache and those compiled afresh (and written
    to it)."""

    def __init__(self):
        import jax.monitoring as mon
        self.loaded = 0
        self.compiled = 0
        mon.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == _HIT:
            self.loaded += 1
        elif event == _WRITE:
            self.compiled += 1

    @property
    def programs(self) -> int:
        return self.loaded + self.compiled


# --------------------------------------------------------------------------
# the profiler window
# --------------------------------------------------------------------------

WINDOW_ANNOTATION = "bench.traced_window"


class Profiler:
    """A profiler trace of a few seconds, written to a temporary directory
    and reduced by ``trace.py`` as soon as it stops."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._ann = None
        self.summary = None
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax
        self.t_start = now()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._ann.__enter__()

    def stop(self, chips: int):
        import jax
        from bench.trace import reduce_dir
        self._ann.__exit__(None, None, None)
        self.t_stop = now()
        jax.profiler.stop_trace()
        try:
            self.summary = reduce_dir(self.dir, chips=chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.summary


class WindowTrace:
    """Traces ``prof.seconds`` of a window (nothing where ``prof`` is None)
    from the request path, starting ``trace_from`` seconds into it where the
    traffic file gives that (the device has work then), else at the end:
    each submit and completion calls :meth:`poll`, which starts the trace
    once ``start_at`` has passed and stops it ``prof.seconds`` later.  An
    event loop held by synchronous work runs no timer in time, but every
    request passes here."""

    def __init__(self, prof, t0: float, seconds: float, chips: int,
                 traffic: dict):
        self.prof, self.chips = prof, chips
        if prof is not None:
            last = seconds - prof.seconds
            self.start_at = t0 + min(traffic.get("trace_from", last), last)

    def poll(self) -> None:
        p = self.prof
        if p is None:
            return
        if p.t_start is None:
            if now() >= self.start_at:
                p.start()
        elif p.t_stop is None and now() >= p.t_start + p.seconds:
            p.stop(self.chips)

    def finish(self) -> None:
        """After the window: a trace still open stops here."""
        p = self.prof
        if p is not None and p.t_start is not None and p.t_stop is None:
            self.prof.stop(self.chips)


def annotate(obj, attr: str, name: str) -> None:
    """Wrap the bound method ``obj.attr`` in a host annotation ``name``,
    so idle gaps on the device can be charged to what the host did."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    setattr(obj, attr, wrapped)


# --------------------------------------------------------------------------
# statistics
# --------------------------------------------------------------------------

TAILS = (50, 75, 80, 85, 90, 95, 99)


def tails(prefix: str, values, unit: str = "ms") -> dict:
    """``{prefix}_p{q}_{unit}`` for each percentile of :data:`TAILS`; a
    cell lists the ones its sample supports."""
    return {f"{prefix}_p{q}_{unit}": percentile(values, q) for q in TAILS}


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics), or None
    for an empty sample."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


# --------------------------------------------------------------------------
# what a run hands to the readers and to the result line
# --------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with its limit: passes when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Run:
    """One run of a cell, as the readers see it.

    ``requests``: one dict per request due in the window, with at least
    ``due``, ``done`` (host clock, seconds; ``done`` None if it never
    came) and ``ok``; drivers add their own keys.  ``counters``: counts
    read from the program.  ``trace``: the reduced profiler window, or
    None in a run without ``--trace 1``."""
    cell: dict
    config: dict
    traffic: dict
    seconds: float
    peaks: dict
    requests: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    trace: object = None
    extra: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)
    end_to_end: dict = field(default_factory=dict)
    setup_s: float = 0.0

    @property
    def attempted(self) -> int:
        return len(self.requests)

    @property
    def failed(self) -> int:
        return sum(1 for r in self.requests if not r["ok"])

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


@dataclass
class Ctx:
    """What a driver is given besides the cell's files."""
    seed: int
    seconds: float
    trace: bool
    devices: list
    counter: CompileCounter
    t_process: float        # host clock at the process's start


def now() -> float:
    return time.perf_counter()
