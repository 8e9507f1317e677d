"""Reduce a profiler trace to what the per-layer metrics read.

A trace is JAX's ``.xplane.pb`` (an XSpace), read with
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per HLO operation as it ran on the chip.
The host plane ``/host:CPU`` holds the benchmark's own annotations, named
``bench.*``; the one named ``bench.traced_window`` bounds the window.

From that, :func:`reduce` gives

  * ``busy_s``: the union of the op intervals inside the window, averaged
    over the chips, and ``window_s``, the window's length;
  * ``device_ops``: seconds per HLO op, most first, averaged over the
    chips.  An op is named by its instruction without the ``.<n>``
    suffix and by its result's type without the layout: the event
    ``%fusion.12 = f32[8388608]{0:T(1024)} fusion(...)`` counts under
    ``fusion f32[8388608]``;
  * ``idle_gaps``: the idle seconds of chip 0 charged to the innermost
    ``bench.*`` annotation open on the host at each gap's middle
    (``host.none`` where none is), most first.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.traced_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NO_ANNOTATION = "host.none"
_SUFFIX = re.compile(r"\.\d+$")
_HLO = re.compile(r"^%?([\w.-]+)\s*=\s*(\S+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    chips: int
    device_ops: list = field(default_factory=list)   # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)    # [[annotation, s]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    all_ops: list = field(default_factory=list)      # every op, as above


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint, sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(event_name: str) -> str:
    m = _HLO.match(event_name)
    if not m:
        return _SUFFIX.sub("", event_name.strip())
    result = m.group(2)
    result = "tuple" if result.startswith("(") else _LAYOUT.sub("", result)
    return f"{_SUFFIX.sub('', m.group(1))} {result}"


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def reduce_dir(directory: str, *, chips: int) -> TraceSummary:
    return reduce(load(find_xplane(directory)), chips=chips)


def _device_planes(data):
    planes = [p for p in data.planes
              if p.name.startswith(DEVICE_PREFIX)
              and p.name[len(DEVICE_PREFIX):].isdigit()]
    return sorted(planes, key=lambda p: int(p.name[len(DEVICE_PREFIX):]))


def _host_annotations(data):
    out = []
    for p in data.planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


def reduce(data, *, chips: int) -> TraceSummary:
    """Reduce a ``ProfileData`` to a :class:`TraceSummary` over the window
    annotation, for the first ``chips`` device planes."""
    notes = _host_annotations(data)
    wins = [(s, e) for n, s, e in notes if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = wins[0][0], wins[-1][1]
    notes = [(n, s, e) for n, s, e in notes if n != WINDOW]
    planes = _device_planes(data)[:chips]
    if len(planes) < chips:
        raise ValueError(f"{len(planes)} device planes in the trace, "
                         f"{chips} expected")
    busy, ops, gaps_of_first = [], {}, []
    for i, plane in enumerate(planes):
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.end_ns, lo, hi)
                if e <= s:
                    continue
                spans.append((s, e))
                name = op_name(ev.name)
                ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
        merged = union(spans)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps_of_first = [(edges[k], edges[k + 1])
                             for k in range(0, len(edges), 2)
                             if edges[k + 1] > edges[k]]
    idle = {}
    for s, e in gaps_of_first:
        mid = (s + e) / 2
        open_ = [(ns, n) for n, ns, ne in notes if ns <= mid < ne]
        label = max(open_)[1] if open_ else NO_ANNOTATION
        idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9
    n = len(planes)
    all_ops = sorted(([k, v / n] for k, v in ops.items()),
                     key=lambda kv: -kv[1])
    return TraceSummary(
        busy_s=sum(busy) / n, window_s=(hi - lo) * 1e-9, chips=n,
        device_ops=all_ops[:10],
        idle_gaps=sorted(([k, v] for k, v in idle.items()),
                         key=lambda kv: -kv[1])[:10],
        all_ops=all_ops)
