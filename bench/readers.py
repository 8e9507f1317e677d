"""Reductions the per-layer metric readers share.

Each reader in ``metrics/`` takes the ``bench.common.Run`` of a run with
``--trace 1`` and returns the metric's value, or None where the run holds
nothing to read it from (the harness then leaves the metric out).  Shares
are percent, and a share of a peak or a roofline is never returned as 0
for want of data.
"""
from __future__ import annotations

from bench.common import percentile


def idle_share(run):
    """Percent of the traced window in which no operation ran on the
    device (averaged over the chips)."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def reuse_share(run):
    """Percent of the completed analyses served by a single-flight twin or
    inside a vmapped batch."""
    routes = run.counters.get("routes")
    total = sum(routes.values()) if routes else 0
    if not total:
        return None
    return 100.0 * (routes["twin"] + routes["vmapped"]) / total


def subplan_node_share(run):
    """Percent of the plan's nodes that the analyses executed on their own
    took from the subplan cache instead of running (``executed`` of each
    ``AnalysisResult`` against the plan's node count)."""
    c = run.counters
    if not c.get("own"):
        return None
    return 100.0 * (1.0 - c["executed"] / (c["own"] * c["plan_nodes"]))


def compiles_in_window(run):
    """Programs compiled, or loaded from the persistent cache, between the
    window's start and its end (0 when the warm-up covered every shape)."""
    return run.counters.get("compiles_in_window")


def queue_wait_p85(run):
    """85th percentile of due -> joined the decode batch, ms (the join
    stamp is the runtime's ``RequestMetrics.joined_at``)."""
    waits = [r["queue_wait_ms"] for r in run.requests if r["ok"]]
    return percentile(waits, 85)


def mfu(run):
    """Percent of the chip's bf16 peak: model FLOPs of every token
    processed (prompt and decode, attention at each token's context) per
    second, from the window's start to the last finish."""
    f = run.extra.get("model_flops_per_s")
    if not f:
        return None
    return 100.0 * f / run.peaks["bf16_flops_per_s"]
