"""Percent of plan nodes that analyses executed on their own took from the
subplan cache (the runtime's AnalysisResult.executed)."""
from bench.readers import subplan_node_share as read  # noqa: F401
