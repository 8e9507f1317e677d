"""Percent of completed analyses served by a single-flight twin or a
vmapped batch (the runtime's AnalysisResult flags)."""
from bench.readers import reuse_share as read  # noqa: F401
