"""XLA programs compiled or loaded inside the measured window (JAX's
monitoring events)."""
from bench.readers import compiles_in_window as read  # noqa: F401
