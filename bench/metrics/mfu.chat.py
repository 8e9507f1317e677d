"""Model FLOP/s utilisation of LM serving, percent of the bf16 peak."""
from bench.readers import mfu as read  # noqa: F401
