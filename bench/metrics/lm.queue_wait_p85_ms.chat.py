"""85th percentile of an LM request's wait from due to joining the
decode batch, ms."""
from bench.readers import queue_wait_p85 as read  # noqa: F401
