"""idle_share.chat: percent of the traced window the device was idle."""
from bench.readers import idle_share as read  # noqa: F401
