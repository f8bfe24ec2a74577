"""device_idle_share: 1 - union of device-op intervals / traced window."""
from bench.metrics._shares import idle_share as read  # noqa: F401
