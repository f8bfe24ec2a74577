"""engine_device_ms: `engine.device` (the cached step's dispatch and the wait
for its results) in the traced window, per mega-step."""
from bench.metrics._spans import device_ms as read  # noqa: F401
