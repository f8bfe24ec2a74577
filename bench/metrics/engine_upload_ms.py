"""engine_upload_ms: `engine.upload` (the host-to-device copy of the batch) in
the traced window, per mega-step."""
from bench.metrics._spans import upload_ms as read  # noqa: F401
