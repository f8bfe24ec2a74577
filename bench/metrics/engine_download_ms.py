"""engine_download_ms: `engine.download` (the copy of the results back to host
arrays) in the traced window, per mega-step."""
from bench.metrics._spans import download_ms as read  # noqa: F401
