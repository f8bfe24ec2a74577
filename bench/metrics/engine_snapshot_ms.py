"""engine_snapshot_ms: `engine.snapshot` (the recovery snapshot's copy of the
batch) in the traced window, per mega-step."""
from bench.metrics._spans import snapshot_ms as read  # noqa: F401
