"""engine_crop_ms: `engine.crop` (cropping each live slot's state for its job)
in the traced window, per mega-step."""
from bench.metrics._spans import crop_ms as read  # noqa: F401
