"""engine_prime_ms: `engine.prime` (packing jobs into slots) in the traced
window, per mega-step."""
from bench.metrics._spans import prime_ms as read  # noqa: F401
