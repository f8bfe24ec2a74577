"""idle_outside_engine_spans: per cent of chip 0's idle time in the window
that no leaf span of the serving engine covers."""
from bench.metrics._spans import idle_outside_spans as read  # noqa: F401
