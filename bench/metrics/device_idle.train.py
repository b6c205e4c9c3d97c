"""Device idle share over the measured window (see _idle.py)."""
from bench.metrics._idle import idle_percent as read  # noqa: F401
