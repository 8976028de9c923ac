"""Longest ``host/gc`` span in the window, train cells; ``count`` and
``total_ms`` beside it."""
from benchmark.lib.program_spans import gc_pause as read  # noqa: F401
