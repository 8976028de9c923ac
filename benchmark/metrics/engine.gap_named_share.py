"""Of the device's idle seconds inside the traced stretch, the share that falls
inside a leaf span of the program (``lib/program_spans.py gap_named``);
``by_span`` names the seconds, ``clock_residual_us`` is the clock mapping's
spread. Nothing to read without a device plane."""
from benchmark.lib.program_spans import gap_named as read  # noqa: F401
