"""Mean duration of a ``step`` span of the window: what queueing one optimizer
step costs the host; ``by_span`` its ``h2d``, ``checks``, ``device``, ``end``
and own time."""
from benchmark.lib.program_spans import train_dispatch as read  # noqa: F401
