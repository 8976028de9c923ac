"""Share of the traced stretch in which no operation ran on the device
(1 - union of device-op intervals over the stretch), train cells."""
from benchmark.lib.readers import idle_share_pct as read  # noqa: F401
