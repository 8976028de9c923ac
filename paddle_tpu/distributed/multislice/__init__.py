"""Multi-slice (cross-DCN) scale-out tier.

One pod slice is an ICI torus; a multi-slice job joins several over the
data-center network. This package makes the two link classes explicit:

- :class:`~.topology.SliceTopology` — the 2-tier mesh with an outermost
  ``slice`` axis, per-axis link classes, and per-slice local views;
- :class:`~.reducer.HierarchicalGradReducer` — the intra-slice
  reduce-scatter → inter-slice DCN allreduce → intra-slice all-gather
  gradient reduction (DCN moves 1/ici_size of each bucket), with buckets
  sized per link class and every stage declared to
  ``analysis.comm_check`` (rules C004/C005).

- :class:`~.heartbeat.SliceHeartbeatMonitor` — per-slice liveness +
  progress beats so the training-health watchdog's escalation can tell a
  **dead** slice (stale beat → relaunch) from a **slow** one (fresh beat,
  trailing step counter → back off).

``framework.sharded.TrainStep`` consumes the reducer behind
``FLAGS_multislice=off|flat|hierarchical``; ``tools/lint_graph.py
--model multislice`` and ``tests/test_multislice.py`` verify the
composition chiplessly on the CPU mesh; the guarded drill
trainer (``fault/_trainer.py`` health mode) beats the monitor per step.
"""

from .heartbeat import SliceHeartbeatMonitor, classify_liveness
from .reducer import HierarchicalGradReducer
from .topology import SLICE_AXIS, SliceTopology

__all__ = ["SliceTopology", "HierarchicalGradReducer", "SLICE_AXIS",
           "SliceHeartbeatMonitor", "classify_liveness"]
