"""Fault-tolerance tier: prove recovery, then measure it.

The elastic manager (``distributed/fleet/elastic``) can relaunch a dead
pod and ``distributed/checkpoint`` can write snapshots; this package
connects them into a story a production run can rely on:

- :class:`~paddle_tpu.fault.checkpoint_manager.CheckpointManager` — async
  train-state snapshots with tmp-dir + atomic-rename commit, per-array
  checksums, retention, and ``latest_complete()`` that skips torn writes;
- :class:`~paddle_tpu.fault.injection.FaultPlan` /
  :class:`~paddle_tpu.fault.injection.FaultInjector` — deterministic,
  seed-driven kills (mid-step SIGKILL, mid-checkpoint-write SIGKILL,
  SIGTERM preemption with a grace-window final save);
- :mod:`~paddle_tpu.fault.goodput` — ``useful_step_time /
  wall_time_including_restart`` plus restart/lost-step/checkpoint-duration
  accounting, published as ``fault.*`` metrics;
- :mod:`~paddle_tpu.fault.drill` — the end-to-end
  train→kill→relaunch→resume drill (``tools/fault_drill.py``) that asserts
  bitwise loss parity against an uninterrupted run and emits the goodput
  record (``tests/test_fault_drill.py``);
- :mod:`~paddle_tpu.fault.health` /
  :mod:`~paddle_tpu.fault.guardian` — the training-health tier for runs
  that are *alive and wrong*: the fused step sentinel (NaN/spike/
  explosion, update gated in-graph), the hang watchdog, the SDC canary,
  and the :class:`~paddle_tpu.fault.guardian.Guardian` policy engine
  (skip-batch / rewind-to-last-good / relaunch / halt) driven by the
  checkpoint manager's promoted last-good pointer
  (``tools/health_drill.py`` proves the loop end to end).

See ``RESILIENCE.md`` for the checkpoint format and drill usage.
"""

from .checkpoint_manager import CheckpointManager  # noqa: F401
from .goodput import compute_goodput, parse_train_log  # noqa: F401
from .guardian import Decision, Guardian  # noqa: F401
from .health import (BatchCursor, HangWatchdog, SdcCanary,  # noqa: F401
                     StepSentinel, HANG_EXIT_CODE)
from .injection import (FAULT_KINDS, FaultEvent, FaultInjector,  # noqa: F401
                        FaultPlan, PREEMPTION_EXIT_CODE)

__all__ = ["CheckpointManager", "FaultPlan", "FaultEvent", "FaultInjector",
           "FAULT_KINDS", "PREEMPTION_EXIT_CODE", "compute_goodput",
           "parse_train_log", "Guardian", "Decision", "StepSentinel",
           "HangWatchdog", "SdcCanary", "BatchCursor", "HANG_EXIT_CODE"]
