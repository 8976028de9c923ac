"""paddle_tpu.observability — always-on runtime telemetry.

The XLA-idiomatic successor to the reference's two-tier profiler
(``paddle/fluid/platform/profiler/``) and ``monitor``/``stat`` registry:
instead of an attach-a-profiler workflow, the training hot path carries a
low-overhead measurement layer that is always there (gated by
``FLAGS_telemetry`` = ``off`` | ``metrics`` (default) | ``trace``):

- :mod:`.metrics` — labeled counters/gauges/log-bucket histograms with
  Prometheus-text and JSON exposition; absorbs the old
  ``profiler.monitor`` flat stat registry (which now forwards here).
- :mod:`.trace` — thread-safe nestable ``span()`` context managers
  buffering into an in-memory ring (records with ``id``/``parent`` on
  ``perf_counter_ns``; on unless ``FLAGS_telemetry=off``), exported as
  chrome-trace JSON or JSONL; ``trace`` mode adds the open-span table.
- :mod:`.request_timeline` — the serving tier's per-request phase
  accounting (queue/prefill/decode/detokenize, exact-value p50/p99),
  feeding the ``serving.*`` metric families.
- :mod:`.step_monitor` — the :class:`StepTimeline` (per-step phases:
  data/h2d/compile/device/offload_in/offload_out/callbacks), the
  recompile sentinel (Diagnostic O001 with the exact shape/dtype diff
  when a jitted callable churns signatures), and HBM watermarks sampled
  from ``device.memory_stats()`` and cross-checked against
  ``tools/hbm_budget.py`` plans (O002).
- :mod:`.flight_recorder` — the crash-persistent tier
  (``FLAGS_flight_recorder=off|on``): an mmap-backed ring of CRC-framed
  records per process incarnation that survives SIGKILL/``os._exit``
  with no flush; :mod:`.fleet` merges every incarnation's ring with the
  fsynced journals into one globally-ordered fleet timeline, and
  ``tools/postmortem.py`` reconstructs + verifies the story.
- :mod:`.live` — the live tier (``FLAGS_fleet_telemetry=off|on``): each
  worker publishes CRC-framed, atomically-replaced registry snapshots
  under ``<run>/fleet/`` on a fixed cadence; the aggregator merges them
  into one labeled fleet view (exact log2-bucket histogram merge,
  fresh/slow/dead staleness) and :mod:`.alerts` evaluates declarative
  threshold/rate/absence SLO rules against it (Diagnostics L001-L003 +
  flight-recorder ``alert`` records — the autoscaler-input contract);
  ``tools/fleet_top.py`` renders the view live or as ``--once --json``.

Wiring: ``framework.sharded.TrainStep``, ``framework.offload``,
``distributed.pipeline_schedule``, ``io.dataloader`` and ``hapi`` report
into the process-wide timeline (``step_monitor.current()``);
``tools/trace_view.py`` renders a ``trace.export_jsonl`` dump. See
OBSERVABILITY.md.
"""

from . import metrics  # noqa: F401
from . import trace  # noqa: F401
from . import flight_recorder  # noqa: F401
from . import step_monitor  # noqa: F401
from . import request_timeline  # noqa: F401
from . import fleet  # noqa: F401
from . import live  # noqa: F401
from . import alerts  # noqa: F401
from .trace import span, telemetry_mode  # noqa: F401
from .step_monitor import (StepTimeline, RecompileSentinel,  # noqa: F401
                           current, reset_default, instrument_jitted,
                           fingerprint, fingerprint_diff)
from .request_timeline import RequestTimeline  # noqa: F401
from .flight_recorder import FlightRecorder  # noqa: F401

__all__ = [
    "metrics", "trace", "step_monitor", "request_timeline",
    "flight_recorder", "fleet", "live", "alerts",
    "span", "telemetry_mode",
    "StepTimeline", "RecompileSentinel", "RequestTimeline",
    "FlightRecorder",
    "current", "reset_default",
    "instrument_jitted", "fingerprint", "fingerprint_diff",
]
