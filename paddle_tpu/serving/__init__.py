"""paddle_tpu.serving — the production inference tier.

The one-shot AOT predictor (:mod:`paddle_tpu.inference`) answers one
request at a time with no KV reuse; this package is the engine that
serves *traffic*: a block-paged KV cache in device memory with a
deterministic free-list allocator and host-memory spill for preempted
sequences (vLLM/PagedAttention, SOSP'23), a continuous-batching
scheduler that re-forms the decode batch at token-iteration granularity
(Orca, OSDI'22), and bucketed-shape compilation so ragged traffic
compiles a bounded executable set with the O001 recompile sentinel
standing guard. The resilience tier (:mod:`.resilience`, RESILIENCE.md)
makes the engine degrade instead of dying: per-request deadlines and
priorities, bounded admission with typed :class:`Rejected` backpressure,
overload load shedding (:class:`ShedPolicy`), per-request failure
isolation (F003 — pool exhaustion and spill errors never cross the
engine loop), and the exactly-once :class:`RequestJournal` the serve
drill (``tools/serve_drill.py``) kills the process against.
The benchmark's serving cells (``BENCHMARK.json``, ``benchmark/run.py``)
measure tokens/s and inter-token latency on the chip;
``tests/test_serving.py`` holds the SLO attainment and shed-rate
arithmetic; ``lint_graph --model serving`` statically verifies the
prefill/decode programs and the declared dispatch plan.
"""

from .buckets import BucketSet, pow2_buckets  # noqa: F401
from .engine import ServingEngine  # noqa: F401
from .paged_cache import (BlockAllocator, NULL_BLOCK,  # noqa: F401
                          OutOfBlocksError, PagedKVCache, SpillError)
from .prefix_tree import PrefixCache, PrefixNode  # noqa: F401
from .resilience import (Rejected, RequestJournal,  # noqa: F401
                         ShedPolicy)
from .scheduler import (FCFSScheduler, Request, Sequence,  # noqa: F401
                        Status, TERMINAL_STATUSES)
from .speculative import (ModelDrafter, NGramDrafter,  # noqa: F401
                          pick_gamma, tune_gamma)

__all__ = [
    "ServingEngine", "Request", "Sequence", "Status", "FCFSScheduler",
    "PagedKVCache", "BlockAllocator", "OutOfBlocksError", "SpillError",
    "NULL_BLOCK", "BucketSet", "pow2_buckets",
    "Rejected", "RequestJournal", "ShedPolicy", "TERMINAL_STATUSES",
    "PrefixCache", "PrefixNode", "NGramDrafter", "ModelDrafter",
    "pick_gamma", "tune_gamma",
]
