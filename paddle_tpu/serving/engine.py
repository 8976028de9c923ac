"""The serving engine: continuous batching over paged KV on the AOT stack.

Composition of the two load-bearing serving ideas on our machinery:

- **paged KV** (:mod:`.paged_cache`): every sequence's KV lives in
  fixed-size blocks of one device pool, allocated from a deterministic
  free list, spilled to the host memory tier under pressure;
- **continuous batching** (:mod:`.scheduler`): requests join and leave
  the decode batch at token-iteration granularity — the decode
  executable runs every iteration over *whoever is resident*, padded to
  a registered batch-width bucket;
- **bucketed-shape compilation** (:mod:`.buckets`): prefill lengths and
  decode widths are padded to small registered bucket sets, so a ragged
  request trace compiles at most ``len(prefill_buckets) +
  len(decode_buckets)`` executables. Each executable family is watched
  by its own :class:`~paddle_tpu.observability.RecompileSentinel` whose
  threshold *is* the bucket count — O001 stays silent exactly while the
  bucketing works, and fires (through the analysis channel) the moment
  an unregistered signature slips through.

Three throughput tiers compose on top (ISSUE 13; each default-off and
byte-identical when off):

- **radix prefix sharing** (``FLAGS_serve_prefix_cache``,
  :mod:`.prefix_tree`): prompts sharing a full-block prefix attach
  copy-on-write to the same pages via the refcounted allocator; only
  the suffix is prefilled (through the ``extend`` executable), eviction
  is LRU-over-refcount-0 trie leaves with a one-copy host spill tier;
- **chunked prefill** (``FLAGS_serve_chunked_prefill``): long prompts
  prefill in fixed-token chunks interleaved with decode iterations —
  the per-iteration prefill token budget — so a 2k-token prompt no
  longer freezes resident decodes; block tables grow incrementally;
- **speculative decoding** (``FLAGS_serve_speculative``,
  :mod:`.speculative`): a drafter proposes gamma tokens which the
  target verifies in ONE bucketed decode-gamma ``extend`` dispatch
  (greedy accept-prefix rule; the target's own token commits at the
  first mismatch), with accepted-length histograms feeding the
  autotune cache's choice of gamma.

The prefill step runs the model's attention forward on one
bucket-padded prompt and scatters each layer's page rows into the
sequence's pages; the decode step is a batched single-query pass that
writes the new token's page row and attends each sequence's pages through
its block table up to its own context (a Pallas kernel on a TPU, gather +
dense attention behind a length mask elsewhere); the ``extend`` step is the
multi-token generalization (offset-causal over gathered pages) shared by
chunked prefill, suffix prefill after a prefix hit, and speculative
verification. Executables
take the page pool **donated** — the pool is updated in place, never
copied — and the whole dispatch sequence is declared as a
:class:`~paddle_tpu.analysis.plan_check.StepPlan` so the
donation-lifetime rules (D001/D002) and the COW write-isolation rule
(D005: a copy-on-write shared buffer is never written or donated)
verify the serving path like every training tier (``lint_graph --model
serving``). At runtime the same isolation is asserted per dispatch:
no scatter ever targets a device block the prefix tree holds.

**One decode iteration is always in flight, and the next is launched before
its tokens are taken.** ``step()`` admits (the prefills queue behind the
decode program the last step launched), tops up blocks, builds and launches
the next decode iteration, and only then waits for the last one's tokens and
commits them. A row that was in the last launch reads its token on the
device, from that launch's result (the decode program takes the previous
result ``prev`` and a row map ``src``: ``tokens = where(src >= 0, prev[src],
tokens)``); a row that was not (fresh from a prefill, restored, back after a
preemption) is fed by the host as before, in the same launch of the same
program. So on a step that admits nothing the device goes from one decode
program into the next, and the wake-up after the wait, the commit, the
top-up, the build and the launch all happen beside it; on a step that admits,
the prefill still waits for its first token and the device idles from there
to the launch. The host decides a launch's rows before it knows the tokens in
flight, with one guess: a row that reaches ``max_new_tokens`` with the token
in flight is known to end and is left out, but a token that ends a request
some other way (``eos_token_id``) is not known, so such a row runs once more
and that token is dropped when it arrives. The same happens to a row
cancelled or preempted while its token is in flight: dropped, and computed
again if the row comes back (``_decode_collect``). A launch whose bucket
differs from the one in flight takes the old order for that once (tokens
first, every row fed by the host): each bucket has one compiled program,
whose ``prev`` has that bucket's shape. ``serving.decode_rows{fed}`` counts
the rows by where their token came from, and the dropped tokens. A request
joins the batch at the launch after its prefill, so its second token is seen
one step after its first.

**The model seam.** The programs know no model: they ask the model for its
layer step, for what it caches a token and for how it generates. A model
serves by giving (``text/models/gpt.py``, ``text/models/deepseek_v2.py``,
``text/models/sdar_moe.py`` and ``text/models/olmo_hybrid.py`` do):

- ``serve_cache_rows()``: the shapes of a token's page rows, one a pool:
  keys and values a head ``((KH, D), (KH, D))``, one latent row ``((W,),)``,
  or keys and values fused into one row ``((2 * KH, D),)`` (keys the first
  ``KH`` heads: a page's keys and values are then one stretch of one pool,
  which a kernel fetches as one descriptor; :mod:`.paged_cache` builds one
  pool a row, its pages laid out by the row's shape so that no axis is
  padded on the chip);
  ``serve_dtype()``; ``serve_latent_value_dim`` (None, or where the pool is
  latent the part of a row that is its value);
- ``serve_generation``: how it generates. ``None``: a token a row a step,
  and the decode program is the single-query one. A block spec (block
  length, denoising steps, confidence threshold, mask id): generation by
  diffusion over blocks, and the decode program is the block-decode program
  (below); the layer then gives ``serve_attend_block(q, pools, tables,
  lengths, block_size, layer)`` (the block's queries over each row's pages
  up to ``lengths``) where the others give ``serve_attend_paged``;
- ``serve_embed(ids, pos)``, ``serve_layers()``, ``serve_final_norm(x)``,
  ``logits(hidden)``;
- a layer: ``serve_project(x, pos) -> (q, rows)`` (the queries and the
  token's page rows), ``serve_attend_prefill(q, rows)`` (causal, over the
  prompt itself), ``serve_attend_paged(q, pools, tables, lengths,
  block_size, layer)`` (one query a row, through the block table),
  ``serve_attend_extend(q, pools, tables, pos, block_size, layer)``
  (several queries a row, offset-causal) and ``serve_finish(x, o, real) ->
  (x, counts or None)`` (the rest of the block);
- ``serve_counts`` (0, or how many int32 counts the layers' ``serve_finish``
  return): the prefill and decode programs then return them behind the
  token, in the one array the host already waits for, and the engine hands
  them to ``model.serve_record_counts(counts, n_real_tokens)`` (the expert
  counters of a routed-expert model);
- **a layer that keeps a state a sequence** (``text/models/olmo_hybrid.py``'s
  linear layers) says ``serve_keeps = "state"`` and gives, in place of the
  four steps above, ``serve_prefill_state(x, n_tokens) -> (x, parts)`` (the
  prompt's first ``n_tokens`` positions real; ``parts`` what the layer keeps
  after them, one array a part with a leading axis of 1) and
  ``serve_decode_state(x, pools, slots, layer) -> (x, pools)`` (one token a
  row over the slot pools, each row's state advanced in its slot ``slots[b]``
  of ``layer``, the layer's place among the state layers; slot 0 is a pad
  row's). The model then gives ``serve_state()``, the shapes and dtypes of
  the parts (:mod:`.paged_cache`: a slot pool a part over the state layers,
  the page pools over the other layers only, each kind indexed by a layer's
  place among its own). A part's last two axes should be whole tiles of
  128 lanes: the decode program writes a part by slot, and a part that is
  one flat row lies in one sublane of each tile, which the chip's compiler
  writes row by row in a loop. The prefill program writes a sequence's
  state into its slot (overwriting it), the decode program takes the slot
  pools donated behind the page pools and the rows' slots last. A sequence
  is granted a slot at admission and gives it back where it gives back its
  blocks; a preemption spills the state with the pages and restores it into
  a fresh slot. Prefix sharing, chunked prefill and speculation are refused
  for such a model (each would need the state at a point no page holds:
  ROADMAP).

The engine writes the rows into the pools, keeps the block tables, and
calls no model by name; scheduler, allocator, spill, spans and counters are
the same for every model. A model without state layers has the programs it
had before any model had them: the same arguments, the same donations.
Every call across the seam runs under one ``jax.named_scope`` of
:data:`device_names.SEAMS` (``embed``, ``attn/project``, ``finish``, ``head``,
``sample``, ...; a family's own scopes nest under them), and a program's
module is named by its kind (``jit_serve_decode``, ...), so that the device
trace names every instruction by the part of the model it runs
(OBSERVABILITY.md, "Device-side names of every serving program").
Decoding is greedy: the argmax of a row's logits, matching
``model.generate``'s default, or for a block model the argmax at the
positions the unmask rule chooses.

**The launch in flight and a state.** A row's token in flight (above) was
computed by a program that also advanced the row's state by the row's input
token. Dropped, that token is computed again, which is harmless for rows a
token (the write is redone) and would count the token twice in a state. So
a row with state layers that is preempted while its token is in flight has
that launch collected first (its tokens taken and committed, the device
order kept): the state spilled is that of its committed tokens only, and the
row resumes from them. A cancelled row never resumes; its slot is given back
and the next prefill into it, queued behind the launch, overwrites it.

**Generation by diffusion over blocks.** A row carries a block of ``B``
positions, some still masked, through several passes of one program over
``[rows, B]`` tokens and a ``[rows, B]`` masked flag. A *denoise pass* embeds
the block (the mask id at masked positions), writes its keys and values to
its page (every later pass of the block overwrites all ``B``), attends
through the block table up to ``pos0 + B`` and, on the device, unmasks by
the rule (:func:`_unmask`: float32 confidences against the threshold, else
the most confident); when none is masked a *commit pass* runs the clean block
once more, writes last, and the block's tokens become output: ``ctx_len``,
``out_tokens``, ``token_t_ns`` and ``t_first_token`` advance only then, by up
to ``B`` (fewer in an answer's first and last block; positions past
``max_new_tokens`` hold the mask id, are never chosen, and a request returns
exactly what it asked for). The prefill runs the prompt's whole blocks under
the model's block-causal mask, stores their keys and values and yields no
token; the prompt's tail opens the first block. PR 33's order holds: the next
pass is launched before the last one's result is taken, and a row of the
last launch takes its state from that result on the device: still masked,
the next denoise pass; whole, its commit pass; committed, a fresh masked
block up to where its answer ends; so the host decides a launch's rows
knowing only which rows' commit in flight ends them. A row preempted,
cancelled or restored mid-block loses only its passes: nothing of an
unfinished block was committed, and it starts that block again from masks.
Prefix sharing, chunked prefill and speculation are refused for such a model
(a block may straddle a shared prefix or a chunk: ROADMAP).
"""

from __future__ import annotations

import math
import time
import types
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence as Seq, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags as _flags
from ..fault.injection import fire as _fault_fire
from ..framework.functional import _swapped_state, get_params
from ..observability import device_names
from ..observability import live as fleet_live
from ..observability import metrics, request_timeline, trace
from ..observability.request_timeline import percentile
from ..observability.step_monitor import RecompileSentinel
from ..ops.flash_attention import takes_paged_kernel
from ..ops.gated_delta import takes_state_kernel
from ..ops.paged_layout import write_blocks, write_tokens
from .buckets import BucketSet, pow2_buckets, pad_axis
from .paged_cache import (NULL_BLOCK, OutOfBlocksError, PagedKVCache,
                          SpillError)
from .prefix_tree import PrefixCache
from .resilience import Rejected, RequestJournal, ShedPolicy
from .scheduler import (BlockInFlight, FCFSScheduler, Request, Sequence,
                        Status)
from .speculative import (DEFAULT_GAMMA, ModelDrafter, NGramDrafter,
                          pick_gamma)

__all__ = ["ServingEngine"]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _keeps_state(layer) -> bool:
    """Whether a layer keeps a fixed-size state a sequence (``serve_keeps =
    "state"``) rather than rows a token."""
    return getattr(layer, "serve_keeps", "rows") == "state"


def _state_spec(model) -> Tuple:
    """What a state layer of ``model`` keeps a sequence, one slot pool a part
    (``model.serve_state()``); () for a model none of whose layers does."""
    if not any(_keeps_state(layer) for layer in model.serve_layers()):
        return ()
    return tuple(model.serve_state())


def _commit_stamps(seq: Sequence) -> Dict[str, Any]:
    """What a terminal request record gains from the commit stamps: nothing
    for a request that has none (``FLAGS_telemetry=off``, or no token)."""
    if not seq.token_t_ns:
        return {}
    return {"t_submit_ns": int(seq.t_submit * 1e9),
            "token_t_ns": list(seq.token_t_ns)}


def _meters() -> types.SimpleNamespace:
    """The metric children the engine touches on every iteration, looked up
    in the registry once (the rare endings keep their by-name look-ups)."""
    kv = metrics.counter(
        "serving.kv_tokens",
        "KV positions a decode dispatch needed (kind=needed: the rows' "
        "contexts) and was handed (kind=gathered: width x table x block)")
    fetches = metrics.counter(
        "serving.kv_page_fetches",
        "page reads of a layer's decode attention: the pages under each "
        "row's length (the whole table on the dense path) times the pools "
        "read apart, which is the DMA descriptors a paged kernel call issues")
    pf = metrics.counter(
        "serving.prefill_tokens",
        "prompt tokens prefilled (kind=real) and the bucket lengths they "
        "were padded to (kind=bucket)")
    fed = metrics.counter(
        "serving.decode_rows",
        "rows of the launched decode iterations by where their token came "
        "from (fed=device: the launch in flight, on the device; fed=host), "
        "and tokens in flight that were thrown away (fed=dropped)")
    passes = metrics.counter(
        "serving.diffusion_passes",
        "passes rows of a block-diffusion model took, a row a pass "
        "(kind=denoise: positions may be unmasked; kind=commit: the whole "
        "block's keys and values are stored and its tokens become output)")
    unmasked = metrics.counter(
        "serving.diffusion_unmasked",
        "positions unmasked, by the branch of the rule that chose them "
        "(rule=threshold: every masked position over the confidence "
        "threshold; rule=schedule: the most confident)")
    slots = metrics.gauge(
        "serving.state_slots",
        "slots of the state pools (a model whose layers keep a state a "
        "sequence) held by sequences (kind=used) and free (kind=free)")
    state_rows = metrics.counter(
        "serving.state_rows",
        "rows of the decode programs that hold a sequence (kind=needed) and "
        "rows whose state slot a program read (kind=read: the kernel skips "
        "a pad row's null slot, the dense path gathers every row's)")
    return types.SimpleNamespace(
        slots_used=slots.labels(kind="used"),
        slots_free=slots.labels(kind="free"),
        state_needed=state_rows.labels(kind="needed"),
        state_read=state_rows.labels(kind="read"),
        passes_denoise=passes.labels(kind="denoise"),
        passes_commit=passes.labels(kind="commit"),
        unmasked_threshold=unmasked.labels(rule="threshold"),
        unmasked_schedule=unmasked.labels(rule="schedule"),
        blocks=metrics.counter(
            "serving.diffusion_blocks",
            "blocks committed by a block-diffusion model").labels(),
        pass_rows=metrics.counter(
            "serving.diffusion_pass_rows",
            "rows of the launched diffusion passes, summed over the "
            "launches").labels(),
        kv_needed=kv.labels(kind="needed"),
        kv_gathered=kv.labels(kind="gathered"),
        kv_fetches=fetches.labels(),
        fed_device=fed.labels(fed="device"),
        fed_host=fed.labels(fed="host"),
        fed_dropped=fed.labels(fed="dropped"),
        prefill_real=pf.labels(kind="real"),
        prefill_bucket=pf.labels(kind="bucket"),
        queue_depth=metrics.gauge(
            "serving.queue_depth",
            "requests waiting for admission").labels(),
        running=metrics.gauge(
            "serving.running",
            "sequences resident in the decode batch").labels(),
        free_block_frac=metrics.gauge(
            "serving.free_block_frac",
            "free fraction of the usable KV pool (the shed policy's "
            "admission signal)").labels(),
        # the family, not a child: the series appears with its first
        # reading, which only a policy or an armed exporter asks for
        decode_p99_ms=metrics.gauge(
            "serving.decode_p99_ms",
            "sliding-window decode-iteration p99 (ms, the shed policy's "
            "latency signal)"),
        decode_step_ms=metrics.histogram(
            "serving.decode_step_ms",
            "decode iteration wall time (ms)").labels())


def _account(t0_ns: int, end_ns: int, phase: str, seqs) -> None:
    """Hand a stretch between two span stamps to the phase account of each
    sequence it served. Under ``FLAGS_telemetry=off`` the spans measured
    nothing (``end_ns`` 0) and nothing is fed."""
    if not end_ns:
        return
    dur_s = (end_ns - t0_ns) * 1e-9
    for seq in seqs:
        seq.add_phase(phase, dur_s)


def _still_rows(seq: Sequence, epoch: int) -> bool:
    """Whether a row launched at preemption count ``epoch`` still is its
    sequence's: not cancelled, finished or preempted since."""
    return seq.status is Status.RUNNING and seq.preemptions == epoch


def _unmask(logits, masked, k_min: int, threshold: float):
    """The unmask rule of generation by diffusion over blocks, on the device:
    ``logits [W, B, V]`` float32 and ``masked [W, B]`` -> ``(x0, chosen,
    by_threshold)``. At each masked position ``x0 = argmax(logits)`` and
    ``conf = softmax_float32(logits)[x0]``; the masked positions with ``conf
    > threshold`` are chosen if they are at least ``k_min``
    (``by_threshold``, a row), else the ``k_min`` most confident (ties to
    the earlier position)."""
    at = jnp.arange(masked.shape[1])[None, :]
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    top = jnp.max(logits, axis=-1)
    conf = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))
    high = jnp.logical_and(masked, conf > threshold)
    by_threshold = (jnp.sum(high, axis=-1) >= k_min)[:, None]
    c = jnp.where(masked, conf, -1.0)
    ahead = jnp.logical_or(
        c[:, None, :] > c[:, :, None],
        jnp.logical_and(c[:, None, :] == c[:, :, None],
                        at[:, None, :] < at[:, :, None]))
    best = jnp.logical_and(masked, jnp.sum(ahead, axis=-1) < k_min)
    return x0, jnp.where(by_threshold, high, best), by_threshold


class _Launch(NamedTuple):
    """A decode iteration on the device whose tokens the host has not taken:
    its rows, each row's preemption count at the launch, the bucket, the
    contexts as launched, the program's result (still on the device: the
    next launch of this bucket reads its tokens there) and when the
    iteration began."""
    batch: List[Sequence]
    epochs: List[int]
    width: int
    lens: np.ndarray
    out: Any
    t0_ns: int
    row_of: Dict[int, int]      # id(sequence) -> its row


class _ParamJit:
    """``jax.jit`` of a step that reads ``model``'s weights, with the
    weights an explicit leading argument of the compiled program.

    The raw step closes over the layer tree; jitted as it stands, every
    weight would be baked into the program as a constant — one copy of
    the model inside each bucket program, which a 1.3B model cannot
    afford on a 16 GB chip (and which no compiler should be handed as
    literals). Callers keep the raw signature: ``fn(*args)`` and
    ``fn.lower(*args)`` put the weights (snapshotted here, as the
    closure's were at first trace) in front.

    ``kind`` names the program: its module is ``jit_serve_<kind>`` on the
    device trace's ``XLA Modules`` line."""

    def __init__(self, raw, model, kind: str):
        self.params = get_params(model)
        self.kind = kind

        def step(params, *args):
            with _swapped_state(model, params, None):
                return raw(*args)

        # the module's name; not functools.wraps, whose __wrapped__ jax
        # would follow for the signature (shifting donate_argnums)
        step.__name__ = step.__qualname__ = f"serve_{kind}"
        # every step is (tokens, *pools, ...): the model's page pools, then
        # its slot pools, are donated (raw args 1.. — behind the weights: 2..)
        n_pools = len(model.serve_cache_rows()) + len(_state_spec(model))
        self.jitted = jax.jit(step,
                              donate_argnums=tuple(range(2, 2 + n_pools)))

    def __call__(self, *args):
        return self.jitted(self.params, *args)

    def lower(self, *args):
        return self.jitted.lower(self.params, *args)


def _dispatch(fn, args, new: bool):
    """``fn(*args)``; where the recompile sentinel has just seen the
    signature for the first time (``new``), the program also goes to
    :func:`device_names.note`, at shapes taken before the dispatch donates
    the pools. A callable that is no :class:`_ParamJit` (a wrapper put in
    its place) has no program to note."""
    if not (new and isinstance(fn, _ParamJit)):
        return fn(*args)
    shapes = device_names.abstract((fn.params,) + args)
    out = fn(*args)
    device_names.note(fn.kind, fn.jitted, shapes)
    return out


class ServingEngine:
    """Paged-KV continuous-batching server over one causal-LM model."""

    def __init__(self, model, *, block_size: int = 8, num_blocks: int = 64,
                 max_batch: int = 8, max_seq_len: Optional[int] = None,
                 prefill_buckets: Optional[Seq[int]] = None,
                 decode_buckets: Optional[Seq[int]] = None,
                 detokenizer: Optional[Callable[[np.ndarray], Any]] = None,
                 max_waiting: Optional[int] = None,
                 max_spilled_bytes: Optional[int] = None,
                 shed_policy: Optional[ShedPolicy] = None,
                 journal: Optional[RequestJournal] = None,
                 validate_capacity: bool = True,
                 prefix_cache: Optional[bool] = None,
                 chunked_prefill: Optional[int] = None,
                 speculative: Optional[int] = None,
                 drafter: Optional[Any] = None):
        """Resilience knobs (all default-off, preserving PR-8 behavior):
        ``max_waiting``/``max_spilled_bytes`` bound admission (over-budget
        submissions return a typed :class:`Rejected`), ``shed_policy``
        arms overload load shedding, ``journal`` records admitted-request
        state for exactly-once replay across process deaths, and
        ``validate_capacity=False`` lets a pool smaller than one
        max-length sequence serve anyway — a request that outgrows it
        FAILS (F003) instead of the constructor refusing, which is how
        the drill proves pool exhaustion never crashes the loop.

        Throughput knobs (``None`` reads the matching ``FLAGS_serve_*``
        flag; every one default-off and byte-identical off):
        ``prefix_cache`` arms the radix prefix-sharing tree;
        ``chunked_prefill`` is the per-iteration prefill token budget
        (0 = one-shot prefill); ``speculative`` is the draft depth gamma
        (0 = off, -1 = the autotune cache's accepted-length-derived
        choice) with ``drafter`` an :class:`NGramDrafter` (default) or
        :class:`ModelDrafter`."""
        model.eval()
        cfg = model.cfg
        self.model = model
        self.block_size = int(block_size)
        limit = int(cfg.max_position_embeddings)
        self.max_seq_len = min(int(max_seq_len or limit), limit)
        self.max_blocks_per_seq = _ceil_div(self.max_seq_len, self.block_size)
        if validate_capacity and num_blocks - 1 < self.max_blocks_per_seq:
            raise ValueError(
                f"pool of {num_blocks} blocks cannot hold one max-length "
                f"sequence ({self.max_blocks_per_seq} blocks of "
                f"{self.block_size})")
        self.detokenizer = detokenizer

        # -- bucket sets (the compile budget) --------------------------------
        max_prefill = self.max_blocks_per_seq * self.block_size
        if prefill_buckets is None:
            prefill_buckets = [min(b * self.block_size, max_prefill)
                               for b in pow2_buckets(
                                   1, self.max_blocks_per_seq)]
        for s in prefill_buckets:
            if s % self.block_size or s > max_prefill:
                raise ValueError(
                    f"prefill bucket {s} must be a multiple of "
                    f"block_size={self.block_size} and <= {max_prefill}")
        self.prefill_buckets = BucketSet(prefill_buckets)
        self.decode_buckets = BucketSet(
            decode_buckets if decode_buckets is not None
            else pow2_buckets(1, max_batch))

        # -- throughput tiers (ISSUE 13) -------------------------------------
        self.prefix_on = bool(_flags.flag("serve_prefix_cache")) \
            if prefix_cache is None else bool(prefix_cache)
        chunk = int(_flags.flag("serve_chunked_prefill")) \
            if chunked_prefill is None else int(chunked_prefill)
        # the chunk budget is block-granular (chunk KV scatters whole
        # blocks); a sub-block budget rounds up to one block
        self.chunk_tokens = 0 if chunk <= 0 else max(
            self.block_size, (chunk // self.block_size) * self.block_size)
        spec = int(_flags.flag("serve_speculative")) \
            if speculative is None else int(speculative)
        self.drafter = None
        self.spec_gamma = 0
        self._draft_cache: Optional[PagedKVCache] = None
        if spec != 0:
            self.drafter = drafter if drafter is not None else NGramDrafter()
            t_desc = (f"gpt_l{cfg.num_layers}_h{cfg.hidden_size}"
                      f"_v{cfg.vocab_size}")
            d_desc = self.drafter.kind
            if isinstance(self.drafter, ModelDrafter):
                dcfg = self.drafter.model.cfg
                d_desc = (f"gpt_l{dcfg.num_layers}_h{dcfg.hidden_size}"
                          f"_v{dcfg.vocab_size}")
            self.spec_gamma = spec if spec > 0 else pick_gamma(
                t_desc, d_desc, default=DEFAULT_GAMMA)
            self._spec_desc = (t_desc, d_desc)
        self._accept_lens: List[int] = []
        self.spec_stats = {"iterations": 0, "proposed": 0, "accepted": 0}

        #: how the model generates: None (a token a row a step) or its
        #: block spec (block length, steps, threshold, mask id), by which
        #: the engine takes its decode program
        self._gen = getattr(model, "serve_generation", None)
        if self._gen is not None:
            if self.prefix_on or self.chunk_tokens or spec:
                raise ValueError(
                    "a model that generates by diffusion over blocks is "
                    "served without prefix sharing, chunked prefill and "
                    "speculation (a block may straddle a shared prefix or "
                    "a chunk)")
            if self.block_size % self._gen.block_length:
                raise ValueError(
                    f"block_size {self.block_size} is not a multiple of the "
                    f"model's block length {self._gen.block_length}: a "
                    "block in flight has to lie inside one page")

        # -- device state ----------------------------------------------------
        self.cache = self._pool_for(model, num_blocks, max_batch)
        if self.cache.states and (self.prefix_on or self.chunk_tokens or spec
                                  or self._gen is not None):
            raise ValueError(
                "a model whose layers keep a state a sequence is served "
                "without prefix sharing, chunked prefill and speculation (a "
                "shared prefix, a chunk or a rejected draft would need the "
                "state at its end, which no page holds)")
        self._n_pools = len(self.cache.arrays)
        #: counts the decode and prefill programs return behind the token
        #: (0: none), handed to ``model.serve_record_counts``
        self._n_counts = int(model.serve_counts)
        if isinstance(self.drafter, ModelDrafter):
            dcfg = self.drafter.model.cfg
            if int(dcfg.vocab_size) != int(cfg.vocab_size):
                raise ValueError(
                    f"drafter vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}")
            self._draft_cache = self._pool_for(self.drafter.model,
                                               num_blocks)
        #: whether the decode program reads pages through the kernel (what
        #: ``serving.kv_tokens{kind=gathered}`` then counts)
        self._decode_paged = takes_paged_kernel(
            self.cache.dtype, self.cache.pools[0],
            model.serve_latent_value_dim, self.block_size)
        #: whether the decode program's state layers take the kernel (what
        #: ``serving.state_rows{kind=read}`` then counts)
        self._state_paged = bool(self.cache.states) and takes_state_kernel(
            self.cache.states[0])
        self.prefix = PrefixCache(self.cache, mirror=self._draft_cache) \
            if self.prefix_on else None
        self.sched = FCFSScheduler(max_batch, max_waiting=max_waiting)
        self._seqs: Dict[str, Sequence] = {}
        self._m = _meters()
        #: scheduler iterations run — the "step index" the live fleet
        #: exporter publishes for a serving worker
        self.n_iterations = 0
        self.peak_blocks_used = 0
        #: peak blocks referenced by live sequences (tree-idle cache
        #: holds excluded — they evict on demand); the fair
        #: pool-pressure comparison across prefix-cache arms
        self.peak_live_blocks = 0

        # -- resilience state ------------------------------------------------
        self.max_spilled_bytes = max_spilled_bytes
        self.shed_policy = shed_policy
        self.journal = journal
        self.rejections: List[Rejected] = []
        self.diagnostics: List[Any] = []     # F003 records, newest last
        self.mode = "healthy"                # healthy | shedding | degraded
        self._spilled_bytes = 0
        self._degraded_width: Optional[int] = None
        self._decode_ms: deque = deque(
            maxlen=shed_policy.window if shed_policy else 64)
        self._p99: Optional[float] = None   # of _decode_ms, as of _p99_at
        self._p99_at = -1
        #: the decode iteration in flight: launched in one step, its tokens
        #: taken in the next, after that step's own launch
        #: (_decode_iteration, _decode_collect)
        self._ahead: Optional[_Launch] = None
        #: bucket -> what a launch with no predecessor reads as ``prev``
        self._no_prev: Dict[int, Any] = {}
        # a shed policy ACTS on the decode iteration's duration, so its two
        # spans measure in every telemetry mode
        self._acted_span = trace.timed_span if shed_policy is not None \
            else trace.span
        if journal is not None:
            journal.launch()

        # -- compiled steps + their sentinels --------------------------------
        self._prefill_raw = self._make_prefill()
        self._decode_raw = self._make_decode() if self._gen is None \
            else self._make_block_decode()
        self._prefill_fn = _ParamJit(self._prefill_raw, model, "prefill")
        self._decode_fn = _ParamJit(
            self._decode_raw, model,
            "decode" if self._gen is None else "block_decode")
        self._sent_prefill = RecompileSentinel(
            threshold=len(self.prefill_buckets))
        self._sent_decode = RecompileSentinel(
            threshold=len(self.decode_buckets))
        self._chunk_raw = None
        self._chunk_fn = None
        self._sent_chunk = None
        if self.prefix_on or self.chunk_tokens:
            self._chunk_raw = self._make_extend(self.model,
                                                last_only=True)
            self._chunk_fn = _ParamJit(self._chunk_raw, model, "extend")
            self._sent_chunk = RecompileSentinel(
                threshold=len(self.prefill_buckets))
        self._verify_raw = None
        self._verify_fn = None
        self._sent_verify = None
        if self.spec_gamma:
            self._verify_raw = self._make_extend(self.model,
                                                 last_only=False)
            self._verify_fn = _ParamJit(self._verify_raw, model, "verify")
            self._sent_verify = RecompileSentinel(
                threshold=len(self.decode_buckets))
        self._draft_decode_fn = None
        self._draft_extend_fn = None
        self._sent_draft = None
        if self._draft_cache is not None:
            self._draft_decode_fn = _ParamJit(
                self._make_decode(self.drafter.model),
                self.drafter.model, "draft_decode")
            self._draft_extend_fn = _ParamJit(
                self._make_extend(self.drafter.model, last_only=True),
                self.drafter.model, "draft_extend")
            self._sent_draft = RecompileSentinel(
                threshold=len(self.decode_buckets) +
                len(self.prefill_buckets))
        self.plan = self._build_plan()
        self._linted = False
        self._gauges()      # a fresh engine's pool reads free, not 0

    # ------------------------------------------------------------------
    # The bucketed executables
    # ------------------------------------------------------------------

    def _pool_for(self, model, num_blocks: int,
                  max_batch: int = 0) -> PagedKVCache:
        """The pools ``model`` asks for: a page pool a row of its cache spec
        over the layers that cache rows, and where layers keep a state a
        sequence a slot pool a part of it over those, a slot for each of
        ``max_batch`` rows and the null slot."""
        layers = model.serve_layers()
        n_state = sum(_keeps_state(layer) for layer in layers)
        return PagedKVCache(len(layers) - n_state, num_blocks,
                            self.block_size, dtype=model.serve_dtype(),
                            rows=model.serve_cache_rows(),
                            state=_state_spec(model), n_state_layers=n_state,
                            n_slots=max_batch + 1)

    @property
    def _donated(self):
        """Positions of the pools among a step's arguments."""
        return tuple(range(1, 1 + self._n_pools))

    def _undonated(self, args):
        """A step's arguments but the pools (what its sentinel watches)."""
        return (args[0],) + tuple(args[1 + self._n_pools:])

    def _take_counts(self, out: np.ndarray, n_tok: int, n_real: int):
        """Split what a prefill or decode program returned beside the pools
        into its token(s) and, for a model that counts
        (``model.serve_counts``), the counts behind them, which go to
        ``model.serve_record_counts`` with the number of real tokens the
        program ran."""
        if not self._n_counts:
            return out
        self.model.serve_record_counts(out[n_tok:], n_real)
        return out[:n_tok]

    @staticmethod
    def _with_counts(tok, counts):
        """The program's one result beside the pools: the token(s), and
        behind them the sum of the counts the model's layers gave (none:
        the token alone), so that they ride the token's transfer."""
        if not counts:
            return tok
        return jnp.concatenate([tok.reshape(-1),
                                sum(counts).astype(jnp.int32)])

    def _make_prefill(self, model=None):
        m = model if model is not None else self.model
        bs = self.block_size
        n_pools = len(m.serve_cache_rows())
        n_states = len(_state_spec(m))
        counted = bool(m.serve_counts)

        def prefill(ids, *rest):
            """ids [1, S] bucket-padded; then the pools (and slot pools);
            block_ids [S//bs] (null-padded); n_tokens: true prompt length;
            for a model with state layers the sequence's slot. Writes the
            prompt's page rows (and its state: a state layer's after the
            prompt, overwriting the slot) and returns the first generated
            token."""
            pools = list(rest[:n_pools])
            states = list(rest[n_pools:n_pools + n_states])
            block_ids, n_tokens, *slot = rest[n_pools + n_states:]
            s = ids.shape[1]
            counts = []
            with jax.named_scope("embed"):
                pos = jnp.arange(s)[None, :]
                real = pos < n_tokens if counted else None
                x = m.serve_embed(ids, pos)
            ri = ki = 0         # a layer's place among its kind
            for layer in m.serve_layers():
                if _keeps_state(layer):
                    with jax.named_scope("state"):
                        x, kept = layer.serve_prefill_state(x, n_tokens)
                    with jax.named_scope("state/write"):
                        for j, part in enumerate(kept):
                            states[j] = states[j].at[ki, slot[0]].set(
                                part[0].astype(states[j].dtype))
                    ki += 1
                    continue
                with jax.named_scope("attn/project"):
                    q, rows = layer.serve_project(x, pos)
                with jax.named_scope("attn/attend"):
                    o = layer.serve_attend_prefill(q, rows)
                with jax.named_scope("attn/cache_write"):
                    for pi, row in enumerate(rows):
                        pools[pi] = write_blocks(pools[pi], ri, block_ids,
                                                 row[0], bs)
                with jax.named_scope("finish"):
                    x, c = layer.serve_finish(x, o, real)
                if c is not None:
                    counts.append(c)
                ri += 1
            with jax.named_scope("head"):
                hidden = m.serve_final_norm(x)
                last = jax.lax.dynamic_index_in_dim(hidden, n_tokens - 1,
                                                    axis=1, keepdims=True)
                logits = m.logits(last)[0, 0]
            with jax.named_scope("sample"):
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            with jax.named_scope("counts"):
                return (self._with_counts(tok, counts), *pools, *states)

        return prefill

    def _make_decode(self, model=None):
        m = model if model is not None else self.model
        bs = self.block_size
        n_pools = len(m.serve_cache_rows())
        n_states = len(_state_spec(m))
        counted = bool(m.serve_counts)

        def decode(tokens, *rest):
            """tokens [B] (each sequence's latest token, not yet cached);
            then the pools; tables [B, M] null-padded block tables;
            ctx_lens [B] tokens already cached (0 = inactive pad row, which
            harmlessly writes the null block and produces a discarded
            output). One iteration: write each token's page row at position
            ctx_len, attend over ctx_len+1 rows, return the next token.

            The engine's own decode also takes ``prev``, what the previous
            launch of this bucket returned (tokens first), and ``src`` [B]:
            row i reads its token from ``prev[src[i]]`` where ``src[i] >= 0``
            (the row that sequence held in that launch, whose token the host
            has not seen yet) and from ``tokens[i]`` where it is -1.

            A model with state layers: the slot pools behind the pools, and
            ``slots`` [B] last (0 = a pad row): a state layer advances each
            row's state in its slot, in place."""
            pools = list(rest[:n_pools])
            states = tuple(rest[n_pools:n_pools + n_states])
            tables, ctx_lens, *fed = rest[n_pools + n_states:]
            if n_states:
                *fed, slots = fed
            if fed:
                prev, src = fed
                with jax.named_scope("feed"):
                    tokens = jnp.where(src >= 0, prev[jnp.maximum(src, 0)],
                                       tokens)
            pos = ctx_lens
            counts = []
            with jax.named_scope("embed"):
                real = (ctx_lens > 0)[:, None] if counted else None
                pos_col = pos[:, None]
                x = m.serve_embed(tokens[:, None], pos_col)
            with jax.named_scope("attn/cache_write"):
                bi = jnp.take_along_axis(tables, (pos // bs)[:, None],
                                         axis=1)[:, 0]
                si = pos % bs
            ri = ki = 0         # a layer's place among its kind
            for layer in m.serve_layers():
                if _keeps_state(layer):
                    with jax.named_scope("state"):
                        x, states = layer.serve_decode_state(x, states, slots,
                                                             ki)
                    ki += 1
                    continue
                with jax.named_scope("attn/project"):
                    q, rows = layer.serve_project(x, pos_col)
                with jax.named_scope("attn/cache_write"):
                    for pi, row in enumerate(rows):
                        pools[pi] = write_tokens(pools[pi], ri, bi, si,
                                                 row[:, 0], bs)
                with jax.named_scope("attn/attend"):
                    o = layer.serve_attend_paged(q, pools, tables, pos + 1,
                                                 bs, ri)
                with jax.named_scope("finish"):
                    x, c = layer.serve_finish(x, o, real)
                if c is not None:
                    counts.append(c)
                ri += 1
            with jax.named_scope("head"):
                hidden = m.serve_final_norm(x)
                logits = m.logits(hidden)[:, 0]
            with jax.named_scope("sample"):
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            with jax.named_scope("counts"):
                return (self._with_counts(tok, counts), *pools, *states)

        return decode

    def _state_len(self, width: int) -> int:
        """Ints of a decode result at bucket ``width`` before the model's
        counts: a token a row, or for a block-decode program the rows' state:
        each row's block (its tokens, its masked flags), its stage and its
        block's first position (:meth:`_block_state`), then the positions the
        pass unmasked by either branch of the rule."""
        if self._gen is None:
            return width
        return width * (2 * self._gen.block_length + 2) + 2

    def _block_state(self, vec, width: int):
        """``(tokens [W, B], masked [W, B], stage [W], pos0 [W])`` of a
        block-decode result, on the device or on the host."""
        n = width * self._gen.block_length
        return (vec[:n].reshape(width, -1), vec[n:2 * n].reshape(width, -1),
                vec[2 * n:2 * n + width], vec[2 * n + width:2 * n + 2 * width])

    def _make_block_decode(self):
        """The decode program of a model that generates by diffusion over
        blocks: one pass over ``[rows, B]`` positions, ``B`` the model's
        block length."""
        m = self.model
        gen = self._gen
        bs = self.block_size
        B, mask_id, thr = gen.block_length, gen.mask_id, gen.threshold
        k_min = max(1, B // gen.steps)
        n_pools = len(m.serve_cache_rows())
        counted = bool(m.serve_counts)

        def block_decode(tokens, *rest):
            """tokens [W, B] (each row's block in flight, the mask id where
            masked); then the pools; tables [W, M]; pos0 [W] the block's
            first position; masked [W, B] (1: still to be unmasked); commit
            [W] (1: the block is whole and this is its commit pass); end_pos
            [W] where the request's answer ends (prompt + max_new_tokens; 0 =
            inactive pad row, which writes the null block); ``prev``, what
            the previous launch of this bucket returned, and ``src`` [W]: row
            i takes its state from row ``src[i]`` of ``prev`` where ``src[i]
            >= 0`` and from the host's arguments where it is -1.

            One pass: embed the block, write its keys and values to its page
            (a denoise pass writes them too: every later pass of the block
            overwrites all B, and the commit pass, which runs the clean
            block, writes last; nothing reads them but the block's own
            passes until then), attend through the block table up to ``pos0
            + B`` (within the block nothing is masked), finish; then, in a
            denoise pass, the unmask rule (:func:`_unmask`) on the device: a
            chosen position takes its ``x0``. A row from ``prev``: still
            masked, the next denoise pass; whole, its commit pass; committed,
            the first pass of the next block, masks up to ``end_pos``.

            Returns the rows' new state (``_state_len``) and the
            expert counts behind it, then the pools."""
            pools = list(rest[:n_pools])
            tables, pos0, masked, commit, end_pos, prev, src = rest[n_pools:]
            w = tokens.shape[0]
            # -- a resident row's state, from the launch in flight ----------
            with jax.named_scope("feed"):
                at = jnp.arange(B)[None, :]
                row = jnp.maximum(src, 0)
                p_tok, p_masked, p_stage, p_pos0 = (
                    part[row] for part in self._block_state(prev, w))
                nxt = (p_stage == 2)[:, None]       # its block was committed
                d_pos0 = p_pos0 + jnp.where(p_stage == 2, B, 0)
                d_tok = jnp.where(nxt, mask_id, p_tok)
                d_masked = jnp.where(
                    nxt, d_pos0[:, None] + at < end_pos[:, None],
                    p_masked > 0)
                fed = src >= 0
                tokens = jnp.where(fed[:, None], d_tok, tokens)
                masked = jnp.where(fed[:, None], d_masked, masked > 0)
                pos0 = jnp.where(fed, d_pos0, pos0)
                commit = jnp.where(fed, p_stage == 1, commit > 0)
                live = end_pos > 0
                masked = jnp.logical_and(masked, live[:, None])
            # -- the pass ---------------------------------------------------
            counts = []
            with jax.named_scope("embed"):
                pos = pos0[:, None] + at
                real = jnp.broadcast_to(live[:, None], (w, B)) if counted \
                    else None
                x = m.serve_embed(tokens, pos)
            with jax.named_scope("attn/cache_write"):
                bi = jnp.take_along_axis(
                    tables, jnp.clip(pos // bs, 0, tables.shape[1] - 1),
                    axis=1)
                bi = jnp.where(live[:, None], bi, NULL_BLOCK)
                si = pos % bs
            with jax.named_scope("attn/attend"):
                lengths = jnp.where(live, pos0 + B, 0)
            for li, layer in enumerate(m.serve_layers()):
                with jax.named_scope("attn/project"):
                    q, rows = layer.serve_project(x, pos)
                with jax.named_scope("attn/cache_write"):
                    for pi, r in enumerate(rows):
                        pools[pi] = write_tokens(pools[pi], li, bi, si, r, bs)
                with jax.named_scope("attn/attend"):
                    o = layer.serve_attend_block(q, pools, tables, lengths,
                                                 bs, li)
                with jax.named_scope("finish"):
                    x, c = layer.serve_finish(x, o, real)
                if c is not None:
                    counts.append(c)
            with jax.named_scope("head"):
                logits = m.logits(m.serve_final_norm(x)).astype(jnp.float32)
            with jax.named_scope("sample"):
                x0, chosen, by_threshold = _unmask(logits, masked, k_min, thr)
                chosen = jnp.logical_and(chosen,
                                         jnp.logical_not(commit)[:, None])
                tokens = jnp.where(chosen, x0, tokens)
                masked = jnp.logical_and(masked, jnp.logical_not(chosen))
                stage = jnp.where(commit, 2,
                                  jnp.where(jnp.any(masked, axis=-1), 0, 1))
                n_thr = jnp.sum(jnp.logical_and(chosen, by_threshold))
                state = jnp.concatenate([
                    tokens.reshape(-1), masked.astype(jnp.int32).reshape(-1),
                    stage.astype(jnp.int32), pos0,
                    jnp.stack([n_thr, jnp.sum(chosen) - n_thr]).astype(
                        jnp.int32)])
            with jax.named_scope("counts"):
                return (self._with_counts(state, counts), *pools)

        return block_decode

    def _make_extend(self, model, last_only: bool = False):
        """The multi-token paged step: chunk prefill, prefix-hit suffix
        prefill, and speculative verify are all this one program at
        different (B, L) buckets. ``last_only=True`` (the chunk/prefill
        form) projects logits for only each row's final real token —
        the verify form needs the argmax at EVERY position for the
        accept-prefix rule, the chunk form only the next token. It returns
        no counts (those ride the prefill and decode programs only)."""
        m = model
        bs = self.block_size
        n_pools = len(m.serve_cache_rows())

        def extend(tokens, *rest):
            """tokens [B, L]; then the pools; tables [B, M] null-padded;
            ctx_lens [B] tokens already cached per row; n_real [B] real
            tokens in this dispatch (padded slots scatter into the null
            block). Writes tokens[b, i]'s page row at position ctx_lens[b]
            + i and returns the greedy argmax — [B, L] (every query) or [B]
            (each row's last real query) under ``last_only``."""
            pools, (tables, ctx_lens, n_real) = list(rest[:n_pools]), \
                rest[n_pools:]
            b, L = tokens.shape
            with jax.named_scope("embed"):
                pos = ctx_lens[:, None] + jnp.arange(L)[None, :]   # [B, L]
                real = jnp.arange(L)[None, :] < n_real[:, None]    # [B, L]
                pos_q = jnp.where(real, pos, 0)
                x = m.serve_embed(tokens, pos_q)
            with jax.named_scope("attn/cache_write"):
                bi = jnp.take_along_axis(
                    tables, jnp.clip(pos // bs, 0, tables.shape[1] - 1),
                    axis=1)
                bi = jnp.where(real, bi, NULL_BLOCK)
                si = pos % bs
            for li, layer in enumerate(m.serve_layers()):
                with jax.named_scope("attn/project"):
                    q, rows = layer.serve_project(x, pos_q)
                with jax.named_scope("attn/cache_write"):
                    for pi, row in enumerate(rows):
                        pools[pi] = write_tokens(pools[pi], li, bi, si, row,
                                                 bs)
                with jax.named_scope("attn/attend"):
                    o = layer.serve_attend_extend(q, pools, tables, pos_q,
                                                  bs, li)
                with jax.named_scope("finish"):
                    x, _ = layer.serve_finish(x, o, None)
            with jax.named_scope("head"):
                hidden = m.serve_final_norm(x)
                if last_only:
                    idx = jnp.maximum(n_real - 1, 0)[:, None, None]
                    hidden = jnp.take_along_axis(
                        hidden, jnp.broadcast_to(
                            idx, (b, 1, hidden.shape[-1])), axis=1)
                logits = m.logits(hidden)
            with jax.named_scope("sample"):
                if last_only:
                    logits = logits[:, 0]
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (toks, *pools)

        return extend

    # ------------------------------------------------------------------
    # Declared plan + static analysis
    # ------------------------------------------------------------------

    def _build_plan(self):
        from ..analysis.plan_check import PlanNode, StepPlan
        nodes = [
            PlanNode("serve.prefill", reads=("weights", "prompt_ids"),
                     donates=("kv_pages",),
                     writes=("kv_pages", "next_tokens")),
        ]
        if self.prefix_on or self.chunk_tokens:
            # the extend step READS the copy-on-write shared pages (the
            # prefix tree's immutable blocks) and writes only private
            # pages — rule D005 rejects any plan that writes/donates a
            # buffer listed in flags["cow_shared_buffers"]
            nodes.append(PlanNode(
                "serve.chunk_prefill",
                reads=("weights", "chunk_ids", "block_tables",
                       "kv_pages_shared"),
                donates=("kv_pages",),
                writes=("kv_pages", "next_tokens")))
        if self.spec_gamma:
            nodes.append(PlanNode(
                "serve.draft",
                reads=("draft_weights", "block_tables", "ctx_lens",
                       "draft_kv_pages_shared"),
                donates=("draft_kv_pages",),
                writes=("draft_kv_pages", "draft_tokens")))
            nodes.append(PlanNode(
                "serve.verify",
                reads=("weights", "draft_tokens", "block_tables",
                       "ctx_lens", "kv_pages_shared"),
                donates=("kv_pages",),
                writes=("kv_pages", "next_tokens")))
        nodes += [
            # resident rows read their token from the last launch's result
            PlanNode("serve.decode",
                     reads=("weights", "block_tables", "ctx_lens",
                            "next_tokens"),
                     donates=("kv_pages",),
                     writes=("kv_pages", "next_tokens")),
            PlanNode("serve.spill", reads=("kv_pages",),
                     writes=("host_kv",)),
            PlanNode("serve.restore", reads=("host_kv",),
                     donates=("kv_pages",), writes=("kv_pages",)),
        ]
        flags = {"block_size": self.block_size,
                 "num_blocks": self.cache.num_blocks,
                 "max_batch": self.sched.max_batch,
                 "prefill_buckets": str(self.prefill_buckets.sizes),
                 "decode_buckets": str(self.decode_buckets.sizes),
                 # resilience knobs change scheduling, not dispatch —
                 # declared so the verified plan names the whole config
                 "max_waiting": str(self.sched.max_waiting),
                 "max_spilled_bytes": str(self.max_spilled_bytes),
                 "shed_policy": repr(self.shed_policy),
                 "serve_prefix_cache": self.prefix_on,
                 "serve_chunked_prefill": self.chunk_tokens,
                 "serve_speculative": self.spec_gamma}
        if self.prefix_on:
            flags["cow_shared_buffers"] = \
                "kv_pages_shared,draft_kv_pages_shared"
        return StepPlan(flags=flags, mesh_axes={}, params={}, nodes=nodes)

    def trace_steps(self):
        """Closed jaxprs of the engine's executables at their smallest
        buckets — the ``lint_graph --model serving`` / plan_check
        inputs. Returns ``{name: (closed_jaxpr, donate_argnums)}``;
        ``extend`` (chunk/suffix prefill), ``verify`` (decode-gamma) and
        the drafter pair appear only when the matching tier is armed."""
        s0 = self.prefill_buckets.sizes[0]
        b0 = self.decode_buckets.sizes[0]
        c = self.cache
        m_blocks = self.max_blocks_per_seq
        pages = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in c.arrays]
        donated = self._donated
        i32 = jnp.int32
        slot = (jax.ShapeDtypeStruct((), i32),) if c.states else ()
        pre = jax.make_jaxpr(self._prefill_raw)(
            jax.ShapeDtypeStruct((1, s0), i32), *pages,
            jax.ShapeDtypeStruct((s0 // self.block_size,), i32),
            jax.ShapeDtypeStruct((), i32), *slot)
        dec = jax.make_jaxpr(self._decode_raw)(
            self._decode_head_spec(b0), *pages, *self._decode_tail_spec(b0))
        out = {"prefill": (pre, donated), "decode": (dec, donated)}
        if self._chunk_raw is not None:
            out["extend"] = (jax.make_jaxpr(self._chunk_raw)(
                jax.ShapeDtypeStruct((1, s0), i32), *pages,
                jax.ShapeDtypeStruct((1, m_blocks), i32),
                jax.ShapeDtypeStruct((1,), i32),
                jax.ShapeDtypeStruct((1,), i32)), donated)
        if self._verify_raw is not None:
            L = self.spec_gamma + 1
            out["verify"] = (jax.make_jaxpr(self._verify_raw)(
                jax.ShapeDtypeStruct((b0, L), i32), *pages,
                jax.ShapeDtypeStruct((b0, m_blocks), i32),
                jax.ShapeDtypeStruct((b0,), i32),
                jax.ShapeDtypeStruct((b0,), i32)), donated)
        if self._draft_cache is not None:
            dpages = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                      for p in self._draft_cache.pools]
            out["draft"] = (jax.make_jaxpr(
                self._make_decode(self.drafter.model))(
                    jax.ShapeDtypeStruct((b0,), i32), *dpages,
                    jax.ShapeDtypeStruct((b0, m_blocks), i32),
                    jax.ShapeDtypeStruct((b0,), i32)),
                tuple(range(1, 1 + len(dpages))))
        return out

    def _decode_head_spec(self, width: int):
        """The decode program's first argument at bucket ``width``: a token
        a row, or a block of them."""
        shape = (width,) if self._gen is None \
            else (width, self._gen.block_length)
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def _decode_tail_spec(self, width: int):
        """The decode program's arguments behind the pools at bucket
        ``width``: tables, contexts (a block-decode program: each block's
        first position, its masked flags, whether the pass commits, where
        the answer ends), the previous launch's result and the row map (and
        the rows' state slots, for a model with state layers)."""
        i32 = jnp.int32
        row = jax.ShapeDtypeStruct((width,), i32)
        tables = jax.ShapeDtypeStruct((width, self.max_blocks_per_seq), i32)
        if self._gen is None:
            slots = (row,) if self.cache.states else ()
            return (tables, row, jax.ShapeDtypeStruct(
                (self._state_len(width) + self._n_counts,), i32), row,
                *slots)
        return (tables, row, self._decode_head_spec(width), row, row,
                jax.ShapeDtypeStruct(
                    (self._state_len(width) + self._n_counts,), i32),
                row)

    def compile_decode(self):
        """AOT lower+compile the decode executable at its smallest
        bucket — the compiled-HLO verifier's serving input
        (``analysis/hlo_check``). Returns ``(compiled,
        donated_leaves)``: the page pool's two donated buffers must
        realize input/output aliases (X002 — an unaliased pool doubles
        the engine's HBM footprint), and a single-partition decode
        module must compile with zero collectives (X001)."""
        b0 = self.decode_buckets.sizes[0]
        c = self.cache
        pages = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in c.arrays]
        compiled = self._decode_fn.lower(
            self._decode_head_spec(b0), *pages,
            *self._decode_tail_spec(b0)).compile()
        return compiled, len(pages)

    def compile_extend(self, verify: bool = False):
        """AOT lower+compile the extend executable (chunk signature, or
        the decode-gamma verify signature) for the X pass — same aliasing
        and zero-collective contract as :meth:`compile_decode`."""
        fn = self._verify_fn if verify else self._chunk_fn
        if fn is None:
            raise ValueError("extend executable not armed (enable "
                             "prefix_cache/chunked_prefill/speculative)")
        c = self.cache
        pages = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in c.arrays]
        i32 = jnp.int32
        if verify:
            b, L = self.decode_buckets.sizes[0], self.spec_gamma + 1
        else:
            b, L = 1, self.prefill_buckets.sizes[0]
        compiled = fn.lower(
            jax.ShapeDtypeStruct((b, L), i32), *pages,
            jax.ShapeDtypeStruct((b, self.max_blocks_per_seq), i32),
            jax.ShapeDtypeStruct((b,), i32),
            jax.ShapeDtypeStruct((b,), i32)).compile()
        return compiled, len(pages)

    def _maybe_lint(self) -> None:
        """FLAGS_static_analysis hook: on first dispatch, lint every
        armed step graph, verify the declared plan (one trace feeds
        them), and — final stage — verify the compiled decode module's
        optimized HLO against the plan (X-rules, analysis/hlo_check)."""
        if self._linted:
            return
        self._linted = True
        from ..analysis import hlo_check, jaxpr_lint, plan_check
        if jaxpr_lint.analysis_mode() == "off":
            return
        diags = []
        traced = self.trace_steps()
        for name, (closed, donate) in traced.items():
            diags += jaxpr_lint.lint_jaxpr(closed, donate_argnums=donate,
                                           where=f"serving.{name}")
        diags += plan_check.check_plan(self.plan, traced["decode"][0],
                                       donate_argnums=traced["decode"][1],
                                       where="serving")
        try:
            compiled, donated = self.compile_decode()
        except Exception:
            compiled = None  # first dispatch will surface the error
        if compiled is not None:
            diags += hlo_check.check_hlo(self.plan, compiled,
                                         donated_leaves=donated,
                                         where="serving.decode.hlo")
        if diags:
            jaxpr_lint.emit(diags, where="serving")

    # ------------------------------------------------------------------
    # Allocation, COW isolation, shared-block accounting
    # ------------------------------------------------------------------

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Evict-aware allocation: on a shortfall the prefix tree spills
        LRU refcount-0 leaves to the host tier until the grant fits (or
        nothing is evictable). The flag-off path is exactly
        ``allocator.alloc``."""
        got = self.cache.allocator.alloc(n)
        if got is None and self.prefix is not None:
            # evict with headroom: the per-token alloc(1) pattern would
            # otherwise pay a tree scan per block under pressure
            deficit = max(n - self.cache.allocator.n_free, 4)
            if self.prefix.evict(deficit) > 0:
                got = self.cache.allocator.alloc(n)
        if got is not None:
            self.peak_blocks_used = max(self.peak_blocks_used,
                                        self.cache.allocator.n_used)
        return got

    def _assert_cow(self, write_ids) -> None:
        """The runtime half of rule D005: no dispatch may scatter into a
        device block the prefix tree holds — shared pages are immutable;
        only the private tail is ever written."""
        if self.prefix is None:
            return
        bad = self.prefix.device_block_ids().intersection(
            int(i) for i in write_ids)
        if bad:
            raise AssertionError(
                f"COW write-isolation violated: dispatch would write "
                f"shared prefix blocks {sorted(bad)}")

    def _write_span_ids(self, seq: Sequence, start: int, n: int
                        ) -> List[int]:
        """Block ids covering token positions [start, start+n)."""
        if n <= 0:
            return []
        lo, hi = start // self.block_size, (start + n - 1) // self.block_size
        return seq.block_ids[lo:hi + 1]

    def _private_blocks(self, seq: Sequence) -> int:
        """The prefix-sharing cost model (satellite 2): blocks a
        preemption/shed of this sequence would actually free — its
        refcount-1 private tail, not the shared tree pages."""
        return len(seq.block_ids) - seq.n_shared_blocks

    def _cost_fn(self):
        """Victim-selection cost hook: armed only with the prefix cache
        (the flag-off scheduler order stays bitwise-identical)."""
        return self._private_blocks if self.prefix is not None else None

    def _free_seq_blocks(self, seq: Sequence) -> None:
        """One exit for a sequence's device-block ownership: release the
        tree attachments (the tree's own cache ref keeps shared pages
        resident) and free the private tail."""
        if seq.prefix_nodes:
            self.prefix.release(seq.prefix_nodes)
            seq.prefix_nodes = []
        private = seq.block_ids[seq.n_shared_blocks:]
        if private:
            self.cache.allocator.free(private)
        seq.block_ids = []
        seq.n_shared_blocks = 0
        if seq.state_slot:
            self.cache.slots.free([seq.state_slot])
            seq.state_slot = 0

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------

    def submit(self, request: Request) -> Union[Sequence, Rejected]:
        """Admit one request, or answer with a typed :class:`Rejected`
        (429-style) when the bounded queue or the host-spill budget is
        over capacity. Malformed requests (a total that can never fit
        ``max_seq_len``) still raise — that is a client contract error,
        not transient overload."""
        n_prompt = int(request.prompt_ids.size)
        with trace.span("serve/submit", rid=request.rid,
                        prompt_len=n_prompt):
            total = n_prompt + request.max_new_tokens
            if total > self.max_seq_len:
                raise ValueError(
                    f"request {request.rid!r}: prompt "
                    f"{request.prompt_ids.size} + max_new_tokens "
                    f"{request.max_new_tokens} exceeds max_seq_len "
                    f"{self.max_seq_len}")
            # the prompt must fit a registered prefill bucket on its own
            self.prefill_buckets.fit(request.prompt_ids.size)
            if not self.sched.can_accept():
                return self._reject(
                    request, "queue_full",
                    f"waiting queue at max_waiting={self.sched.max_waiting}")
            if (self.max_spilled_bytes is not None
                    and self._spilled_bytes > self.max_spilled_bytes):
                return self._reject(
                    request, "spill_budget",
                    f"host spill {self._spilled_bytes}B over budget "
                    f"{self.max_spilled_bytes}B")
            seq = Sequence(request)
            seq.t_submit = time.perf_counter()
            self._seqs[request.rid] = seq
            if self.journal is not None:
                self.journal.submitted(request)
            self.sched.submit(seq)
            self._gauges()
            return seq

    def _reject(self, request: Request, reason: str,
                detail: str) -> Rejected:
        rej = Rejected(request.rid, reason, detail)
        self.rejections.append(rej)
        metrics.counter("serving.rejected",
                        "submissions refused by bounded admission").inc()
        if self.journal is not None:
            self.journal.terminal(request.rid, "rejected", reason)
        request_timeline.current().record(
            rid=request.rid, prompt_tokens=request.prompt_ids.size,
            new_tokens=0, phases_ms={}, total_ms=0.0,
            outcome="rejected", error=f"{reason}: {detail}",
            deadline_ms=(None if request.deadline_s is None
                         else request.deadline_s * 1e3))
        return rej

    def _gauges(self) -> None:
        m = self._m
        m.queue_depth.set(len(self.sched.waiting))
        m.running.set(len(self.sched.running))
        used = self.cache.allocator.n_used
        self.peak_blocks_used = max(self.peak_blocks_used, used)
        live = used - (self.prefix.n_idle_device_blocks()
                       if self.prefix is not None else 0)
        self.peak_live_blocks = max(self.peak_live_blocks, live)
        usable = self.cache.num_blocks - 1
        m.free_block_frac.set(self.cache.allocator.n_free / usable
                              if usable else 0.0)
        if self.cache.slots is not None:
            m.slots_used.set(self.cache.slots.n_used)
            m.slots_free.set(self.cache.slots.n_free)
        if fleet_live.enabled():     # the armed exporter publishes it
            self._decode_p99()

    def _decode_p99(self) -> Optional[float]:
        """p99 of the decode-time window, sorted at most once an
        iteration and only for a reader: the shed policy, or the armed
        fleet exporter (through the ``serving.decode_p99_ms`` gauge)."""
        if self._p99_at != self.n_iterations:
            self._p99_at = self.n_iterations
            self._p99 = percentile(list(self._decode_ms), 99)
            if self._p99 is not None:
                self._m.decode_p99_ms.set(self._p99)
        return self._p99

    def reset_peaks(self) -> None:
        """Restart the peak-blocks watermarks (to read the steady
        state, not the warmup)."""
        self.peak_blocks_used = 0
        self.peak_live_blocks = 0
        self._gauges()

    # -- terminal non-success paths (isolation, deadlines, shedding) ---------

    def _cancel(self, seq: Sequence, status: Status, reason: str,
                *, diagnose: bool = False) -> None:
        """The one exit for every non-FINISHED ending: scheduler
        retirement, provable reclamation of device blocks AND host-spill
        buffers, journal acknowledgment, timeline record, counters. The
        allocator-invariant tests pin the zero-leak property."""
        self.sched.retire(seq, status)
        self._free_seq_blocks(seq)
        if seq.host_kv is not None:
            seq.host_kv = None
            seq.host_draft_kv = None
            seq.host_state = None
            self._account_spill(-seq.spilled_bytes)
            seq.spilled_bytes = 0
        seq.error = reason
        outcome = status.value
        metrics.counter(f"serving.{outcome}",
                        f"requests ending {outcome}").inc()
        if diagnose:
            self._diagnose_failure(seq, reason)
        if self.journal is not None:
            self.journal.terminal(seq.rid, outcome, reason)
        req = seq.request
        end = time.perf_counter()
        request_timeline.current().record(
            rid=seq.rid, prompt_tokens=seq.prompt_len,
            new_tokens=seq.n_generated,
            phases_ms={k: v * 1e3 for k, v in seq.phase_s.items()},
            total_ms=(end - seq.t_submit) * 1e3,
            ttft_ms=((seq.t_first_token - seq.t_submit) * 1e3
                     if seq.t_first_token is not None else None),
            preemptions=seq.preemptions, outcome=outcome, error=reason,
            deadline_ms=(None if req.deadline_s is None
                         else req.deadline_s * 1e3),
            **_commit_stamps(seq))
        self._gauges()

    def _diagnose_failure(self, seq: Sequence, reason: str) -> None:
        from ..analysis.jaxpr_lint import Diagnostic, emit
        d = Diagnostic(
            rule="F003", name="serving-request-failed", severity="warning",
            message=f"request {seq.rid!r} failed after "
                    f"{seq.n_generated} token(s): {reason}",
            hint="the failure is isolated to this request; the engine "
                 "loop continues and its blocks were reclaimed",
            where="serving.engine")
        self.diagnostics.append(d)
        # Operational finding — forced warn so it is visible even with
        # FLAGS_static_analysis=off (same contract as F001).
        emit([d], where="serving.engine", mode="warn")

    def _account_spill(self, delta_bytes: int) -> None:
        self._spilled_bytes = max(0, self._spilled_bytes + delta_bytes)
        metrics.gauge("serving.spilled_bytes",
                      "bytes of preempted KV held in the host tier").set(
                          self._spilled_bytes)

    def _expire_deadlines(self) -> None:
        """Cancel every live sequence past its deadline — iteration
        granularity, measured from TRUE submission time (``t_submit`` is
        never rewritten by preemption)."""
        now = time.perf_counter()
        live = list(self.sched.waiting) + list(self.sched.running)
        for seq in live:
            d = seq.request.deadline_s
            if d is not None and now - seq.t_submit > d:
                self._cancel(seq, Status.EXPIRED,
                             f"deadline {d * 1e3:.0f}ms exceeded "
                             f"({(now - seq.t_submit) * 1e3:.0f}ms elapsed)")

    def _apply_shed_policy(self) -> None:
        """One policy consult per iteration: set ``mode``, shed at most
        one request (lowest-priority, then most-private-blocks under the
        prefix cost model, youngest last; waiting first), and in
        degraded mode compute the shrunken decode-bucket cap."""
        pol = self.shed_policy
        if pol is None:
            return
        usable = self.cache.num_blocks - 1
        free_frac = self.cache.allocator.n_free / usable if usable else 0.0
        why = pol.overloaded(free_frac, self._decode_p99())
        if why is None:
            self.mode = "healthy"
            self._degraded_width = None
            return
        self.mode = "degraded" if pol.degrade else "shedding"
        metrics.counter("serving.overload_iterations",
                        "iterations spent in shed/degraded mode").inc()
        # degrade mode preserves residents (they get a smaller bucket);
        # pure shed mode may drop running work to free blocks
        victim = self.sched.shed_candidate(waiting_only=pol.degrade,
                                           cost=self._cost_fn())
        if victim is not None:
            self._cancel(victim, Status.SHED, f"load shed: {why}")
        if pol.degrade and len(self.sched.running) > 1:
            fit = self.decode_buckets.fit(len(self.sched.running))
            smaller = [b for b in self.decode_buckets.sizes if b < fit]
            self._degraded_width = smaller[-1] if smaller else 1

    def _enforce_degraded_width(self) -> None:
        """Degraded mode shrinks the active decode bucket: preempt the
        lowest-priority residents (most private blocks first under the
        prefix cost model — the normal spill path) until the batch fits
        the smaller bucket."""
        cap = self._degraded_width
        if cap is None:
            return
        while len(self.sched.running) > cap:
            victim = self.sched.preempt_victim(cost=self._cost_fn())
            if victim is None:
                break
            try:
                self._preempt(victim)
            except SpillError as e:
                self._cancel(victim, Status.FAILED,
                             f"KV spill failed: {e}", diagnose=True)

    # -- admission (prefill / restore) --------------------------------------

    def _try_admit(self) -> bool:
        if self.mode != "healthy":
            return False            # overload: pause fresh admissions
        seq = self.sched.peek_waiting()
        if seq is None or not self.sched.has_capacity():
            return False
        if seq.status is Status.PREEMPTED:
            return self._admit_restore(seq)
        if self.prefix is not None or self.chunk_tokens:
            return self._admit_extend(seq)
        # -- the flag-off path: byte-identical to the PR-8/9 engine ------
        n_need = _ceil_div(seq.prompt_len, self.block_size)
        ids = self.cache.allocator.alloc(n_need)
        if ids is None:
            if not self.sched.running and self.cache.allocator.n_used == 0:
                # an idle pool that still cannot grant the front request
                # will never be able to: fail it (isolation), keep going
                self._cancel(
                    seq, Status.FAILED,
                    f"needs {n_need} KV block(s), pool has only "
                    f"{self.cache.allocator.n_free}", diagnose=True)
                return True
            return False
        slot = self._take_slot(ids)
        if slot is None:
            return False
        self.sched.admit(seq)
        seq.state_slot = slot
        try:
            self._prefill(seq, ids)
        except Exception as e:  # per-sequence device error: isolate it
            if seq.block_ids:
                # blocks granted this admission that _cancel would miss
                extra = [i for i in ids if i not in seq.block_ids]
            else:
                seq.block_ids = list(ids)
                extra = []
            if extra:
                self.cache.allocator.free(extra)
            self._cancel(seq, Status.FAILED,
                         f"{type(e).__name__}: {e}", diagnose=True)
        return True

    def _admit_restore(self, seq: Sequence) -> bool:
        """Re-admit a preempted sequence: restore its spilled private
        blocks (the shared prefix never left the device — its refs were
        kept through preemption)."""
        n_need = int(seq.host_kv[0].shape[1])
        ids = self._alloc(n_need)
        if ids is None:
            return False
        slot = self._take_slot(ids)
        if slot is None:
            return False
        self.sched.admit(seq)
        seq.state_slot = slot
        try:
            self._restore(seq, ids)
        except Exception as e:
            if not set(ids) <= set(seq.block_ids):
                self.cache.allocator.free(ids)
            self._cancel(seq, Status.FAILED,
                         f"{type(e).__name__}: {e}", diagnose=True)
        return True

    def _take_slot(self, ids: List[int]) -> Optional[int]:
        """A state slot for a sequence being admitted with blocks ``ids``: 0
        for a model without state layers, None (the blocks given back) where
        none is free."""
        if self.cache.slots is None:
            return 0
        got = self.cache.slots.alloc(1)
        if got is None:
            self.cache.allocator.free(ids)
            return None
        return got[0]

    def _admit_extend(self, seq: Sequence) -> bool:
        """Admission with the prefix tree and/or chunked prefill armed:
        attach to the longest cached full-block prefix copy-on-write,
        allocate blocks for the first prefill span (the whole suffix, or
        one chunk under the chunked budget), and either prefill inline
        (one-shot path) or leave the sequence in the chunk pipeline."""
        prompt = seq.request.prompt_ids
        chain: List[Any] = []
        shared_ids: List[int] = []
        if self.prefix is not None and not seq.prefix_nodes:
            chain = self.prefix.match(prompt)
            if chain:
                shared_ids = self.prefix.attach(seq.rid, chain, self._alloc)
                chain = chain[:len(shared_ids)]
        cached = len(shared_ids) * self.block_size
        span = seq.prompt_len - cached
        if self.chunk_tokens:
            span = min(span, self.chunk_tokens)
        n_new = _ceil_div(cached + span, self.block_size) - len(shared_ids)
        ids = self._alloc(n_new)
        if ids is None:
            if chain:
                self.prefix.release(chain)      # clean retry next round
            if not self.sched.running and \
                    self.cache.allocator.n_used == len(
                        self.prefix.device_block_ids()
                        if self.prefix is not None else ()):
                self._cancel(
                    seq, Status.FAILED,
                    f"needs {n_new} KV block(s) beyond the shared prefix, "
                    f"pool has only {self.cache.allocator.n_free}",
                    diagnose=True)
                return True
            return False
        self.sched.admit(seq)
        if self.prefix is not None:
            self.prefix.account(seq.prompt_len, cached)
        if not self.chunk_tokens and cached == 0:
            # cold full prompt, no chunk budget: the one-shot flash
            # prefill path (it inserts the finished blocks into the tree)
            try:
                self._prefill(seq, ids)
            except Exception as e:
                if not seq.block_ids:
                    seq.block_ids = list(ids)
                self._cancel(seq, Status.FAILED,
                             f"{type(e).__name__}: {e}", diagnose=True)
            return True
        seq.add_phase("queue", time.perf_counter() - seq.t_enqueue)
        seq.prefix_nodes = list(chain)
        seq.n_shared_blocks = len(shared_ids)
        seq.block_ids = shared_ids + ids
        seq.block_log.extend(shared_ids + ids)
        seq.ctx_len = cached
        seq.prefill_pos = cached
        if self.chunk_tokens:
            return True             # the chunk pipeline takes it from here
        try:
            self._chunk_prefill(seq, span)
        except Exception as e:
            self._cancel(seq, Status.FAILED,
                         f"{type(e).__name__}: {e}", diagnose=True)
        return True

    def _prefill(self, seq: Sequence, block_ids: List[int]) -> None:
        if self._gen is not None:
            return self._prefill_blocks(seq, block_ids)
        self._prefill_run(seq, block_ids, seq.prompt_len)

    def _prefill_blocks(self, seq: Sequence, block_ids: List[int]) -> None:
        """Admission of a request of a model that generates by diffusion
        over blocks: the prompt's whole blocks are prefilled clean in one
        block-causal pass that only stores their keys and values (the bucket's
        padding lies in later blocks, which no prompt position sees) and
        yields no token; the prompt's tail opens the first block in flight. A
        prompt shorter than a block has no such pass."""
        B = self._gen.block_length
        n_clean = seq.prompt_len // B * B
        if n_clean:
            self._prefill_run(seq, block_ids, n_clean)
        else:
            seq.add_phase("queue", time.perf_counter() - seq.t_enqueue)
            seq.block_ids = list(block_ids)
            seq.block_log.extend(block_ids)
            seq.prefill_pos = seq.prompt_len
        seq.block = self._fresh_block(seq, n_clean)

    def _prefill_run(self, seq: Sequence, block_ids: List[int],
                     n_tokens: int) -> None:
        """The prefill program over the prompt's first ``n_tokens`` tokens:
        all of it, whose last position gives the first token, or (a model
        that generates by diffusion over blocks) its whole blocks, which
        give none."""
        seq.add_phase("queue", time.perf_counter() - seq.t_enqueue)
        bucket = self.prefill_buckets.fit(n_tokens)
        with trace.span("serve/prefill", rid=seq.rid,
                        prompt_len=n_tokens, bucket=bucket) as sp:
            with trace.span("serve/prefill/build"):
                nb_bucket = bucket // self.block_size
                ids = pad_axis(seq.request.prompt_ids[None, :n_tokens], 1,
                               bucket)
                btab = np.full((nb_bucket,), NULL_BLOCK, np.int32)
                # (a prompt's tail may have a block past the bucket's)
                held = block_ids[:nb_bucket]
                btab[:len(held)] = held
                args = (jnp.asarray(ids, jnp.int32), *self.cache.arrays,
                        jnp.asarray(btab),
                        jnp.asarray(n_tokens, jnp.int32))
                if self.cache.states:
                    args += (jnp.asarray(seq.state_slot, jnp.int32),)
                self._maybe_lint()
                self._assert_cow(block_ids)
                new = self._sent_prefill.observe_tree(
                    "serving.prefill", self._undonated(args),
                    donate=self._donated, where="serving.prefill")
            with trace.span("serve/prefill/launch"):
                tok, *pools = _dispatch(self._prefill_fn, args, new)
            with trace.span("serve/prefill/wait") as wait:
                # host sync: the first token exists now
                tok = int(self._take_counts(
                    np.asarray(tok), 1, n_tokens).reshape(-1)[0])
            _account(sp.t0_ns, wait.end_ns, "prefill", (seq,))
            with trace.span("serve/prefill/commit"):
                self.cache.swap(*pools)
                seq.block_ids = list(block_ids)
                seq.block_log.extend(block_ids)
                seq.ctx_len = n_tokens
                seq.prefill_pos = seq.prompt_len
                self._m.prefill_real.inc(n_tokens)
                self._m.prefill_bucket.inc(bucket)
                if self._gen is not None:
                    return      # no first token: the blocks give the tokens
                self._commit_first_token(seq, tok)
                self._mirror_draft_prefill(seq)
                if self.prefix is not None:
                    new_nodes = self.prefix.insert(
                        seq.request.prompt_ids, seq.block_ids,
                        seq.prompt_len, have=len(seq.prefix_nodes))
                    seq.prefix_nodes += new_nodes
                    seq.n_shared_blocks = len(seq.prefix_nodes)
                if seq.is_finished_by(tok):
                    self._finish(seq)

    @staticmethod
    def _commit_first_token(seq: Sequence, tok: int) -> None:
        seq.out_tokens.append(tok)
        seq.t_first_token = time.perf_counter()
        if trace.enabled():
            seq.token_t_ns.append(int(seq.t_first_token * 1e9))

    def _chunk_prefill(self, seq: Sequence, span: int) -> None:
        """Prefill ``span`` prompt tokens through the ``extend``
        executable starting at ``seq.prefill_pos`` (a block boundary):
        the prefix-hit suffix path and the chunked-prefill path. The
        final span commits the first generated token; every completed
        full block is inserted into the prefix tree as it fills."""
        start = seq.prefill_pos
        L = self.prefill_buckets.fit(span)
        with trace.span("serve/extend", rid=seq.rid, prompt_len=span,
                        bucket=L) as sp:
            with trace.span("serve/prefill/build"):
                toks = pad_axis(
                    seq.request.prompt_ids[None, start:start + span], 1, L)
                table = np.full((1, self.max_blocks_per_seq), NULL_BLOCK,
                                np.int32)
                table[0, :len(seq.block_ids)] = seq.block_ids
                args = (jnp.asarray(toks, jnp.int32), *self.cache.arrays,
                        jnp.asarray(table),
                        jnp.asarray([start], jnp.int32),
                        jnp.asarray([span], jnp.int32))
                self._maybe_lint()
                self._assert_cow(self._write_span_ids(seq, start, span))
                new = self._sent_chunk.observe_tree(
                    "serving.extend", self._undonated(args),
                    donate=self._donated, where="serving.extend")
            with trace.span("serve/prefill/launch"):
                out, *pools = _dispatch(self._chunk_fn, args, new)
            with trace.span("serve/prefill/wait") as wait:
                out = np.asarray(out)   # host sync: the chunk is done
            _account(sp.t0_ns, wait.end_ns, "chunk_prefill", (seq,))
            with trace.span("serve/prefill/commit"):
                self.cache.swap(*pools)
                if self._draft_extend_fn is not None:
                    _, *dpools = self._draft_extend_fn(
                        args[0], *self._draft_cache.pools,
                        *args[1 + self._n_pools:])
                    self._draft_cache.swap(*dpools)
                    seq.draft_ctx = start + span
                seq.prefill_pos = start + span
                seq.ctx_len = seq.prefill_pos
                self._m.prefill_real.inc(span)
                self._m.prefill_bucket.inc(L)
                if self.prefix is not None:
                    new_nodes = self.prefix.insert(
                        seq.request.prompt_ids, seq.block_ids,
                        seq.prefill_pos, have=len(seq.prefix_nodes))
                    seq.prefix_nodes += new_nodes
                    seq.n_shared_blocks = len(seq.prefix_nodes)
                if self.chunk_tokens:
                    metrics.counter(
                        "serving.chunked_prefill_iterations",
                        "prefill chunks interleaved with decode").inc()
                if seq.prefill_pos >= seq.prompt_len:
                    tok = int(out[0])   # last_only: [B] of last-real argmax
                    self._commit_first_token(seq, tok)
                    if seq.is_finished_by(tok):
                        self._finish(seq)

    def _mirror_draft_prefill(self, seq: Sequence) -> None:
        """ModelDrafter: materialize the drafter's prompt KV in the
        mirrored pool (same block table) after a one-shot target
        prefill."""
        if self._draft_extend_fn is None or not seq.block_ids:
            return
        p = seq.prompt_len
        L = self.prefill_buckets.fit(p)
        toks = pad_axis(seq.request.prompt_ids[None, :], 1, L)
        table = np.full((1, self.max_blocks_per_seq), NULL_BLOCK, np.int32)
        table[0, :len(seq.block_ids)] = seq.block_ids
        _, *dpools = self._draft_extend_fn(
            jnp.asarray(toks, jnp.int32), *self._draft_cache.pools,
            jnp.asarray(table),
            jnp.asarray([0], jnp.int32), jnp.asarray([p], jnp.int32))
        self._draft_cache.swap(*dpools)
        seq.draft_ctx = p

    def _chunk_iteration(self) -> None:
        """The chunked-prefill scheduler slot: at most ``chunk_tokens``
        prompt tokens prefill per engine iteration (the oldest
        mid-prefill resident goes first), interleaved with the decode
        work — a long prompt costs every resident a bounded slice per
        token instead of one unbounded stall."""
        if not self.chunk_tokens:
            return
        for seq in list(self.sched.running):
            if seq.status is not Status.RUNNING or \
                    seq.prefill_pos >= seq.prompt_len:
                continue
            span = min(self.chunk_tokens, seq.prompt_len - seq.prefill_pos)
            needed = _ceil_div(seq.prefill_pos + span, self.block_size)
            ok = True
            while len(seq.block_ids) < needed:
                got = self._alloc(1)
                if got is not None:
                    seq.block_ids.extend(got)
                    seq.block_log.extend(got)
                    continue
                victim = self.sched.preempt_victim(exclude=seq,
                                                   cost=self._cost_fn())
                if victim is None:
                    self._cancel(
                        seq, Status.FAILED,
                        f"needs block {len(seq.block_ids) + 1} of "
                        f"{needed} mid-prefill and there is nothing "
                        "left to preempt — the request outgrew the pool",
                        diagnose=True)
                    ok = False
                    break
                try:
                    self._preempt(victim)
                except SpillError as e:
                    self._cancel(victim, Status.FAILED,
                                 f"KV spill failed: {e}", diagnose=True)
            if ok:
                try:
                    self._chunk_prefill(seq, span)
                except Exception as e:
                    self._cancel(seq, Status.FAILED,
                                 f"{type(e).__name__}: {e}", diagnose=True)
            break                     # one chunk per iteration: the budget

    def _restore(self, seq: Sequence, ids: List[int]) -> None:
        seq.add_phase("queue", time.perf_counter() - seq.t_enqueue)
        with trace.span("serve/restore", rid=seq.rid,
                        blocks=len(ids)) as sp:
            self.cache.restore(seq.host_kv, ids)
            if seq.host_state is not None:
                self.cache.restore_state(seq.host_state, seq.state_slot)
                seq.host_state = None
            if self._draft_cache is not None and \
                    seq.host_draft_kv is not None:
                self._draft_cache.restore(seq.host_draft_kv, ids)
                seq.host_draft_kv = None
            seq.host_kv = None
            self._account_spill(-seq.spilled_bytes)
            seq.spilled_bytes = 0
            # the shared prefix never left the device — rebuild the table
            # as (pinned shared ids) + (freshly restored private ids)
            seq.block_ids = seq.block_ids[:seq.n_shared_blocks] + list(ids)
            seq.block_log.append(-1)  # spill/restore boundary
            seq.block_log.extend(ids)
            if seq.block is not None:
                # nothing of an unfinished block was committed: its passes
                # are lost, and it starts again from masks
                seq.block = self._fresh_block(seq, seq.block.pos0)
        # KV re-materialization substitutes for prefill on resume
        _account(sp.t0_ns, sp.end_ns, "prefill", (seq,))

    def _preempt(self, seq: Sequence) -> None:
        if self.cache.states and self._flight_row(seq) >= 0:
            # the launch in flight has advanced this row's state by a token
            # the host has not taken; dropped and computed again, it would
            # count twice in the state. So that launch is collected first, and
            # the state spilled is that of the committed tokens only.
            ahead, self._ahead = self._ahead, None
            self._decode_collect(ahead)
            if seq.status is not Status.RUNNING:
                return          # that token finished it
        with trace.span("serve/preempt", rid=seq.rid):
            self._spill(seq)

    def _spill(self, seq: Sequence) -> None:
        self.sched.preempt(seq)
        shared = seq.n_shared_blocks
        private = seq.block_ids[shared:]
        # refcount-aware spill: the shared prefix pages stay pinned on
        # device (this sequence keeps its refs; other sharers and the
        # tree hold them anyway) — only the refcount-1 private tail
        # moves, and it moves exactly once
        if self._draft_cache is not None and private:
            seq.host_draft_kv = self._draft_cache.snapshot(private)
        seq.host_kv = self.cache.spill(private)
        seq.block_ids = seq.block_ids[:shared]
        draft_bytes = (self._draft_cache.bytes_per_block * len(private)
                       if self._draft_cache is not None else 0)
        seq.spilled_bytes = (len(private) * self.cache.bytes_per_block
                             + draft_bytes)
        if seq.state_slot:
            seq.host_state = self.cache.spill_state(seq.state_slot)
            seq.state_slot = 0
            seq.spilled_bytes += self.cache.bytes_per_slot
        self._account_spill(seq.spilled_bytes)
        # queue time for the preempted span restarts now; t_submit stays
        # the TRUE arrival so latency + deadlines measure end to end
        seq.t_requeue = time.perf_counter()
        metrics.counter("serving.preemptions",
                        "sequences preempted for KV capacity").inc()

    # -- the decode iteration ------------------------------------------------

    def _decodable(self) -> List[Sequence]:
        """Resident sequences with a committed frontier token (a
        mid-prefill chunked sequence is resident but not yet
        decodable)."""
        if self._gen is not None:
            return [s for s in self.sched.iteration_batch()
                    if s.block is not None]
        return [s for s in self.sched.iteration_batch() if s.out_tokens]

    def _flight_row(self, seq: Sequence) -> int:
        """The row of the launch in flight whose token ``seq`` will be given,
        or -1: it holds none, or the row was cancelled or preempted since and
        its token will be dropped."""
        ahead = self._ahead
        if ahead is None:
            return -1
        row = ahead.row_of.get(id(seq), -1)
        if row >= 0 and not _still_rows(seq, ahead.epochs[row]):
            return -1
        return row

    def _ensure_decode_blocks(self) -> None:
        """Every sequence of the next launch needs real blocks through the
        position it writes there (+ gamma under speculation): ``ctx_len``,
        or one further for a row whose token is in flight (a row whose last
        token that is needs none); preempt (lowest-priority,
        most-private-blocks, youngest) to make room. Pool exhaustion with
        nothing left to preempt fails *that* sequence (F003) —
        :class:`OutOfBlocksError` never crosses the engine loop."""
        if self._gen is not None:
            # a block-diffusion row: the block the host knows it in and the
            # next (the launch in flight may commit the one, and the launch
            # being built then runs the other), up to the answer's last block
            B = self._gen.block_length
            for seq in list(self.sched.running):
                if seq.status is not Status.RUNNING or seq.block is None:
                    continue
                reach = min(seq.block.pos0 + 2 * B,
                            _ceil_div(seq.end_pos, B) * B)
                self._grow_blocks(seq, _ceil_div(reach, self.block_size))
            return
        lookahead = self.spec_gamma if self.spec_gamma else 0
        for seq in list(self.sched.running):
            if seq.status is not Status.RUNNING or not seq.out_tokens:
                continue
            reach = seq.ctx_len + lookahead
            if len(seq.block_ids) > (reach + 1) // self.block_size:
                continue        # enough even one position further
            ahead = int(self._flight_row(seq) >= 0)
            if ahead and seq.length_reached(1):
                continue
            self._grow_blocks(seq, (reach + ahead) // self.block_size + 1)

    def _grow_blocks(self, seq: Sequence, needed: int) -> None:
        """Top ``seq`` up to ``needed`` blocks, preempting for room; with
        nothing left to preempt the sequence fails (or, where a preemption
        took the launch in flight first, that launch's token finished it)."""
        while seq.status is Status.RUNNING and len(seq.block_ids) < needed:
            got = self._alloc(1)
            if got is not None:
                seq.block_ids.extend(got)
                seq.block_log.extend(got)
                continue
            victim = self.sched.preempt_victim(exclude=seq,
                                               cost=self._cost_fn())
            if victim is None:
                err = OutOfBlocksError(
                    f"sequence {seq.rid!r} needs block "
                    f"{len(seq.block_ids) + 1} of {needed} and there "
                    "is nothing left to preempt — the request "
                    "outgrew the pool")
                self._cancel(seq, Status.FAILED, str(err), diagnose=True)
                break
            try:
                self._preempt(victim)
            except SpillError as e:
                self._cancel(victim, Status.FAILED,
                             f"KV spill failed: {e}", diagnose=True)

    def _decode_rows(self) -> List[Tuple[Sequence, int]]:
        """Who decodes next, each with the row of the launch in flight that
        holds its token (-1: the host has it). Decided before that launch's
        tokens are known: a row that reaches ``max_new_tokens`` with the
        token in flight is left out; whether that token ends a request some
        other way is not known, and such a row runs once too often
        (:meth:`_decode_collect` drops the token)."""
        rows = []
        for seq in self._decodable():
            src = self._flight_row(seq)
            if src >= 0 and seq.length_reached(1):
                continue
            rows.append((seq, src))
        return rows

    def _decode_iteration(self) -> None:
        """Launch the next decode iteration, then take the tokens of the one
        the last step launched: the new program is queued behind the old one
        while that still runs, and the wake-up, the commit and the next
        step's build happen beside the device. Where the next launch falls
        into another bucket than the one in flight (or there is nothing to
        launch) the tokens are taken first and every row is fed by the host:
        one compiled program a bucket, whose ``prev`` has that bucket's
        shape. (Speculation keeps its own iteration, which waits.) A model
        that generates by diffusion over blocks runs a pass where the others
        decode a token, in the same order through the same launch and
        collect; its own are who runs (``_block_rows``), what the host sends
        up (``_block_args``) and what a row's result commits
        (``_block_commit``)."""
        if self.spec_gamma:
            batch = self._decodable()
            if batch:
                self._spec_iteration(batch)
            return
        pick = self._decode_rows if self._gen is None else self._block_rows
        rows, prev = pick(), self._ahead
        if prev is not None and not (
                rows and self.decode_buckets.fit(len(rows)) == prev.width):
            self._ahead = None
            self._decode_collect(prev)
            rows, prev = pick(), None
        self._ahead = self._decode_launch(rows) if rows else None
        if prev is not None:
            self._decode_collect(prev)

    def _no_prev_for(self, width: int):
        """What a launch with no predecessor of its bucket reads as ``prev``
        (every ``src`` is -1, so its values are not read): zeros of the
        result's shape, placed as a program's results are (the pools are the
        last program's), so that the jitted program sees one signature."""
        blank = self._no_prev.get(width)
        if blank is None:
            like = self.cache.pools[0]
            blank = jax.device_put(
                np.zeros((self._state_len(width) + self._n_counts,),
                         np.int32),
                like.sharding if like.committed else None)
            self._no_prev[width] = blank
        return blank

    def _decode_launch(self, rows: List[Tuple[Sequence, int]]) -> _Launch:
        """Build and launch one decode iteration over ``rows`` and return
        without waiting. A row the launch in flight holds reads its token
        (a block model's row: its block, or after its commit pass the next
        one) from that launch's result on the device and stands that far
        ahead of what the host has committed."""
        batch = [seq for seq, _ in rows]
        n = len(batch)
        width = self.decode_buckets.fit(n)
        m_blocks = self.max_blocks_per_seq
        host_args = self._token_args if self._gen is None \
            else self._block_args
        with self._acted_span("serve/decode", rows=n, width=width) as sp:
            with trace.span("serve/decode/build"):
                src = np.full((width,), -1, np.int32)
                tables = np.full((width, m_blocks), NULL_BLOCK, np.int32)
                src[:n] = [row for _, row in rows]
                for i, seq in enumerate(batch):
                    tables[i] = seq.table_row(m_blocks, NULL_BLOCK)
                # (the program does not read the host's token or block
                # where src >= 0)
                first, after = host_args(batch, src, width)
                prev = self._ahead.out if self._ahead is not None \
                    else self._no_prev_for(width)
                first_d, tables_d, *after_d, src_d = jax.device_put(
                    (first, tables, *after, src))
                args = (first_d, *self.cache.arrays, tables_d, *after_d,
                        prev, src_d)
                if self.cache.states:
                    slots = np.zeros((width,), np.int32)
                    slots[:n] = [seq.state_slot for seq in batch]
                    args += (jax.device_put(slots),)
                n_device = int((src >= 0).sum())
                self._m.fed_device.inc(n_device)
                self._m.fed_host.inc(n - n_device)
            with trace.span("serve/decode/checks"):
                self._maybe_lint()
                if self.prefix is not None:
                    for seq, pos in zip(batch, after[0].tolist()):
                        self._assert_cow(self._write_span_ids(seq, pos, 1))
                new = self._sent_decode.observe_tree(
                    "serving.decode", self._undonated(args),
                    donate=self._donated, where="serving.decode")
            with trace.span("serve/decode/launch"):
                out, *pools = _dispatch(self._decode_fn, args, new)
                # the pools this program returns are the cache from here
                # on: whatever is launched before its tokens are taken (a
                # prefill, a spill) runs behind it on them
                self.cache.swap(*pools)
        return _Launch(batch, [seq.preemptions for seq in batch], width,
                       after[0], out, sp.t0_ns,
                       {id(seq): i for i, seq in enumerate(batch)})

    def _token_args(self, batch: List[Sequence], src: np.ndarray,
                    width: int):
        """What the host sends a decode program beside tables and ``src``:
        ``(tokens [W], (lens [W],))``, the first before the pools, the rest
        behind the tables; ``lens`` is where each row writes."""
        n = len(batch)
        tokens = np.zeros((width,), np.int32)
        lens = np.zeros((width,), np.int32)
        tokens[:n] = [seq.out_tokens[-1] for seq in batch]
        lens[:n] = [seq.ctx_len for seq in batch]
        lens[:n] += src[:n] >= 0
        return tokens, (lens,)

    def _decode_collect(self, launch: _Launch) -> None:
        """Wait for a launched decode iteration and commit its tokens. A row
        that was cancelled or preempted while the program ran, or that the
        previous launch's token finished (an end-of-sequence token: the one
        ending the host cannot see ahead), is skipped: its token (a block
        model's row: its pass) is dropped, and computed again if the row
        comes back."""
        batch, epochs, width, _, out, t0_ns, _ = launch
        rows = len(batch)
        gen = self._gen
        per_row = 1 if gen is None else gen.block_length
        with trace.span("serve/decode", rows=rows, width=width):
            with self._acted_span("serve/decode/wait") as wait:
                # host sync per iteration
                out = self._take_counts(
                    np.asarray(out), self._state_len(width), rows * per_row)
            if self._ahead is not None and wait.end_ns > self._ahead.t0_ns:
                # the launch queued behind this one began on the device now
                self._ahead = self._ahead._replace(t0_ns=wait.end_ns)
            with trace.span("serve/decode/commit"):
                # Drill seam: a kill here lands AFTER the iteration's
                # compute but BEFORE any token is committed/acknowledged —
                # the relaunch must replay every in-flight request from
                # scratch, exactly once.
                _fault_fire("serve.mid_decode")
                live = [i for i, (seq, epoch) in enumerate(zip(batch, epochs))
                        if _still_rows(seq, epoch)]
                if len(live) < rows:
                    self._m.fed_dropped.inc(rows - len(live))
                _account(t0_ns, wait.end_ns, "decode",
                         [batch[i] for i in live])
                self._decode_done(t0_ns, wait)
                # one commit stamp a step, shared by its rows; none
                # under FLAGS_telemetry=off
                now_ns = time.perf_counter_ns() if trace.enabled() else 0
                commit = self._token_commit if gen is None \
                    else self._block_commit
                for seq in commit(launch, out, live, now_ns):
                    self._finish(seq)

    def _token_commit(self, launch: _Launch, out: np.ndarray,
                      live: List[int], now_ns: int) -> List[Sequence]:
        """Commit a decode program's token to each live row, and return the
        rows that token finished."""
        self._kv_count(launch.lens, self._decode_paged)
        if self.cache.states:
            n = len(launch.batch)
            self._m.state_needed.inc(n)
            self._m.state_read.inc(n if self._state_paged else launch.width)
        toks = out.tolist()
        finished: List[Sequence] = []
        for i in live:
            seq, tok = launch.batch[i], toks[i]
            seq.ctx_len += 1
            seq.out_tokens.append(tok)
            if now_ns:
                seq.token_t_ns.append(now_ns)
            if seq.is_finished_by(tok):
                finished.append(seq)
        return finished

    # -- the decode iteration of a model that generates by diffusion --------

    def _fresh_block(self, seq: Sequence, pos0: int) -> BlockInFlight:
        return BlockInFlight.fresh(seq.request, pos0,
                                   self._gen.block_length, self._gen.mask_id)

    def _block_rows(self) -> List[Tuple[Sequence, int]]:
        """:meth:`_decode_rows` for blocks: who runs a pass next, each with
        the row of the launch in flight that holds its state (-1: the host
        has it). A row whose launch in flight is the commit pass of its
        answer's last block is left out (the host knows a whole block's next
        pass commits it); a block that ends its request some other way (an
        end-of-sequence token) runs once more and that result is dropped."""
        B = self._gen.block_length
        rows = []
        for seq in self._decodable():
            src = self._flight_row(seq)
            blk = seq.block
            if src >= 0 and blk.stage == 1 and blk.pos0 + B >= seq.end_pos:
                continue
            rows.append((seq, src))
        return rows

    def _block_args(self, batch: List[Sequence], src: np.ndarray,
                    width: int):
        """:meth:`_token_args` for a pass: ``(tokens [W, B], (pos0 [W],
        masked [W, B], commit [W], end_pos [W]))``, each row's block as the
        host knows it."""
        B = self._gen.block_length
        tokens = np.zeros((width, B), np.int32)
        masked = np.zeros((width, B), np.int32)
        pos0 = np.zeros((width,), np.int32)
        commit = np.zeros((width,), np.int32)
        end_pos = np.zeros((width,), np.int32)
        for i, seq in enumerate(batch):
            blk = seq.block
            tokens[i], masked[i] = blk.tokens, blk.masked
            pos0[i], commit[i] = blk.pos0, blk.stage == 1
            end_pos[i] = seq.end_pos
        self._m.pass_rows.inc(len(batch))
        return tokens, (pos0, masked, commit, end_pos)

    def _block_commit(self, launch: _Launch, out: np.ndarray,
                      live: List[int], now_ns: int) -> List[Sequence]:
        """:meth:`_token_commit` for a pass: take each live row's new state.
        Only a commit pass gives tokens: the block's positions from the
        prompt's end to the answer's become output together, and ``ctx_len``
        moves past the block. (A row that was not live starts its block
        again from masks if it comes back.)"""
        batch, width = launch.batch, launch.width
        rows = len(batch)
        B = self._gen.block_length
        toks, masked, stage, pos0 = self._block_state(out, width)
        self._m.unmasked_threshold.inc(int(out[-2]))
        self._m.unmasked_schedule.inc(int(out[-1]))
        # the last position each row attends (a pad row: none)
        reach = np.zeros((width,), np.int64)
        reach[:rows] = pos0[:rows] + (B - 1)
        self._kv_count(reach, self._decode_paged)
        n_commit = int((stage[live] == 2).sum())
        self._m.passes_commit.inc(n_commit)
        self._m.passes_denoise.inc(len(live) - n_commit)
        finished: List[Sequence] = []
        for i in live:
            seq, p0 = batch[i], int(pos0[i])
            if stage[i] != 2:
                seq.block = BlockInFlight(p0, toks[i], masked[i] > 0,
                                          int(stage[i]))
                continue
            self._m.blocks.inc()
            first = max(seq.prompt_len - p0, 0)
            seq.ctx_len = min(p0 + B, seq.end_pos)
            if not seq.out_tokens:
                seq.t_first_token = time.perf_counter()
            for tok in toks[i, first:seq.ctx_len - p0].tolist():
                seq.out_tokens.append(tok)
                if now_ns:
                    seq.token_t_ns.append(now_ns)
                if seq.is_finished_by(tok):
                    finished.append(seq)
                    break
            else:
                seq.block = self._fresh_block(seq, p0 + B)
        return finished

    def _decode_done(self, t0_ns: int, wait) -> None:
        """The decode iteration's wall time, the start of its build to the
        arrival of its tokens, to the policy's window and the histogram."""
        if wait.end_ns:
            ms = (wait.end_ns - t0_ns) / 1e6
            self._decode_ms.append(ms)
            self._m.decode_step_ms.observe(ms)

    def _kv_count(self, lens: np.ndarray, paged: bool = False) -> None:
        """Useful over attempted where the padding happens, from the last
        position each row of the bucket attends (``lens``). The dense
        decode program and verify are handed the whole table of every row
        of the bucket, whatever the rows' contexts; the paged decode kernel
        reads each row's pages up to its context and the token it wrote
        (a pad row: the null page), the last page whole. Each page is read
        once from every pool (``serving.kv_page_fetches``: keys and values
        apart 2 a page, a latent or a fused row 1)."""
        self._m.kv_needed.inc(int(lens.sum()))
        bs = self.block_size
        pages = int((lens // bs + 1).sum()) if paged \
            else len(lens) * self.max_blocks_per_seq
        self._m.kv_gathered.inc(pages * bs)
        self._m.kv_fetches.inc(pages * len(self.cache.pools))

    # -- speculative decoding ------------------------------------------------

    def _draft_proposals(self, batch: List[Sequence], width: int,
                         tables: np.ndarray) -> List[List[int]]:
        """Per-sequence proposals (each ≤ gamma tokens). The NGram
        drafter is pure host work; the ModelDrafter runs sequential
        decode dispatches over the mirrored pool — each feed writes the
        fed token's KV at its position, catch-up feeds (committed tokens
        whose drafter KV a rejection invalidated) first."""
        gamma = self.spec_gamma
        if not isinstance(self.drafter, ModelDrafter):
            return [self.drafter.propose(
                list(s.request.prompt_ids) + s.out_tokens, gamma)
                for s in batch]
        hists = [list(int(t) for t in s.request.prompt_ids) + s.out_tokens
                 for s in batch]
        feeds = [h[s.draft_ctx:] for h, s in zip(hists, batch)]
        # feeds ends with the frontier token t0 (KV absent); catch-up
        # length is len(feeds)-1; one proposal lands per feed from t0 on
        steps = max(len(f) - 1 for f in feeds) + gamma
        proposals: List[List[int]] = [[] for _ in batch]
        cur = [list(f) for f in feeds]
        pos0 = [s.draft_ctx for s in batch]
        for t in range(steps):
            toks = np.zeros((width,), np.int32)
            ctxs = np.zeros((width,), np.int32)
            for i, seq in enumerate(batch):
                hi = min(t, len(cur[i]) - 1)
                toks[i] = cur[i][hi] if t < len(cur[i]) else cur[i][-1]
                ctxs[i] = min(pos0[i] + t, seq.ctx_len + gamma)
            dargs = (jnp.asarray(toks), jnp.asarray(tables),
                     jnp.asarray(ctxs))
            if t == 0:
                self._sent_draft.observe_tree(
                    "serving.draft", dargs, donate=self._donated,
                    where="serving.draft")
            out, *dpools = self._draft_decode_fn(
                dargs[0], *self._draft_cache.pools, dargs[1], dargs[2])
            self._draft_cache.swap(*dpools)
            out = np.asarray(out)
            for i in range(len(batch)):
                catchup = len(feeds[i]) - 1
                if t >= catchup and len(proposals[i]) < gamma:
                    proposals[i].append(int(out[i]))
                    cur[i].append(int(out[i]))
        return proposals

    def _spec_iteration(self, batch: List[Sequence]) -> List[Sequence]:
        """One speculative iteration: draft gamma proposals per resident
        sequence, verify the whole batch in ONE decode-gamma ``extend``
        dispatch, and commit each row's accepted prefix plus the
        target's own token at the first mismatch (1..gamma+1 tokens) —
        exactly the target's greedy stream, drafts or no drafts."""
        gamma = self.spec_gamma
        L = gamma + 1
        rows = len(batch)
        width = self.decode_buckets.fit(rows)
        m_blocks = self.max_blocks_per_seq
        with self._acted_span("serve/decode", rows=rows, width=width,
                              gamma=gamma) as sp:
            with trace.span("serve/decode/build"):
                tables = np.full((width, m_blocks), NULL_BLOCK, np.int32)
                for i, seq in enumerate(batch):
                    tables[i, :len(seq.block_ids)] = seq.block_ids
            with trace.span("serve/decode/draft") as draft:
                proposals = self._draft_proposals(batch, width, tables)
            with trace.span("serve/decode/build"):
                tokens = np.zeros((width, L), np.int32)
                lens = np.zeros((width,), np.int32)
                n_real = np.zeros((width,), np.int32)
                for i, seq in enumerate(batch):
                    fed = [seq.out_tokens[-1]] + proposals[i]
                    tokens[i, :len(fed)] = fed
                    lens[i] = seq.ctx_len
                    n_real[i] = len(fed)
                args = (jnp.asarray(tokens), *self.cache.arrays,
                        jnp.asarray(tables), jnp.asarray(lens),
                        jnp.asarray(n_real))
            with trace.span("serve/decode/checks"):
                self._maybe_lint()
                for i, seq in enumerate(batch):
                    self._assert_cow(self._write_span_ids(
                        seq, seq.ctx_len, int(n_real[i])))
                new = self._sent_verify.observe_tree(
                    "serving.verify", self._undonated(args),
                    donate=self._donated, where="serving.verify")
            with trace.span("serve/decode/launch"):
                out, *pools = _dispatch(self._verify_fn, args, new)
            with self._acted_span("serve/decode/wait") as wait:
                out = np.asarray(out)
            with trace.span("serve/decode/commit"):
                self.cache.swap(*pools)
                _fault_fire("serve.mid_decode")
                _account(draft.t0_ns, draft.end_ns, "draft", batch)
                if draft.end_ns:
                    _account(draft.end_ns, wait.end_ns, "verify", batch)
                self._decode_done(sp.t0_ns, wait)
                self._kv_count(lens)
                finished = self._spec_commit(batch, proposals, out)
        return finished

    def _spec_commit(self, batch: List[Sequence],
                     proposals: List[List[int]], out) -> List[Sequence]:
        gamma = self.spec_gamma
        self.spec_stats["iterations"] += 1
        now_ns = time.perf_counter_ns() if trace.enabled() else 0
        finished: List[Sequence] = []
        for i, seq in enumerate(batch):
            props = proposals[i]
            o = out[i]
            accepted = 0
            while accepted < len(props) and \
                    props[accepted] == int(o[accepted]):
                accepted += 1
            committed = [int(props[j]) for j in range(accepted)]
            committed.append(int(o[accepted]))
            self.spec_stats["proposed"] += len(props)
            self.spec_stats["accepted"] += accepted
            self._accept_lens.append(accepted)
            metrics.histogram(
                "serving.spec_accept_len",
                "draft tokens accepted per speculative iteration"
            ).observe(accepted)
            ctx0 = seq.ctx_len
            done = False
            for tok in committed:
                seq.out_tokens.append(tok)
                if now_ns:
                    seq.token_t_ns.append(now_ns)
                seq.ctx_len += 1
                if seq.is_finished_by(tok):
                    done = True
                    break
            if isinstance(self.drafter, ModelDrafter):
                # drafter KV is valid through the accepted prefix it
                # fed (t0 + the accepted proposals it chained); the
                # fallback token's KV is next round's catch-up feed
                seq.draft_ctx = min(ctx0 + 1 + min(accepted, gamma - 1)
                                    if gamma > 1 else ctx0 + 1,
                                    seq.ctx_len)
            if done:
                finished.append(seq)
        for seq in finished:
            self._finish(seq)
        return finished

    def record_spec_tuning(self) -> Optional[int]:
        """Persist the accepted-length-derived gamma for this target/
        drafter pair into the kernel autotune cache (consumed by
        ``FLAGS_serve_speculative=-1``). Returns the stored gamma."""
        if not self.spec_gamma or not self._accept_lens:
            return None
        from .speculative import tune_gamma
        return tune_gamma(self._spec_desc[0], self._spec_desc[1],
                          self._accept_lens)

    def _finish(self, seq: Sequence) -> None:
        with trace.span("serve/finish", rid=seq.rid) as sp:
            self.sched.finish(seq)
            self._free_seq_blocks(seq)
            out = seq.full_output()
            seq.output = out
            # Acknowledge BEFORE detokenize/record: once the journal holds
            # the done record (fsynced), a relaunch will not replay this
            # request.
            if self.journal is not None:
                self.journal.done(seq.rid, seq.out_tokens)
            if self.detokenizer is not None:
                seq.text = self.detokenizer(out)
        _account(sp.t0_ns, sp.end_ns, "detokenize", (seq,))
        end = time.perf_counter()
        total_ms = (end - seq.t_submit) * 1e3
        ttft_ms = ((seq.t_first_token - seq.t_submit) * 1e3
                   if seq.t_first_token is not None else None)
        request_timeline.current().record(
            rid=seq.rid, prompt_tokens=seq.prompt_len,
            new_tokens=seq.n_generated,
            phases_ms={k: v * 1e3 for k, v in seq.phase_s.items()},
            total_ms=total_ms, ttft_ms=ttft_ms,
            preemptions=seq.preemptions, outcome="ok",
            deadline_ms=(None if seq.request.deadline_s is None
                         else seq.request.deadline_s * 1e3),
            **_commit_stamps(seq))

    # ------------------------------------------------------------------
    # Driving loop
    # ------------------------------------------------------------------

    def step(self) -> List[Sequence]:
        """One scheduler iteration: expire deadlines, consult the shed
        policy, admit whatever fits (prefill / restore at token
        granularity), run one prefill chunk under the chunked budget, top up
        decode blocks (preempting under pressure), launch the next decode
        iteration, then take the tokens of the one the last step launched.
        The decode program runs while this step returns and the next one
        admits, builds and launches, so a row's token is seen a step after
        the launch that computed it, and a drained engine needs one step
        more than it has tokens to give. Returns every sequence that reached
        a terminal state this iteration — FINISHED, and also EXPIRED / SHED /
        FAILED retirements."""
        n0 = len(self.sched.finished)
        with trace.span("serve/step", iteration=self.n_iterations) as root:
            if root:        # the queue lengths only for a span that records
                root.set(running=len(self.sched.running),
                         waiting=len(self.sched.waiting))
            with trace.span("serve/expire_shed"):
                self._expire_deadlines()
                self._apply_shed_policy()
                self._enforce_degraded_width()
            with trace.span("serve/admit") as sp:
                admitted = 0
                while self._try_admit():
                    admitted += 1
                sp.set(admitted=admitted)
            if self.chunk_tokens:
                with trace.span("serve/chunk"):
                    self._chunk_iteration()
            with trace.span("serve/ensure_blocks"):
                self._ensure_decode_blocks()
            self._decode_iteration()
            with trace.span("serve/gauges"):
                self._gauges()
                self.n_iterations += 1
                fleet_live.note_progress(self.n_iterations)
        return self.sched.finished[n0:]

    def serve(self, requests: Seq[Request],
              respect_arrivals: bool = False
              ) -> Dict[str, Union[Sequence, Rejected]]:
        """Drive the full trace to completion; returns rid -> Sequence
        (with ``.output`` / ``.text`` — check ``.status`` for the
        EXPIRED/SHED/FAILED endings) or the :class:`Rejected` answer for
        requests bounded admission refused. ``respect_arrivals`` replays
        each request's ``arrival_s`` offset instead of submitting
        everything up front."""
        order = sorted(requests, key=lambda r: r.arrival_s) \
            if respect_arrivals else list(requests)
        t0 = time.perf_counter()
        idx = 0
        done: Dict[str, Union[Sequence, Rejected]] = {}
        while idx < len(order) or self.sched.n_pending:
            now = time.perf_counter() - t0
            while idx < len(order) and (
                    not respect_arrivals or order[idx].arrival_s <= now):
                res = self.submit(order[idx])
                if isinstance(res, Rejected):
                    done[res.rid] = res
                idx += 1
            if not self.sched.n_pending:
                if idx < len(order) and respect_arrivals:
                    time.sleep(
                        max(0.0, order[idx].arrival_s -
                            (time.perf_counter() - t0)))
                continue
            for seq in self.step():
                done[seq.rid] = seq
        self.sched.assert_idle()
        return done

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    def compile_report(self) -> Dict[str, Any]:
        """Distinct executable signatures dispatched vs the bucket
        budget — the '≤ n_buckets compilations, O001 silent' check."""
        n_pre = len(self._sent_prefill._seen.get("serving.prefill", ()))
        n_dec = len(self._sent_decode._seen.get("serving.decode", ()))
        n_ext = (len(self._sent_chunk._seen.get("serving.extend", ()))
                 if self._sent_chunk is not None else 0)
        n_ver = (len(self._sent_verify._seen.get("serving.verify", ()))
                 if self._sent_verify is not None else 0)
        ext_budget = (self._sent_chunk.threshold
                      if self._sent_chunk is not None else 0)
        ver_budget = (self._sent_verify.threshold
                      if self._sent_verify is not None else 0)
        return {
            "prefill_signatures": n_pre,
            "decode_signatures": n_dec,
            "extend_signatures": n_ext,
            "verify_signatures": n_ver,
            "budget": (len(self.prefill_buckets) +
                       len(self.decode_buckets) + ext_budget +
                       ver_budget),
            "prefill_buckets": self.prefill_buckets.sizes,
            "decode_buckets": self.decode_buckets.sizes,
            "within_budget": (n_pre <= len(self.prefill_buckets) and
                              n_dec <= len(self.decode_buckets) and
                              n_ext <= ext_budget and
                              n_ver <= ver_budget),
            "o001_fired": bool(
                self._sent_prefill.diagnostics or
                self._sent_decode.diagnostics or
                (self._sent_chunk is not None and
                 self._sent_chunk.diagnostics) or
                (self._sent_verify is not None and
                 self._sent_verify.diagnostics) or
                (self._sent_draft is not None and
                 self._sent_draft.diagnostics)),
        }

    def prefix_report(self) -> Dict[str, Any]:
        """Prefix-sharing effectiveness: hit rate, live tree size, and
        the pool-pressure headline (peak blocks in use)."""
        rep = {
            "enabled": self.prefix is not None,
            "peak_blocks_used": self.peak_blocks_used,
            "peak_live_blocks": self.peak_live_blocks,
            "blocks_shared_now": self.cache.allocator.n_shared,
        }
        if self.prefix is not None:
            rep.update({
                "hit_rate": round(self.prefix.hit_rate(), 4),
                "hit_tokens": self.prefix.hit_tokens,
                "lookup_tokens": self.prefix.lookup_tokens,
                "tree_nodes": self.prefix.n_nodes,
                "device_blocks_held": len(self.prefix.device_block_ids()),
            })
        return rep

    def spec_report(self) -> Dict[str, Any]:
        """Speculative-decoding effectiveness: acceptance and the mean
        committed tokens per verify dispatch."""
        it = self.spec_stats["iterations"]
        prop = self.spec_stats["proposed"]
        acc = self.spec_stats["accepted"]
        rows = len(self._accept_lens)   # per-sequence verify samples
        return {
            "enabled": bool(self.spec_gamma),
            "gamma": self.spec_gamma,
            "drafter": getattr(self.drafter, "kind", None),
            "iterations": it,
            "proposed": prop,
            "accepted": acc,
            "accept_rate": round(acc / prop, 4) if prop else 0.0,
            "mean_accept_len": round(acc / rows, 4) if rows else 0.0,
            "tokens_per_verify": round((acc + rows) / rows, 4)
            if rows else 0.0,
        }
