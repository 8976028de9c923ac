"""Serving-tier tests: paged allocator invariants, spill/restore bitwise
round trip, deterministic block assignment, continuous-batching engine
vs model.generate (token-exact), bucketed-compile budget, request
timeline, the declared serving plan through plan_check, and the
resilience tier (ISSUE 9): deadlines, bounded admission, load shedding,
per-request failure isolation, cancellation hygiene, and the
exactly-once request journal.

Everything runs on the CPU mesh with micro GPT configs — this file is
the tier-1-safe quick serving gate (the subprocess kill drill is in
test_serve_drill.py).
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics, request_timeline
from paddle_tpu.serving import (BlockAllocator, BucketSet, ModelDrafter,
                                NGramDrafter, NULL_BLOCK, PagedKVCache,
                                PrefixCache, Rejected, Request,
                                RequestJournal, Sequence, ServingEngine,
                                ShedPolicy, SpillError, Status,
                                pick_gamma, pow2_buckets, tune_gamma)
from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny


def micro_model(**over):
    paddle.seed(7)
    cfg = gpt_tiny(**{**dict(vocab_size=128, hidden_size=48, num_layers=2,
                             num_heads=4, max_position_embeddings=64),
                      **over})
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def ragged_requests(n, vocab=128, lo=3, hi=14, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt_ids=rng.integers(0, vocab,
                                            int(rng.integers(lo, hi + 1))),
                    max_new_tokens=max_new)
            for i in range(n)]


def ref_generate(model, req):
    return np.asarray(model.generate(jnp.asarray(req.prompt_ids[None]),
                                     max_new_tokens=req.max_new_tokens))[0]


# ---------------------------------------------------------------------------
# Allocator + buckets
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_lowest_id_first_and_reuse(self):
        a = BlockAllocator(8)
        assert a.alloc(3) == [1, 2, 3]          # block 0 reserved
        assert a.alloc(2) == [4, 5]
        a.free([2, 4])
        # freed blocks come back lowest-first, before untouched ids
        assert a.alloc(3) == [2, 4, 6]
        assert a.n_free == 1 and a.n_used == 6

    def test_all_or_nothing(self):
        a = BlockAllocator(4)                    # 3 usable
        assert a.alloc(4) is None
        assert a.n_free == 3                     # nothing partially granted
        assert a.alloc(3) == [1, 2, 3]
        assert a.alloc(1) is None

    def test_double_free_and_reserved(self):
        a = BlockAllocator(4)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(ValueError, match="double-free"):
            a.free([ids[0]])
        with pytest.raises(ValueError, match="reserved"):
            a.free([NULL_BLOCK])

    def test_too_small_pool_rejected(self):
        with pytest.raises(ValueError, match="null sink"):
            BlockAllocator(1)


class TestBuckets:
    def test_fixed_set_fit(self):
        b = BucketSet([4, 8, 32])
        assert b.fit(1) == 4 and b.fit(8) == 8 and b.fit(9) == 32
        with pytest.raises(ValueError, match="exceeds the largest"):
            b.fit(33)

    def test_grow_ladder(self):
        b = BucketSet([1], grow=True)
        assert [b.fit(n) for n in (3, 45, 7, 64)] == [4, 64, 8, 64]
        assert b.sizes == [1, 4, 8, 64]

    def test_pow2_buckets(self):
        assert pow2_buckets(1, 8) == (1, 2, 4, 8)
        assert pow2_buckets(4, 33) == (4, 8, 16, 32, 64)


# ---------------------------------------------------------------------------
# Paged cache: spill / restore round trip
# ---------------------------------------------------------------------------

class TestPagedCache:
    def test_spill_restore_bitwise(self):
        cache = PagedKVCache(n_layers=2, num_blocks=8, block_size=4,
                             kv_heads=2, head_dim=8)
        ids = cache.allocator.alloc(3)
        rng = np.random.default_rng(0)
        k_vals = rng.standard_normal((2, 3, 4, 2, 8)).astype(np.float32)
        v_vals = rng.standard_normal((2, 3, 4, 2, 8)).astype(np.float32)
        from paddle_tpu.serving.paged_cache import _scatter_blocks
        cache.k = _scatter_blocks(cache.k, jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(k_vals))
        cache.v = _scatter_blocks(cache.v, jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(v_vals))
        host_kv = cache.spill(ids)
        assert cache.allocator.n_used == 0       # blocks reusable
        # restore into DIFFERENT blocks: ids are rewritten, bytes are not
        new_ids = cache.allocator.alloc(3)
        assert new_ids == ids                    # min-id determinism
        cache.allocator.free(new_ids)
        other = cache.allocator.alloc(1)         # shift the free list
        new_ids = cache.allocator.alloc(3)
        assert new_ids != ids
        cache.restore(host_kv, new_ids)
        k_back, v_back = cache.read_blocks(new_ids)
        np.testing.assert_array_equal(k_back, k_vals)
        np.testing.assert_array_equal(v_back, v_vals)
        cache.allocator.free(other + new_ids)

    def test_restore_count_mismatch(self):
        cache = PagedKVCache(1, 4, 2, 1, 4)
        ids = cache.allocator.alloc(2)
        host_kv = cache.spill(ids)
        bad = cache.allocator.alloc(1)
        with pytest.raises(ValueError, match="restore of 2 blocks"):
            cache.restore(host_kv, bad)


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One engine run shared by the e2e assertions (compiles once)."""
    model = micro_model()
    engine = ServingEngine(model, block_size=4, num_blocks=32, max_batch=4)
    requests = ragged_requests(5)
    rt = request_timeline.reset_default()
    results = engine.serve(requests)
    return model, engine, requests, results, rt


class TestEngine:
    def test_outputs_match_generate(self, served):
        model, _, requests, results, _ = served
        for r in requests:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))

    def test_compile_budget_and_o001_silent(self, served):
        _, engine, _, _, _ = served
        rep = engine.compile_report()
        assert rep["within_budget"], rep
        assert not rep["o001_fired"], rep
        assert rep["prefill_signatures"] <= len(rep["prefill_buckets"])
        assert rep["decode_signatures"] <= len(rep["decode_buckets"])

    def test_all_blocks_freed_after_drain(self, served):
        _, engine, _, _, _ = served
        assert engine.cache.allocator.n_used == 0
        engine.sched.assert_idle()

    def test_request_timeline_records(self, served, tmp_path):
        _, _, requests, _, rt = served
        recs = rt.records()
        assert len(recs) == len(requests)
        for rec in recs:
            assert rec["kind"] == "request"
            assert {"queue", "prefill", "decode",
                    "detokenize"} <= set(rec["phases"])
            assert rec["ttft_ms"] <= rec["total_ms"]
        s = rt.summary()
        assert s["requests"] == len(requests)
        assert s["p50_ms"] <= s["p99_ms"]
        assert s["new_tokens"] == sum(r.max_new_tokens for r in requests)
        out = tmp_path / "req.jsonl"
        assert rt.export_jsonl(str(out)) == len(requests)
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        assert lines[0]["kind"] == "request"

    def test_oversize_request_rejected(self, served):
        _, engine, _, _, _ = served
        with pytest.raises(ValueError, match="exceeds"):
            engine.submit(Request(rid="big",
                                  prompt_ids=np.zeros(60, np.int32),
                                  max_new_tokens=10))


class TestPreemption:
    def test_out_of_blocks_spill_restore_exact(self):
        """Capacity pressure forces preemption (spill to the host tier)
        and the resumed sequences still match generate token-exactly —
        the KV round trip is bitwise."""
        model = micro_model(max_position_embeddings=32)
        engine = ServingEngine(model, block_size=4, num_blocks=10,
                               max_batch=4, max_seq_len=32)
        metrics.reset_all()
        requests = ragged_requests(4, lo=8, hi=14, max_new=8, seed=1)
        results = engine.serve(requests)
        assert metrics.counter("serving.preemptions").get() > 0
        assert metrics.counter("serving.kv_spills").get() > 0
        assert metrics.counter("serving.kv_restores").get() > 0
        for r in requests:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        assert engine.cache.allocator.n_used == 0

    def test_deterministic_block_assignment(self):
        """The same seeded request schedule produces the same block
        grants (including across preemptions) on a fresh engine — the
        min-id free list has no hidden state."""
        model = micro_model(max_position_embeddings=32)
        requests = ragged_requests(4, lo=8, hi=14, max_new=8, seed=2)

        def run():
            eng = ServingEngine(model, block_size=4, num_blocks=10,
                                max_batch=4, max_seq_len=32)
            res = eng.serve(requests)
            return {r.rid: (list(res[r.rid].block_log),
                            res[r.rid].preemptions,
                            res[r.rid].output.tolist())
                    for r in requests}

        a, b = run(), run()
        assert a == b
        assert any(-1 in log for log, _, _ in a.values()), \
            "schedule was expected to preempt at least once"


class TestDecodeInFlight:
    """A step launches its decode iteration and the next step takes the
    tokens; a row may leave in between."""

    def _engine(self, model):
        return ServingEngine(model, block_size=4, num_blocks=32, max_batch=4,
                             max_seq_len=32)

    def test_a_step_returns_with_its_decode_launched(self):
        model = micro_model(max_position_embeddings=32)
        engine = self._engine(model)
        req = ragged_requests(1, lo=8, hi=8, max_new=4, seed=5)[0]
        seq = engine.submit(req)
        engine.step()                       # prefill's token, decode launched
        assert len(seq.out_tokens) == 1 and engine._ahead is not None
        engine.step()
        assert len(seq.out_tokens) == 2 and seq.ctx_len == 9
        while engine.sched.n_pending:
            engine.step()
        assert engine._ahead is None
        np.testing.assert_array_equal(seq.output, ref_generate(model, req))

    def test_preempted_in_flight_row_is_recomputed_exactly(self):
        model = micro_model(max_position_embeddings=32)
        engine = self._engine(model)
        requests = ragged_requests(3, lo=7, hi=9, max_new=8, seed=3)
        seqs = [engine.submit(r) for r in requests]
        victim = seqs[1]
        engine.step()
        while victim.ctx_len % 4:           # the write in flight opens a block
            engine.step()
        assert victim in engine._ahead[0]
        n_out = len(victim.out_tokens)
        engine._preempt(victim)             # its next token is in flight
        engine.step()                       # restored at once: room is left
        assert victim.preemptions == 1 and len(victim.out_tokens) == n_out
        while engine.sched.n_pending:
            engine.step()
        for r, seq in zip(requests, seqs):
            np.testing.assert_array_equal(seq.output, ref_generate(model, r))
        assert engine.cache.allocator.n_used == 0

    def test_cancelled_in_flight_row_leaves_the_others_exact(self):
        model = micro_model(max_position_embeddings=32)
        engine = self._engine(model)
        requests = ragged_requests(3, lo=7, hi=9, max_new=8, seed=4)
        seqs = [engine.submit(r) for r in requests]
        for _ in range(2):
            engine.step()
        gone = seqs[0]
        assert gone in engine._ahead[0]
        n_out = len(gone.out_tokens)
        engine._cancel(gone, Status.EXPIRED, "test: cancelled in flight")
        while engine.sched.n_pending:
            engine.step()
        assert gone.status is Status.EXPIRED and len(gone.out_tokens) == n_out
        for r, seq in zip(requests[1:], seqs[1:]):
            np.testing.assert_array_equal(seq.output, ref_generate(model, r))
        assert engine.cache.allocator.n_used == 0


class _Launches:
    """Wraps an engine's decode program and keeps what each launch was
    handed behind the pools: contexts, ``prev`` and the row map ``src``."""

    def __init__(self, engine):
        self.src, self.lens, self.prev = [], [], []
        inner = engine._decode_fn

        def recording(*args):
            self.lens.append(np.asarray(args[-3]))
            self.prev.append(args[-2])
            self.src.append(np.asarray(args[-1]))
            return inner(*args)
        engine._decode_fn = recording


def _fed(kind):
    return metrics.counter("serving.decode_rows").labels(fed=kind).get()


def _decode_spans(recs):
    """The ring's ``serve/decode`` parts in order: (step iteration, name,
    t0_ns, end_ns)."""
    by_id = {r["id"]: r for r in recs}
    out = []
    for r in recs:
        if r["name"].startswith(("serve/decode/", "serve/prefill/")):
            top = r
            while top["parent"] is not None:
                top = by_id[top["parent"]]
            out.append((top["attrs"]["iteration"], r["name"], r["t0_ns"],
                        r["t0_ns"] + r["dur_ns"]))
    return sorted(out, key=lambda x: x[2])


class TestTokensFedOnTheDevice:
    """``step()`` launches decode k before it takes decode k-1's tokens: a
    row of k-1 reads its token from that launch's result on the device."""

    def _engine(self, model, **kw):
        kw.setdefault("decode_buckets", [4])
        return ServingEngine(model, block_size=4, num_blocks=48, max_batch=4,
                             max_seq_len=32, **kw)

    def test_ragged_lengths_and_joiners_match_generate(self):
        """Rows finish at different lengths and requests join mid-stream, so
        launches mix device-fed and host-fed rows and a row's place moves
        (``src`` is not the identity); every output equals ``generate``."""
        model = micro_model(max_position_embeddings=32)
        engine = self._engine(model)
        seen = _Launches(engine)
        metrics.reset_all()
        rng = np.random.default_rng(11)
        requests = [Request(rid=f"m{i}",
                            prompt_ids=rng.integers(0, 128,
                                                    int(rng.integers(3, 12))),
                            max_new_tokens=int(n))
                    for i, n in enumerate([3, 9, 5, 12, 2, 7, 4, 10, 6])]
        seqs = [engine.submit(r) for r in requests[:4]]
        later = list(requests[4:])
        while engine.sched.n_pending or later:
            engine.step()
            if later and engine.n_iterations % 2 == 0:
                seqs.append(engine.submit(later.pop(0)))
        for r, seq in zip(requests, seqs):
            np.testing.assert_array_equal(seq.output, ref_generate(model, r))
        assert engine.cache.allocator.n_used == 0
        # some launch mixes the two feeds, and some row changed its place
        assert any((s >= 0).any() and (s[:int((l > 0).sum())] < 0).any()
                   for s, l in zip(seen.src, seen.lens))
        assert any(((s >= 0) & (s != np.arange(4))).any() for s in seen.src)
        n_rows = sum(len(q.out_tokens) - 1 for q in seqs)
        assert _fed("device") + _fed("host") == n_rows
        assert _fed("device") > _fed("host") > 0 and _fed("dropped") == 0
        # a device-fed row stands one token further than the host had
        # committed, and is handed the launch in flight as it stands
        for lens, src, prev in zip(seen.lens[1:], seen.src[1:],
                                   seen.prev[1:]):
            assert prev.shape == (4,) and (lens[src >= 0] > 0).all()
        rep = engine.compile_report()
        assert rep["decode_signatures"] == 1 and not rep["o001_fired"], rep

    @pytest.mark.parametrize("prefix_cache", [False, True])
    def test_end_of_sequence_token_costs_one_dropped_row(self, prefix_cache):
        """A token that ends a request cannot be seen ahead: the row runs
        once too often, that token is dropped, the output ends at the
        end-of-sequence token and the other rows stay exact. With the prefix
        cache on, the write of the row that ran on passes D005's assertion
        (it lands in a block the sequence held at the launch)."""
        model = micro_model(max_position_embeddings=32)
        requests = ragged_requests(3, lo=7, hi=9, max_new=10, seed=7)
        ref = ref_generate(model, requests[1])
        n = requests[1].prompt_ids.size
        new = ref[n:].tolist()
        # the first token that is new at its place, past the prefill's and
        # short of the length: it fires in a decode launch
        at = next(j for j in range(1, len(new) - 2) if new[j] not in new[:j])
        requests[1].eos_token_id = int(new[at])
        engine = self._engine(model, prefix_cache=prefix_cache)
        metrics.reset_all()
        seqs = [engine.submit(r) for r in requests]
        while engine.sched.n_pending:
            engine.step()
        assert seqs[1].status is Status.FINISHED
        np.testing.assert_array_equal(seqs[1].output, ref[:n + at + 1])
        for i in (0, 2):
            np.testing.assert_array_equal(seqs[i].output,
                                          ref_generate(model, requests[i]))
        assert _fed("dropped") == 1
        n_rows = sum(len(q.out_tokens) - 1 for q in seqs)
        assert _fed("device") + _fed("host") == n_rows + 1
        if prefix_cache:
            assert_allocator_pristine_shared(engine)
        else:
            assert engine.cache.allocator.n_used == 0

    @pytest.mark.parametrize("committed", [False, True])
    def test_nothing_compiles_after_a_warm_up_of_two_token_requests(
            self, committed):
        """The benchmark warms an engine with requests of two tokens: one
        prefill and one decode whose rows the host feeds. The launches after
        it, fed on the device from a real result, run the same compiled
        program, whether the weights (and with them every result) are
        committed to their device or not."""
        import jax
        from jax._src import monitoring
        model = micro_model(max_position_embeddings=32)
        if committed:
            from paddle_tpu.framework.functional import get_params, set_params
            set_params(model, {k: jax.device_put(v, jax.devices()[0])
                               for k, v in get_params(model).items()})
        engine = ServingEngine(model, block_size=4, num_blocks=48,
                               max_batch=4, max_seq_len=32,
                               prefill_buckets=[16], decode_buckets=[4])
        rng = np.random.default_rng(0)
        # as ``warm_engine`` does; both prompts in the one prefill bucket,
        # whose program sees a fresh pool once and a program's result after
        for i, length in enumerate([2, 9]):
            engine.submit(Request(rid=f"warm{i}",
                                  prompt_ids=rng.integers(0, 128, length),
                                  max_new_tokens=2))
        while engine.sched.n_pending:
            engine.step()
        compiled = []

        def on(event, duration, **_):
            if "backend_compile" in event:
                compiled.append(event)
        monitoring.register_event_duration_secs_listener(on)
        try:
            requests = ragged_requests(9, lo=3, hi=14, max_new=9, seed=8)
            for r, n in zip(requests, [9, 4, 7, 2, 9, 5, 3, 8, 6]):
                r.max_new_tokens = n
            results = engine.serve(requests)
        finally:
            monitoring.unregister_event_duration_listener(on)
        assert compiled == []
        for r in requests:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        rep = engine.compile_report()
        assert rep["within_budget"] and not rep["o001_fired"], rep

    def test_launch_goes_out_before_the_wait_unless_the_step_admits(self):
        """The ring: in a step that admits nothing, ``serve/decode/launch``
        of iteration k lies before the end of ``serve/decode/wait`` of k-1;
        in a step that admits, it lies after the prefill's commit (and still
        before that wait)."""
        from paddle_tpu.observability import trace
        model = micro_model(max_position_embeddings=32)
        engine = self._engine(model)
        requests = ragged_requests(3, lo=7, hi=9, max_new=8, seed=3)
        trace.clear()
        engine.submit(requests[0])
        engine.submit(requests[1])
        for _ in range(3):
            engine.step()
        engine.submit(requests[2])
        joined = engine.n_iterations        # the step that admits it
        while engine.sched.n_pending:
            engine.step()
        spans = _decode_spans(trace.spans())
        by_step = {}
        for it, name, t0, end in spans:
            by_step.setdefault(it, []).append((name, t0, end))
        quiet = admitting = 0
        for it, parts in by_step.items():
            names = [p[0] for p in parts]
            if "serve/decode/launch" not in names or \
                    "serve/decode/wait" not in names:
                continue
            launch = parts[names.index("serve/decode/launch")]
            wait = parts[names.index("serve/decode/wait")]
            assert launch[2] <= wait[2]     # launched before the wait ends
            if "serve/prefill/commit" in names:
                commit = parts[names.index("serve/prefill/commit")]
                assert commit[2] <= launch[1]
                admitting += 1
            else:
                assert names.index("serve/decode/launch") < \
                    names.index("serve/decode/wait")
                quiet += 1
        assert quiet > 0 and admitting > 0
        assert "serve/prefill/commit" in [p[0] for p in by_step[joined]]

    def test_a_launch_after_a_bucket_change_is_fed_by_the_host(self):
        """Two decode buckets: where the next launch falls into another
        bucket than the one in flight, the tokens are taken first and every
        row of that launch is fed by the host; outputs stay exact and each
        bucket compiles once."""
        model = micro_model(max_position_embeddings=32)
        engine = self._engine(model, decode_buckets=[2, 4])
        seen = _Launches(engine)
        metrics.reset_all()
        requests = ragged_requests(4, lo=5, hi=9, max_new=10, seed=9)
        for r, n in zip(requests, [10, 3, 6, 8]):
            r.max_new_tokens = n
        seqs = [engine.submit(r) for r in requests]
        while engine.sched.n_pending:
            engine.step()
        for r, seq in zip(requests, seqs):
            np.testing.assert_array_equal(seq.output, ref_generate(model, r))
        widths = [len(s) for s in seen.src]
        assert set(widths) == {2, 4}
        changes = [i for i in range(1, len(widths))
                   if widths[i] != widths[i - 1]]
        assert changes
        for i in changes:
            assert (seen.src[i] == -1).all()
        # and between changes the rows read on the device
        assert any((s >= 0).any() for s in seen.src)
        for src, prev in zip(seen.src, seen.prev):
            assert prev.shape == src.shape
        assert _fed("dropped") == 0
        rep = engine.compile_report()
        assert rep["decode_signatures"] == 2 and not rep["o001_fired"], rep


class TestGQA:
    def test_grouped_kv_heads_match_generate(self):
        model = micro_model(num_heads=4, num_kv_heads=2)
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=4)
        requests = ragged_requests(3, max_new=4, seed=3)
        results = engine.serve(requests)
        for r in requests:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))


# ---------------------------------------------------------------------------
# Resilience tier (ISSUE 9): deadlines, admission, shedding, isolation
# ---------------------------------------------------------------------------

def assert_allocator_pristine(engine):
    """Cancellation hygiene: zero leaked blocks AND zero reserved-id
    drift — the pool is indistinguishable from a fresh allocator."""
    alloc = engine.cache.allocator
    assert alloc.n_used == 0
    assert alloc._reserved == frozenset({NULL_BLOCK})
    n = alloc.num_blocks - 1
    got = alloc.alloc(n)
    assert got == list(range(1, n + 1)), got   # min-id list fully intact
    alloc.free(got)


class TestDeadlines:
    def test_expired_requests_cancelled_clean(self):
        model = micro_model()
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=4)
        metrics.reset_all()
        rt = request_timeline.reset_default()
        reqs = ragged_requests(3)
        for r in reqs:
            r.deadline_s = 1e-9          # unattainable: expire at step 1
        results = engine.serve(reqs)
        for r in reqs:
            assert results[r.rid].status is Status.EXPIRED
            assert "deadline" in results[r.rid].error
        assert metrics.counter("serving.expired").get() == len(reqs)
        assert_allocator_pristine(engine)
        engine.sched.assert_idle()
        recs = rt.records()
        assert all(rec["outcome"] == "expired" and
                   rec["deadline_met"] is False for rec in recs)
        s = rt.summary()
        assert s["slo_attainment_pct"] == 0.0
        assert s["outcomes"] == {"expired": 3}

    def test_generous_deadline_met_and_recorded(self):
        model = micro_model()
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=4)
        rt = request_timeline.reset_default()
        reqs = ragged_requests(2)
        for r in reqs:
            r.deadline_s = 300.0
        results = engine.serve(reqs)
        for r in reqs:
            assert results[r.rid].status is Status.FINISHED
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        s = rt.summary()
        assert s["slo_attainment_pct"] == 100.0
        assert all(rec["deadline_met"] for rec in rt.records())

    def test_preemption_keeps_true_submit_time(self):
        """Satellite regression: _preempt must NOT rewrite t_submit —
        end-to-end latency and the deadline check measure from true
        submission, the queue phase restarts from t_requeue."""
        model = micro_model(max_position_embeddings=32)
        engine = ServingEngine(model, block_size=4, num_blocks=10,
                               max_batch=4, max_seq_len=32)
        reqs = ragged_requests(4, lo=8, hi=14, max_new=8, seed=1)
        results = engine.serve(reqs)
        preempted = [results[r.rid] for r in reqs
                     if results[r.rid].preemptions > 0]
        assert preempted, "trace was expected to preempt"
        for seq in preempted:
            assert seq.t_requeue is not None
            assert seq.t_requeue > seq.t_submit
            # TTFT can only be measured against the true arrival
            assert seq.t_first_token > seq.t_submit


class TestBoundedAdmission:
    def test_queue_full_returns_typed_rejection(self):
        model = micro_model()
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=2, max_waiting=2)
        metrics.reset_all()
        rt = request_timeline.reset_default()
        reqs = ragged_requests(6)
        results = engine.serve(reqs)
        rejected = {rid: r for rid, r in results.items()
                    if isinstance(r, Rejected)}
        served = {rid: r for rid, r in results.items()
                  if not isinstance(r, Rejected)}
        assert len(rejected) == 4 and len(served) == 2  # closed-loop trace
        for rej in rejected.values():
            assert rej.reason == "queue_full"
            assert not rej                      # falsy by contract
        for r in reqs:
            if r.rid in served:
                np.testing.assert_array_equal(served[r.rid].output,
                                              ref_generate(model, r))
        assert metrics.counter("serving.rejected").get() == 4
        assert engine.rejections == list(rejected.values())
        assert_allocator_pristine(engine)
        s = rt.summary()
        assert s["outcomes"] == {"ok": 2, "rejected": 4}
        assert s["shed_rate"] == pytest.approx(4 / 6, abs=1e-3)

    def test_preempted_resident_not_counted_against_queue(self):
        """A preempted sequence re-queues at the front without consuming
        a max_waiting slot — backpressure applies to NEW work only."""
        from paddle_tpu.serving.scheduler import FCFSScheduler, Sequence
        sched = FCFSScheduler(2, max_waiting=1)
        a = Sequence(Request(rid="a", prompt_ids=np.ones(4, np.int32),
                             max_new_tokens=2))
        sched.submit(a)
        sched.admit(a)
        sched.preempt(a)
        assert a.status is Status.PREEMPTED and len(sched.waiting) == 1
        assert sched.can_accept()       # the preempted one doesn't count

    def test_spill_budget_rejects(self):
        model = micro_model(max_position_embeddings=32)
        engine = ServingEngine(model, block_size=4, num_blocks=10,
                               max_batch=4, max_seq_len=32,
                               max_spilled_bytes=0)
        # force some spill state, then submit against the zero budget
        reqs = ragged_requests(4, lo=8, hi=14, max_new=8, seed=1)
        for r in reqs:
            engine.submit(r)
        while not engine.sched.running or not any(
                s.host_kv is not None for s in engine.sched.waiting):
            if not engine.sched.n_pending:
                pytest.skip("trace no longer preempts")
            engine.step()
        late = Request(rid="late", prompt_ids=np.ones(4, np.int32),
                       max_new_tokens=2)
        rej = engine.submit(late)
        assert isinstance(rej, Rejected) and rej.reason == "spill_budget"
        while engine.sched.n_pending:
            engine.step()
        assert_allocator_pristine(engine)


class TestLoadShedding:
    def test_sheds_lowest_priority_youngest_first(self):
        model = micro_model()
        engine = ServingEngine(
            model, block_size=4, num_blocks=32, max_batch=4,
            shed_policy=ShedPolicy(min_free_block_frac=2.0))  # always on
        metrics.reset_all()
        rng = np.random.default_rng(0)
        reqs = [Request(rid=f"r{i}", prompt_ids=rng.integers(0, 128, 6),
                        max_new_tokens=3, priority=(1 if i == 0 else 0))
                for i in range(4)]
        results = engine.serve(reqs)
        assert all(results[r.rid].status is Status.SHED for r in reqs)
        # shed order: lowest priority first, youngest within the class;
        # the priority-1 request r0 survives longest
        order = [s.rid for s in engine.sched.finished]
        assert order == ["r3", "r2", "r1", "r0"]
        assert metrics.counter("serving.shed").get() == 4
        assert engine.mode == "shedding"
        assert_allocator_pristine(engine)

    def test_degraded_mode_shrinks_decode_bucket(self):
        """p99-triggered degraded mode: the active decode bucket drops a
        rung (youngest residents preempted through the normal LIFO spill
        path) and the survivors still match generate token-exactly."""
        model = micro_model(max_position_embeddings=32)
        pol = ShedPolicy(max_p99_decode_ms=1e-6, degrade=True)
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=4, max_seq_len=32,
                               shed_policy=pol)
        metrics.reset_all()
        reqs = ragged_requests(4, lo=4, hi=8, max_new=6, seed=5)
        results = engine.serve(reqs)
        finished = [r for r in reqs
                    if results[r.rid].status is Status.FINISHED]
        shed = [r for r in reqs if results[r.rid].status is Status.SHED]
        assert finished and shed          # degraded, not dead
        for r in finished:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        assert engine.mode == "degraded"
        assert metrics.counter("serving.overload_iterations").get() > 0
        assert_allocator_pristine(engine)

    def test_healthy_policy_changes_nothing(self):
        """An armed-but-never-tripped policy is bitwise inert: same
        outputs, same block log as the bare engine."""
        model = micro_model()
        reqs = ragged_requests(3)

        def run(policy):
            eng = ServingEngine(model, block_size=4, num_blocks=32,
                                max_batch=4, shed_policy=policy)
            res = eng.serve(reqs)
            return {r.rid: (res[r.rid].output.tolist(),
                            res[r.rid].block_log) for r in reqs}

        assert run(None) == run(ShedPolicy(min_free_block_frac=0.0))


class TestFailureIsolation:
    def test_pool_exhaustion_fails_request_not_engine(self):
        """The acceptance-criterion scenario: a request that outgrows the
        pool mid-decode ends FAILED (F003) and every other request is
        served token-exact — OutOfBlocksError never crosses the loop."""
        model = micro_model(max_position_embeddings=64)
        engine = ServingEngine(model, block_size=4, num_blocks=6,
                               max_batch=2, validate_capacity=False)
        metrics.reset_all()
        rng = np.random.default_rng(2)
        grower = Request(rid="grower", prompt_ids=rng.integers(0, 128, 16),
                         max_new_tokens=8)    # 24 tokens > 5 usable blocks
        small = Request(rid="small", prompt_ids=rng.integers(0, 128, 4),
                        max_new_tokens=3)
        results = engine.serve([grower, small])
        assert results["grower"].status is Status.FAILED
        assert "nothing left to preempt" in results["grower"].error
        np.testing.assert_array_equal(results["small"].output,
                                      ref_generate(model, small))
        assert metrics.counter("serving.failed").get() == 1
        assert [d.rule for d in engine.diagnostics] == ["F003"]
        assert_allocator_pristine(engine)

    def test_impossible_admission_fails_request(self):
        """A prompt the idle pool can never grant fails at admission
        instead of deadlocking the serve loop."""
        model = micro_model(max_position_embeddings=64)
        engine = ServingEngine(model, block_size=4, num_blocks=4,
                               max_batch=2, validate_capacity=False)
        rng = np.random.default_rng(3)
        big = Request(rid="big", prompt_ids=rng.integers(0, 128, 20),
                      max_new_tokens=4)      # needs 5 blocks, pool has 3
        small = Request(rid="small", prompt_ids=rng.integers(0, 128, 4),
                        max_new_tokens=2)
        results = engine.serve([big, small])
        assert results["big"].status is Status.FAILED
        assert results["small"].status is Status.FINISHED
        assert_allocator_pristine(engine)

    def test_spill_error_isolated_to_victim(self):
        """An injected host-spill failure (the serve.mid_spill seam —
        same mechanism the drill SIGKILLs through) fails only the spill
        victim; everyone else is served token-exact."""
        from paddle_tpu.fault.injection import register_fire_point
        model = micro_model(max_position_embeddings=32)
        engine = ServingEngine(model, block_size=4, num_blocks=10,
                               max_batch=4, max_seq_len=32)
        metrics.reset_all()
        reqs = ragged_requests(4, lo=8, hi=14, max_new=8, seed=1)
        state = {"n": 0}

        def bomb():
            state["n"] += 1
            if state["n"] == 1:
                raise SpillError("injected host allocation failure")

        register_fire_point("serve.mid_spill", bomb)
        try:
            results = engine.serve(reqs)
        finally:
            register_fire_point("serve.mid_spill", None)
        assert state["n"] >= 1, "trace was expected to spill"
        failed = [r for r in reqs if results[r.rid].status is Status.FAILED]
        ok = [r for r in reqs if results[r.rid].status is Status.FINISHED]
        assert len(failed) == 1
        assert "KV spill failed" in results[failed[0].rid].error
        for r in ok:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        assert_allocator_pristine(engine)


class TestRequestJournal:
    def test_exactly_once_round_trip(self, tmp_path):
        model = micro_model()
        path = str(tmp_path / "journal.jsonl")
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=2, journal=RequestJournal(path))
        reqs = ragged_requests(3)
        results = engine.serve(reqs)
        replay = RequestJournal(path)
        rids = [r.rid for r in reqs]
        report = replay.exactly_once_report(rids)
        assert report["exactly_once"] and report["launches"] == 1
        assert replay.pending_rids(rids) == []
        outs = replay.done_outputs()
        for r in reqs:
            prompt = r.prompt_ids.tolist()
            assert prompt + outs[r.rid] == results[r.rid].output.tolist()

    def test_unacknowledged_requests_replay(self, tmp_path):
        """Submitted-but-unacked state (what a mid-decode SIGKILL leaves
        behind) is exactly the replay set; acked requests are not."""
        path = str(tmp_path / "journal.jsonl")
        j = RequestJournal(path)
        j.launch()
        for rid in ("a", "b", "c"):
            j.submitted(Request(rid=rid, prompt_ids=np.ones(4, np.int32),
                                max_new_tokens=2))
        j.done("a", [5, 6])
        j.terminal("b", "expired", "deadline")
        j.close()
        j2 = RequestJournal(path)
        assert j2.pending_rids(["a", "b", "c"]) == ["c"]
        report = j2.exactly_once_report(["a", "b", "c"])
        assert report["lost"] == ["c"] and report["duplicated"] == []

    def test_torn_tail_line_tolerated(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        j = RequestJournal(path)
        j.launch()
        j.done("a", [1])
        j.close()
        with open(path, "a") as f:
            f.write('{"event": "done", "rid": "b", "tok')  # torn by a kill
        j2 = RequestJournal(path)
        assert j2.acknowledged_rids() == {"a"}

    def test_duplicate_ack_detected(self, tmp_path):
        j = RequestJournal(str(tmp_path / "j.jsonl"))
        j.done("a", [1])
        j.done("a", [1])
        report = j.exactly_once_report(["a"])
        assert report["duplicated"] == ["a"]
        assert not report["exactly_once"]


# ---------------------------------------------------------------------------
# Declared plan through plan_check
# ---------------------------------------------------------------------------

class TestServingPlan:
    def test_plan_and_traces_clean(self):
        from paddle_tpu.analysis import jaxpr_lint, plan_check
        engine = ServingEngine(micro_model(), block_size=4, num_blocks=32,
                               max_batch=2)
        traced = engine.trace_steps()
        for name, (closed, donate) in traced.items():
            assert jaxpr_lint.lint_jaxpr(
                closed, donate_argnums=donate,
                where=f"serving.{name}") == []
        diags = plan_check.check_plan(engine.plan, traced["decode"][0],
                                      donate_argnums=traced["decode"][1])
        assert diags == []

    def test_bad_plan_caught(self):
        """Sanity: the verifier actually guards the serving dispatch —
        reading the pool after a spill-side donation without a
        re-materializing write is a D001."""
        from paddle_tpu.analysis import plan_check
        from paddle_tpu.analysis.plan_check import PlanNode, StepPlan
        plan = StepPlan(nodes=[
            PlanNode("serve.decode", donates=("kv_pages",),
                     writes=("next_tokens",)),      # forgot the rewrite
            PlanNode("serve.spill", reads=("kv_pages",),
                     writes=("host_kv",)),
        ])
        diags = plan_check.check_plan(plan)
        assert any(d.rule == "D001" for d in diags)


# ---------------------------------------------------------------------------
# ISSUE 13: refcounted allocator + radix prefix tree (satellite 3)
# ---------------------------------------------------------------------------

def assert_allocator_pristine_shared(engine):
    """Prefix-cache extension of :func:`assert_allocator_pristine`: after
    a drain, only the tree's cache holds may remain — evicting the whole
    tree (drop path) must land the allocator back at a fresh free list
    with zero refcount residue."""
    alloc = engine.cache.allocator
    held = (engine.prefix.device_block_ids()
            if engine.prefix is not None else frozenset())
    assert alloc.n_used == len(held), (alloc.n_used, sorted(held))
    for i in held:
        assert alloc.refcount(i) == 1       # tree cache ref only
    if engine.prefix is not None:
        engine.prefix.evict(alloc.num_blocks, spill=False)
    assert_allocator_pristine(engine)


class TestAllocatorRefcounts:
    def test_ref_free_lifecycle(self):
        a = BlockAllocator(8)
        ids = a.alloc(2)
        a.ref(ids)                           # second owner
        assert a.n_shared == 2
        a.free(ids)                          # first owner lets go
        assert a.n_used == 2 and a.n_shared == 0
        assert a.refcount(ids[0]) == 1
        a.free(ids)                          # last owner: back to free
        assert a.n_used == 0 and a.n_free == 7
        with pytest.raises(ValueError, match="double-free"):
            a.free([ids[0]])

    def test_ref_of_unallocated_rejected(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError, match="unallocated"):
            a.ref([2])

    def test_flag_off_semantics_unchanged(self):
        """refcount-1 alloc/free round trips are exactly the historical
        allocator: min-id order, all-or-nothing, reserved guard."""
        a = BlockAllocator(8)
        assert a.alloc(3) == [1, 2, 3]
        a.free([2])
        assert a.alloc(2) == [2, 4]
        with pytest.raises(ValueError, match="reserved"):
            a.free([NULL_BLOCK])


class TestPrefixTree:
    def _cache(self, num_blocks=16):
        return PagedKVCache(n_layers=2, num_blocks=num_blocks,
                            block_size=4, kv_heads=2, head_dim=8)

    def _fill(self, cache, ids, seed=0):
        from paddle_tpu.serving.paged_cache import _scatter_blocks
        rng = np.random.default_rng(seed)
        k = rng.standard_normal(
            (2, len(ids), 4, 2, 8)).astype(np.float32)
        v = rng.standard_normal(
            (2, len(ids), 4, 2, 8)).astype(np.float32)
        cache.k = _scatter_blocks(cache.k, jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(k))
        cache.v = _scatter_blocks(cache.v, jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(v))
        return k, v

    def test_match_caps_at_prompt_minus_one(self):
        """The final prompt token is always recomputed (its logits are
        the first generated token) — an exactly-block-aligned prompt
        matches one block fewer than it inserted."""
        cache = self._cache()
        tree = PrefixCache(cache)
        prompt = np.arange(8, dtype=np.int32)     # 2 exact blocks
        ids = cache.allocator.alloc(2)
        assert len(tree.insert(prompt, ids, 8)) == 2
        assert len(tree.match(prompt)) == 1       # (8-1)//4 = 1
        longer = np.arange(9, dtype=np.int32)
        assert len(tree.match(longer)) == 2       # (9-1)//4 = 2

    def test_shared_spill_restore_bitwise_both_sharers_alive(self):
        """Satellite 3 acceptance: a shared block spilled by tree
        eviction restores BITWISE while both sharing requests still
        exist (preempted — refs released, re-attach pending)."""
        cache = self._cache(num_blocks=8)
        tree = PrefixCache(cache)
        prompt = np.arange(9, dtype=np.int32)
        ids = cache.allocator.alloc(2)
        k0, v0 = self._fill(cache, ids)
        inserted = tree.insert(prompt, ids, 8)
        # two live sharers attach (so the pages are genuinely shared),
        # then both get preempted: seq refs released, requests alive
        chains = [tree.match(prompt) for _ in range(2)]
        for c in chains:
            got = tree.attach("s", c, cache.allocator.alloc)
            assert got == ids
        assert cache.allocator.n_shared == 2
        tree.release(inserted)
        for c in chains:
            tree.release(c)
        # evict under pressure: ONE host copy per node
        assert tree.evict(2) == 2
        assert cache.allocator.n_used == 0
        # both sharers resume: first re-attach restores, second attaches
        # to the restored block — no second host transfer
        metrics.reset_all()
        c1 = tree.match(prompt)
        a1 = tree.attach("s1", c1, cache.allocator.alloc)
        c2 = tree.match(prompt)
        a2 = tree.attach("s2", c2, cache.allocator.alloc)
        assert a1 == a2
        # one restore per spilled node (the second sharer re-attaches to
        # the already-restored pages — no second host transfer)
        assert metrics.counter("serving.kv_restores").get() == 2
        k_back, v_back = cache.read_blocks(a1)
        np.testing.assert_array_equal(k_back, k0)
        np.testing.assert_array_equal(v_back, v0)
        tree.assert_consistent()

    def test_never_rematched_eviction_drops_not_spills(self):
        cache = self._cache(num_blocks=8)
        tree = PrefixCache(cache)
        ids = cache.allocator.alloc(2)
        new = tree.insert(np.arange(9, dtype=np.int32), ids, 8)
        tree.release(new)
        assert tree.evict(2) == 2
        assert tree.n_nodes == 0              # dropped: hits == 0
        assert tree.match(np.arange(9, dtype=np.int32)) == []

    def test_randomized_trie_workload_invariants(self):
        """Randomized attach/insert/release/evict churn: the allocator
        never leaks, never double-frees, reserved ids never drift, and
        the tree's refcount bookkeeping stays consistent throughout."""
        rng = np.random.default_rng(42)
        cache = self._cache(num_blocks=24)
        tree = PrefixCache(cache)
        prompts = [rng.integers(0, 8, int(rng.integers(5, 17)))
                   for _ in range(6)]
        live = []                             # (chain, private_ids)
        for step in range(200):
            op = rng.integers(0, 3)
            if op == 0 and len(live) < 8:     # admit a random prompt
                p = prompts[int(rng.integers(0, len(prompts)))]
                chain = tree.match(p)
                got = tree.attach("s", chain, cache.allocator.alloc)
                chain = chain[:len(got)]
                n_total = -(-p.size // 4)
                ids = cache.allocator.alloc(n_total - len(got))
                if ids is None:
                    if chain:
                        tree.release(chain)
                    cache.allocator.alloc(0)
                    tree.evict(4)
                    continue
                new = tree.insert(p, got + ids, p.size,
                                  have=len(chain))
                live.append((chain + new, (got + ids)[len(chain) +
                                                      len(new):]))
            elif op == 1 and live:            # retire one
                chain, priv = live.pop(int(rng.integers(0, len(live))))
                if chain:
                    tree.release(chain)
                if priv:
                    cache.allocator.free(priv)
            else:                             # pressure: evict
                tree.evict(int(rng.integers(1, 4)))
            tree.assert_consistent()
            # reserved never drifts, used+free partitions the pool
            assert cache.allocator._reserved == frozenset({NULL_BLOCK})
            assert (cache.allocator.n_used + cache.allocator.n_free
                    == cache.allocator.num_blocks - 1)
        for chain, priv in live:
            if chain:
                tree.release(chain)
            if priv:
                cache.allocator.free(priv)
        tree.evict(cache.allocator.num_blocks, spill=False)
        assert cache.allocator.n_used == 0


# ---------------------------------------------------------------------------
# ISSUE 13: the three throughput tiers through the engine
# ---------------------------------------------------------------------------

def shared_prefix_requests(n, shared_len=12, suffix=4, max_new=6,
                           vocab=128, seed=3):
    rng = np.random.default_rng(seed)
    sysp = rng.integers(0, vocab, shared_len)
    return [Request(rid=f"s{i}",
                    prompt_ids=np.concatenate(
                        [sysp, rng.integers(0, vocab, suffix)]),
                    max_new_tokens=max_new) for i in range(n)]


class TestPrefixCacheEngine:
    def test_shared_trace_token_exact_with_hits(self):
        model = micro_model()
        reqs = shared_prefix_requests(4)
        engine = ServingEngine(model, block_size=4, num_blocks=64,
                               max_batch=4, prefix_cache=True)
        results = engine.serve(reqs)
        for r in reqs:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        rep = engine.prefix_report()
        assert rep["hit_rate"] > 0.3          # sharers attached
        assert rep["tree_nodes"] > 0
        assert_allocator_pristine_shared(engine)

    def test_outputs_equal_flag_off(self):
        """The cache changes WHERE KV lives, never what comes out."""
        model = micro_model()
        reqs = ragged_requests(4, seed=6)
        on = ServingEngine(model, block_size=4, num_blocks=32,
                           max_batch=4, prefix_cache=True).serve(reqs)
        off = ServingEngine(model, block_size=4, num_blocks=32,
                            max_batch=4).serve(reqs)
        for r in reqs:
            np.testing.assert_array_equal(on[r.rid].output,
                                          off[r.rid].output)

    def test_token_exact_under_preemption_pressure(self):
        """Acceptance criterion: prefix cache on + pool pressure — the
        refcount-aware spill keeps shared pages pinned, spills only the
        private tail, and every output still matches generate."""
        model = micro_model(max_position_embeddings=32)
        reqs = shared_prefix_requests(4, shared_len=12, suffix=4,
                                      max_new=8)
        metrics.reset_all()
        engine = ServingEngine(model, block_size=4, num_blocks=14,
                               max_batch=4, max_seq_len=32,
                               prefix_cache=True)
        results = engine.serve(reqs)
        assert metrics.counter("serving.preemptions").get() > 0
        for r in reqs:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        assert_allocator_pristine_shared(engine)

    def test_cow_runtime_assert_fires(self):
        model = micro_model()
        engine = ServingEngine(model, block_size=4, num_blocks=64,
                               max_batch=2, prefix_cache=True)
        engine.serve(shared_prefix_requests(2))
        held = engine.prefix.device_block_ids()
        assert held
        with pytest.raises(AssertionError, match="COW write-isolation"):
            engine._assert_cow([next(iter(held))])


class TestCostAwarePreemption:
    """Satellite 2: victim/shed cost accounting counts only private
    (refcount-1) blocks."""

    def _mk(self, rid, t_submit, priority=0, blocks=0, shared=0):
        s = Sequence(Request(rid=rid, prompt_ids=np.ones(4, np.int32),
                             max_new_tokens=2, priority=priority))
        s.t_submit = t_submit
        s.block_ids = list(range(10, 10 + blocks))
        s.n_shared_blocks = shared
        s.status = Status.RUNNING
        return s

    def test_victim_prefers_private_kv_hog(self):
        from paddle_tpu.serving.scheduler import FCFSScheduler
        sched = FCFSScheduler(4)
        sharer = self._mk("sharer", 2.0, blocks=6, shared=5)  # 1 private
        hog = self._mk("hog", 1.0, blocks=6, shared=0)        # 6 private
        sched.running = [hog, sharer]
        # historical LIFO picks the youngest (the cheap sharer)...
        assert sched.preempt_victim() is sharer
        # ...the cost model picks the hog whose spill actually frees KV
        cost = lambda s: len(s.block_ids) - s.n_shared_blocks
        assert sched.preempt_victim(cost=cost) is hog

    def test_priority_still_dominates_cost(self):
        from paddle_tpu.serving.scheduler import FCFSScheduler
        sched = FCFSScheduler(4)
        lo = self._mk("lo", 1.0, priority=0, blocks=1, shared=0)
        hi = self._mk("hi", 2.0, priority=1, blocks=9, shared=0)
        sched.running = [lo, hi]
        cost = lambda s: len(s.block_ids) - s.n_shared_blocks
        assert sched.preempt_victim(cost=cost) is lo

    def test_shed_candidate_cost_order(self):
        from paddle_tpu.serving.scheduler import FCFSScheduler
        sched = FCFSScheduler(4)
        a = self._mk("a", 1.0, blocks=2, shared=2)   # 0 private
        b = self._mk("b", 2.0, blocks=4, shared=1)   # 3 private
        sched.running = [a, b]
        assert sched.shed_candidate() is b           # youngest (old rule)
        cost = lambda s: len(s.block_ids) - s.n_shared_blocks
        assert sched.shed_candidate(cost=cost) is b  # also most private
        a.n_shared_blocks = 0                        # now a frees 2
        b.n_shared_blocks = 4                        # b frees 0
        assert sched.shed_candidate(cost=cost) is a


class TestChunkedPrefill:
    def test_token_exact(self):
        model = micro_model()
        reqs = ragged_requests(4, lo=9, hi=14, max_new=5, seed=8)
        metrics.reset_all()
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=4, chunked_prefill=8)
        results = engine.serve(reqs)
        for r in reqs:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        assert metrics.counter(
            "serving.chunked_prefill_iterations").get() > 0
        recs = [s for s in results.values()
                if "chunk_prefill" in s.phase_s]
        assert recs, "chunk phase expected on the timeline"

    def test_long_prompt_interleaves_with_decode(self):
        """The point of the budget: a resident keeps committing tokens
        WHILE the long prompt's chunks prefill."""
        model = micro_model()
        engine = ServingEngine(model, block_size=4, num_blocks=64,
                               max_batch=4, chunked_prefill=4)
        rng = np.random.default_rng(4)
        resident = Request(rid="res", prompt_ids=rng.integers(0, 128, 5),
                           max_new_tokens=20)
        long_req = Request(rid="long",
                           prompt_ids=rng.integers(0, 128, 24),
                           max_new_tokens=2)
        engine.submit(resident)
        while not engine._seqs["res"].out_tokens:
            engine.step()
        engine.submit(long_req)
        interleaved = False
        n0 = engine._seqs["res"].n_generated
        for _ in range(100):
            engine.step()
            seq = engine._seqs["long"]
            if (0 < seq.prefill_pos < seq.prompt_len
                    and engine._seqs["res"].n_generated > n0):
                interleaved = True
            if not engine.sched.n_pending:
                break
        assert interleaved, \
            "resident decode must progress mid-prefill of the long prompt"
        np.testing.assert_array_equal(
            engine._seqs["res"].output, ref_generate(model, resident))
        np.testing.assert_array_equal(
            engine._seqs["long"].output, ref_generate(model, long_req))


class TestSpeculative:
    def test_ngram_propose(self):
        d = NGramDrafter(repeat_fallback=False)
        assert d.propose([1, 2, 3, 1, 2], 3) == [3, 1, 2]
        assert d.propose([5, 6, 7], 2) == []          # no repeat
        d2 = NGramDrafter()
        assert d2.propose([5, 6, 7], 2) == [7, 7]     # fallback

    def test_ngram_token_exact(self):
        model = micro_model()
        reqs = ragged_requests(4, max_new=8, seed=9)
        engine = ServingEngine(model, block_size=4, num_blocks=32,
                               max_batch=4, speculative=3)
        results = engine.serve(reqs)
        for r in reqs:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        rep = engine.spec_report()
        assert rep["iterations"] > 0
        assert rep["gamma"] == 3
        h = metrics.histogram("serving.spec_accept_len").labels()
        assert h.get()["count"] > 0

    def test_model_drafter_token_exact(self):
        """A drafter LM over the mirrored paged pool: own page dims,
        same block ids/tables, spills and restores with its sequence."""
        model = micro_model(max_position_embeddings=32)
        paddle.seed(11)
        from paddle_tpu.text.models.gpt import gpt_tiny as _tiny
        dm = GPTForCausalLM(_tiny(vocab_size=128, hidden_size=32,
                                  num_layers=1, num_heads=2,
                                  max_position_embeddings=32))
        reqs = ragged_requests(4, lo=8, hi=14, max_new=8, seed=1)
        metrics.reset_all()
        engine = ServingEngine(model, block_size=4, num_blocks=10,
                               max_batch=4, max_seq_len=32,
                               speculative=2, drafter=ModelDrafter(dm))
        results = engine.serve(reqs)     # pool pressure: spills too
        assert metrics.counter("serving.preemptions").get() > 0
        for r in reqs:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        assert engine.cache.allocator.n_used == 0

    def test_gamma_autotune_round_trip(self, tmp_path):
        from paddle_tpu.core.flags import set_flags
        from paddle_tpu.ops._pallas import autotune as at
        set_flags({"kernel_autotune_cache_path":
                   str(tmp_path / "tune.json")})
        old = at._cache
        at._cache = None
        try:
            assert pick_gamma("t", "d", default=5) == 5
            assert tune_gamma("t", "d", [2, 3, 3, 4]) == 3  # ceil(mean 3)
            assert pick_gamma("t", "d", default=5) == 3
            from paddle_tpu.serving.speculative import store_gamma
            store_gamma("t", "d", 6)
            assert pick_gamma("t", "d") == 6
        finally:
            at._cache = old
            set_flags({"kernel_autotune_cache_path": ""})

    def test_all_three_tiers_composed(self):
        model = micro_model(max_position_embeddings=32)
        reqs = shared_prefix_requests(4, shared_len=8, suffix=6,
                                      max_new=8, seed=2)
        engine = ServingEngine(model, block_size=4, num_blocks=12,
                               max_batch=4, max_seq_len=32,
                               prefix_cache=True, chunked_prefill=8,
                               speculative=2)
        results = engine.serve(reqs)
        for r in reqs:
            np.testing.assert_array_equal(results[r.rid].output,
                                          ref_generate(model, r))
        rep = engine.compile_report()
        assert rep["within_budget"] and not rep["o001_fired"], rep
        assert_allocator_pristine_shared(engine)


class TestCowPlanRule:
    def test_d005_fires_on_shared_write(self):
        from paddle_tpu.analysis import plan_check
        from paddle_tpu.analysis.plan_check import PlanNode, StepPlan
        plan = StepPlan(
            flags={"cow_shared_buffers": "kv_pages_shared"},
            nodes=[PlanNode("serve.verify",
                            donates=("kv_pages_shared",),
                            writes=("next_tokens",))])
        assert "D005" in {d.rule for d in plan_check.check_plan(plan)}

    def test_d005_silent_on_engine_plan(self):
        from paddle_tpu.analysis import plan_check
        engine = ServingEngine(micro_model(), block_size=4,
                               num_blocks=32, max_batch=2,
                               prefix_cache=True, chunked_prefill=8,
                               speculative=2)
        diags = plan_check.check_plan(engine.plan)
        assert [d for d in diags if d.rule == "D005"] == []


class TestJournalPromptHash:
    def test_submitted_carries_content_hash(self, tmp_path):
        from paddle_tpu.serving.resilience import prompt_hash
        path = str(tmp_path / "j.jsonl")
        j = RequestJournal(path)
        req = Request(rid="a", prompt_ids=np.asarray([3, 1, 4], np.int32),
                      max_new_tokens=2)
        j.submitted(req)
        j.close()
        j2 = RequestJournal(path)
        shas = j2.prompt_hashes()
        assert shas == {"a": prompt_hash([3, 1, 4])}
        assert shas["a"] != prompt_hash([3, 1, 5])

    def test_worker_rejects_drifted_replay_trace(self, tmp_path):
        """A relaunch whose trace no longer matches the journaled
        prompt hashes must refuse to serve wrong tokens under old
        rids."""
        import json as _json
        from paddle_tpu.serving import _drill_worker as worker
        trace = [{"rid": "r0", "prompt": [1, 2, 3], "max_new_tokens": 2}]
        with open(tmp_path / "trace.jsonl", "w") as f:
            f.write(_json.dumps(trace[0]) + "\n")
        j = RequestJournal(str(tmp_path / "journal.jsonl"))
        j.submitted(Request(rid="r0",
                            prompt_ids=np.asarray([9, 9, 9], np.int32),
                            max_new_tokens=2))
        j.close()
        with pytest.raises(RuntimeError, match="journaled submission"):
            worker.run(str(tmp_path), dict(
                model_seed=7, vocab=128, hidden=32, layers=1, heads=2,
                max_pos=32, block_size=4, num_blocks=8, max_batch=2))


# ---------------------------------------------------------------------------
# Generation by diffusion over blocks (a model whose serve_generation is a
# block spec): a pass yields no token or a whole block
# ---------------------------------------------------------------------------

def block_model(**over):
    from paddle_tpu.text.models.sdar_moe import (SdarMoeForCausalLM,
                                                 sdar_moe_tiny)
    paddle.seed(11)
    m = SdarMoeForCausalLM(sdar_moe_tiny(initializer_range=0.2, **over))
    m.eval()
    return m


def block_free_run(model, req):
    """The model's own greedy generation by diffusion over blocks, no cache:
    every denoise pass one block-causal forward of the clean sequence so far
    followed by the block as it stands; the rule of the engine's
    ``_unmask`` in numpy."""
    gen = model.serve_generation
    B, k_min = gen.block_length, max(1, gen.block_length // gen.steps)
    seq = [int(t) for t in req.prompt_ids]
    n_prompt, end = len(seq), len(seq) + req.max_new_tokens
    for pos0 in range(n_prompt // B * B, end, B):
        pos = pos0 + np.arange(B)
        blk = np.full((B,), gen.mask_id, np.int64)
        blk[:max(0, n_prompt - pos0)] = seq[pos0:]
        masked = (pos >= n_prompt) & (pos < end)
        while masked.any():
            ids = np.concatenate([np.asarray(seq[:pos0], np.int64), blk])
            logits = np.asarray(model(jnp.asarray(ids[None], jnp.int32)),
                                np.float32)[0, pos0:]
            prob = np.exp(logits - logits.max(-1, keepdims=True))
            conf = 1.0 / prob.sum(-1)
            high = masked & (conf > gen.threshold)
            if high.sum() >= k_min:
                take = high
            else:
                order = np.argsort(-np.where(masked, conf, -1.0),
                                   kind="stable")
                take = np.zeros_like(masked)
                take[order[:k_min]] = True
                take &= masked
            blk[take] = logits.argmax(-1)[take]
            masked &= ~take
        seq = seq[:pos0] + [int(t) for t in blk[:min(B, end - pos0)]]
    return np.asarray(seq, np.int32)


def block_requests(n, lo=2, hi=14, seed=0, new=(1, 13)):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"b{i}",
                    prompt_ids=rng.integers(0, 500,
                                            int(rng.integers(lo, hi + 1))),
                    max_new_tokens=int(rng.integers(new[0], new[1] + 1)))
            for i in range(n)]


class TestBlockDiffusion:
    def _engine(self, model, **kw):
        args = dict(block_size=8, num_blocks=40, max_batch=3, max_seq_len=32,
                    prefill_buckets=[8, 16], decode_buckets=[1, 3])
        args.update(kw)
        return ServingEngine(model, **args)

    def test_the_engine_takes_its_decode_program_by_the_models_answer(self):
        assert micro_model().serve_generation is None
        gpt = ServingEngine(micro_model(), block_size=4, num_blocks=17,
                            max_batch=2)
        assert gpt._gen is None and gpt._decode_head_spec(2).shape == (2,)
        eng = self._engine(block_model())
        assert eng._gen.block_length == 4
        assert eng._decode_head_spec(3).shape == (3, 4)
        # the ONE pool of fused rows (keys | values: 2 x 2 heads) is stored
        # heads first, GPT's two pools as ever
        assert [p.shape for p in eng.cache.pools] == [(2, 40, 4, 8, 128)]
        assert [p.shape for p in gpt.cache.pools] == [(2, 17, 4, 4, 12)] * 2

    def test_rows_join_and_leave_mid_block_and_tokens_match_a_free_run(self):
        """Seven requests through three rows: a row that ends frees its slot
        while its neighbours are in the middle of a block, and the joiner's
        first pass runs beside their later ones, under the launch-ahead
        order; every answer has exactly the tokens it asked for, the model's
        own."""
        model = block_model()
        eng = self._engine(model)
        metrics.reset_all()
        requests = block_requests(7, seed=3)
        mid_block_joins = 0
        seqs = [eng.submit(r) for r in requests]
        running = set()
        while eng.sched.n_pending:
            eng.step()
            now = {s.rid for s in eng.sched.running}
            if now - running and any(
                    s.block is not None and s.block.masked.any()
                    and not s.block.masked.all()
                    for s in eng.sched.running if s.rid in running):
                mid_block_joins += 1
            running = now
        assert mid_block_joins > 0
        for r, seq in zip(requests, seqs):
            assert seq.status is Status.FINISHED
            assert len(seq.out_tokens) == r.max_new_tokens
            np.testing.assert_array_equal(seq.output,
                                          block_free_run(model, r))
        fed = metrics.counter("serving.decode_rows")
        assert fed.labels(fed="device").get() > fed.labels(fed="host").get()
        assert fed.labels(fed="dropped").get() == 0
        assert eng._ahead is None and eng.cache.allocator.n_used == 0

    def test_nothing_moves_until_a_block_commits(self):
        """A denoise pass writes the block's keys and values to its page (the
        commit pass writes last) and changes nothing the host counts:
        ``ctx_len``, the output and the first-token stamp move at a commit,
        by the block's tokens."""
        model = block_model()
        eng = self._engine(model)
        req = Request("one", np.arange(10, 19), max_new_tokens=7)  # P % 4 = 1
        seq = eng.submit(req)
        eng.step()                  # prefill of 8, the first pass launched
        assert seq.ctx_len == 8 and not seq.out_tokens
        assert seq.block.pos0 == 8 and seq.t_first_token is None
        page = seq.block_ids[1]
        seen = []
        while not seq.out_tokens:
            before = np.asarray(eng.cache.k[0, page]).copy()
            eng.step()
            seen.append((seq.ctx_len, len(seq.out_tokens),
                         bool((np.asarray(eng.cache.k[0, page])
                               != before).any())))
        # three positions to unmask: three denoise passes and a commit, all
        # of which wrote the page; the first block gives 3 tokens, not 4
        assert [s[:2] for s in seen[:-1]] == [(8, 0)] * (len(seen) - 1)
        assert seen[-1][:2] == (12, 3) and all(s[2] for s in seen[:3])
        assert seq.t_first_token is not None
        while eng.sched.n_pending:
            eng.step()
        assert len(seq.out_tokens) == 7 and seq.ctx_len == 16
        np.testing.assert_array_equal(seq.output, block_free_run(model, req))

    def test_a_row_preempted_mid_block_loses_only_its_passes(self):
        model = block_model()
        eng = self._engine(model)
        requests = block_requests(3, lo=6, hi=9, seed=5, new=(9, 12))
        seqs = [eng.submit(r) for r in requests]
        victim = seqs[1]
        eng.step()
        while not (victim.block is not None and victim.block.stage == 0
                   and 0 < victim.block.masked.sum() < 4
                   and victim.out_tokens):
            eng.step()
        assert victim in eng._ahead[0]
        n_out, ctx, pos0 = len(victim.out_tokens), victim.ctx_len, \
            victim.block.pos0
        eng._preempt(victim)            # a pass of its block is in flight
        eng.step()                      # restored at once: room is left
        assert victim.preemptions == 1
        assert (len(victim.out_tokens), victim.ctx_len) == (n_out, ctx)
        # the block starts again from masks
        assert victim.block.pos0 == pos0 and victim.block.stage == 0
        assert victim.block.masked.sum() >= 3
        while eng.sched.n_pending:
            eng.step()
        for r, seq in zip(requests, seqs):
            np.testing.assert_array_equal(seq.output,
                                          block_free_run(model, r))
        assert metrics.counter("serving.decode_rows").labels(
            fed="dropped").get() >= 1
        assert eng.cache.allocator.n_used == 0

    def test_a_row_cancelled_mid_block_leaves_the_others_exact(self):
        model = block_model()
        eng = self._engine(model)
        requests = block_requests(3, lo=6, hi=9, seed=6, new=(9, 12))
        seqs = [eng.submit(r) for r in requests]
        for _ in range(3):
            eng.step()
        gone = seqs[0]
        assert gone in eng._ahead[0] and gone.block.masked.any()
        n_out = len(gone.out_tokens)
        eng._cancel(gone, Status.EXPIRED, "test: cancelled mid-block")
        while eng.sched.n_pending:
            eng.step()
        assert gone.status is Status.EXPIRED and len(gone.out_tokens) == n_out
        for r, seq in zip(requests[1:], seqs[1:]):
            np.testing.assert_array_equal(seq.output,
                                          block_free_run(model, r))
        assert eng.cache.allocator.n_used == 0

    def test_pool_pressure_spills_and_restores_exactly(self):
        """A pool too small for three rows: a row is preempted (its pages go
        to the host and come back), restarts the block it was in, and every
        answer is still the model's own."""
        model = block_model()
        eng = self._engine(model, num_blocks=8)
        metrics.reset_all()
        requests = block_requests(4, lo=8, hi=14, seed=7, new=(10, 14))
        results = eng.serve(requests)
        assert metrics.counter("serving.preemptions").get() > 0
        assert metrics.counter("serving.kv_restores").get() > 0
        for r in requests:
            assert results[r.rid].status is Status.FINISHED
            np.testing.assert_array_equal(results[r.rid].output,
                                          block_free_run(model, r))
        assert eng.cache.allocator.n_used == 0

    def test_an_end_of_sequence_token_ends_a_request_inside_a_block(self):
        model = block_model()
        req = Request("e", np.arange(3, 12), max_new_tokens=12)
        free = block_free_run(model, req)[9:]
        eos = int(free[5])
        cut = int(np.flatnonzero(free == eos)[0]) + 1
        eng = self._engine(model)
        seq = eng.submit(Request("e", np.arange(3, 12), max_new_tokens=12,
                                 eos_token_id=eos))
        while eng.sched.n_pending:
            eng.step()
        assert seq.status is Status.FINISHED
        assert seq.out_tokens == [int(t) for t in free[:cut]]
        # (the pass launched before the token was seen is never taken)
        assert eng.cache.allocator.n_used == 0
