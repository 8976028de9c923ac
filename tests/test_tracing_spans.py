"""The one span path after ISSUE 26: a record's fields, self time, the
``FLAGS_telemetry=off`` contract (shared no-op, empty ring, bitwise-equal
engine and TrainStep outputs), the span tree inside ``eng.step()`` and
``TrainStep.step``, the counts taken at the same boundaries, per-token commit
stamps, and the host hooks (compiles and garbage collections as spans)."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import flags as core_flags
from paddle_tpu.observability import (metrics, request_timeline,
                                      step_monitor, trace)
from paddle_tpu.serving import Request, ServingEngine, ShedPolicy
from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

STEP_CHILDREN = {"serve/expire_shed", "serve/admit", "serve/chunk",
                 "serve/ensure_blocks", "serve/decode", "serve/gauges"}
# a step has up to two ``serve/decode`` spans: it builds and launches the
# next iteration, then takes the tokens of the one the last step launched
DECODE_TAKE = ["serve/decode/wait", "serve/decode/commit"]
DECODE_LAUNCH = ["serve/decode/build", "serve/decode/checks",
                 "serve/decode/launch"]
PREFILL_CHILDREN = ["serve/prefill/build", "serve/prefill/launch",
                    "serve/prefill/wait", "serve/prefill/commit"]


@pytest.fixture(autouse=True)
def _fresh():
    prev = core_flags.get_flags(["telemetry"])
    core_flags.set_flags({"telemetry": "metrics"})
    step_monitor.reset_default()
    request_timeline.reset_default()
    trace.clear()
    metrics.reset_all()
    yield
    core_flags.set_flags(prev)
    step_monitor.reset_default()
    trace.clear()


def _mode(m):
    core_flags.set_flags({"telemetry": m})


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = GPTForCausalLM(gpt_tiny(vocab_size=128, hidden_size=48, num_layers=2,
                                num_heads=4, max_position_embeddings=64))
    m.eval()
    return m


def _engine(model, **kw):
    return ServingEngine(model, block_size=8, num_blocks=33, max_batch=4,
                         prefill_buckets=[16, 32], decode_buckets=[4], **kw)


def _requests(n=5, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt_ids=rng.integers(0, 128, int(rng.integers(3, 15))),
                    max_new_tokens=max_new) for i in range(n)]


def _drive(eng, reqs):
    """Submit everything, step to the end; the context lengths fed to each
    decode dispatch, read from the outside before each step."""
    for r in reqs:
        eng.submit(r)
    fed = []
    while eng.sched.n_pending:
        before = {s.rid: (s.ctx_len, len(s.out_tokens))
                  for s in eng.sched.running}
        eng.step()
        fed.append(before)
    return fed


def _by_parent(recs):
    """Children by parent id, by start; a collection may land anywhere and
    is no part of the documented tree."""
    kids = {}
    for r in recs:
        if r["name"] != "host/gc":
            kids.setdefault(r["parent"], []).append(r)
    for v in kids.values():
        v.sort(key=lambda r: r["t0_ns"])
    return kids


# -- the record and the flag -------------------------------------------------

def test_record_fields_and_parent_links():
    with trace.span("outer", step=3) as outer:
        with trace.span("inner", rid="q1"):
            pass
        trace.record("late", outer.t0_ns, 5, why="reported")
    inner, late, root = trace.spans()
    assert {"kind", "name", "t0_ns", "dur_ns", "id", "parent", "tid",
            "depth"} <= set(root)
    assert root["parent"] is None and root["depth"] == 0
    assert inner["parent"] == root["id"] == late["parent"]
    assert inner["attrs"] == {"rid": "q1"} and inner["depth"] == 1
    assert late["dur_ns"] == 5 and late["attrs"] == {"why": "reported"}
    assert len({inner["id"], late["id"], root["id"]}) == 3
    assert root["t0_ns"] <= inner["t0_ns"]
    assert inner["t0_ns"] + inner["dur_ns"] <= root["t0_ns"] + root["dur_ns"]
    assert outer.dur_ns == root["dur_ns"]       # the exit's one duration


def test_self_time_of_a_nest():
    recs = [
        {"id": 1, "parent": None, "t0_ns": 0, "dur_ns": 100},
        {"id": 2, "parent": 1, "t0_ns": 10, "dur_ns": 30},
        {"id": 3, "parent": 1, "t0_ns": 50, "dur_ns": 20},
        {"id": 4, "parent": 2, "t0_ns": 15, "dur_ns": 10},
        # a compile reported inside a compile: overlapping children count once
        {"id": 5, "parent": 3, "t0_ns": 50, "dur_ns": 10},
        {"id": 6, "parent": 3, "t0_ns": 55, "dur_ns": 10},
        {"id": 7, "parent": 99, "t0_ns": 500, "dur_ns": 7},   # parent evicted
    ]
    assert trace.self_times(recs) == {1: 50, 2: 20, 3: 5, 4: 10, 5: 10,
                                      6: 10, 7: 7}


def test_trace_view_has_the_same_self_time_and_stands_alone(tmp_path):
    import gc
    import inspect
    from tools import trace_view
    # an automatic collection between here and the export would add a
    # ``host/gc`` record; whether one falls due depends on what the process
    # has allocated so far, not on this test
    gc.disable()
    try:
        trace.clear()
        with trace.span("root"):
            with trace.span("child"):
                with trace.span("leaf"):
                    pass
            with trace.span("child"):
                pass
        path = tmp_path / "ring.jsonl"
        assert trace.export_jsonl(str(path)) == 4
    finally:
        gc.enable()
    _, spans = trace_view.load_jsonl(str(path))
    assert trace_view.self_times(spans) == trace.self_times(spans)
    table = {r["span"]: r for r in trace_view.self_time_table(spans)}
    assert table["child"]["calls"] == 2 and set(table) == {"root", "child",
                                                           "leaf"}
    own = trace.self_times(spans)
    for name, row in table.items():
        assert row["self_ms"] == pytest.approx(sum(
            own[s["id"]] for s in spans if s["name"] == name) / 1e6, abs=1e-3)
    src = inspect.getsource(trace_view)
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    assert "sys.path" not in src


def test_flag_follows_set_flags_at_run_time():
    seen = []
    core_flags.watch("telemetry", seen.append)
    assert seen == ["metrics"]
    for m in ("off", "trace", "metrics"):
        _mode(m)
        assert trace.telemetry_mode() == m
        assert trace.enabled() == (m != "off")
    assert seen == ["metrics", "off", "trace", "metrics"]
    with pytest.raises(KeyError):
        core_flags.watch("telemetri", seen.append)


def test_off_is_one_shared_noop_and_timed_span_still_measures():
    _mode("off")
    a, b = trace.span("x", rid=1), trace.span("y")
    assert a is b and not a             # falsy: ``if sp: sp.set(...)``
    assert trace.timed_span("z")        # a real span is not
    with a as got:
        got.set(rows=3)
    assert (got.t0_ns, got.dur_ns, got.end_ns) == (0, 0, 0)
    trace.record("late", 0, 5)
    with trace.timed_span("acted") as t:
        pass
    assert t.dur_ns > 0 and t.end_ns == t.t0_ns + t.dur_ns
    assert trace.spans() == []


# -- the engine --------------------------------------------------------------

def test_engine_step_span_tree(model):
    eng = _engine(model)
    _drive(eng, _requests())
    recs = trace.spans()
    kids = _by_parent(recs)
    steps = [r for r in recs if r["name"] == "serve/step"]
    assert len(steps) == eng.n_iterations > 0
    assert [r["attrs"]["iteration"] for r in steps] == list(range(len(steps)))
    n_take = n_launch = 0
    for st in steps:
        assert st["parent"] is None
        mine = kids[st["id"]]
        names = [k["name"] for k in mine]
        assert set(names) <= STEP_CHILDREN, names
        assert names[0] == "serve/expire_shed" and names[-1] == "serve/gauges"
        assert "serve/chunk" not in names            # no chunk budget set
        for a, b in zip(mine, mine[1:]):             # children do not overlap
            assert a["t0_ns"] + a["dur_ns"] <= b["t0_ns"]
        assert sum(k["dur_ns"] for k in mine) <= st["dur_ns"]
        assert mine[-1]["t0_ns"] + mine[-1]["dur_ns"] <= \
            st["t0_ns"] + st["dur_ns"]
        for k in mine:
            if k["name"] == "serve/decode":
                parts = [d["name"] for d in kids[k["id"]]
                         if d["name"] != "serve/finish"]
                assert parts in (DECODE_TAKE, DECODE_LAUNCH)
                n_take += parts == DECODE_TAKE
                n_launch += parts == DECODE_LAUNCH
                assert k["attrs"]["width"] == 4
                assert 1 <= k["attrs"]["rows"] <= 4
        decodes = [k for k in mine if k["name"] == "serve/decode"]
        if decodes:                  # the blocks are topped up first
            assert names.index("serve/ensure_blocks") + 1 == \
                names.index("serve/decode")
        if len(decodes) == 2:        # one bucket: launched, then taken
            assert [d["name"] for d in kids[decodes[0]["id"]]] == \
                DECODE_LAUNCH
    assert n_take == n_launch > 0    # every launch is taken, a step later
    prefills = [r for r in recs if r["name"] == "serve/prefill"]
    assert sorted(p["attrs"]["rid"] for p in prefills) == \
        [f"r{i}" for i in range(5)]
    for p in prefills:                                # one request, one rid
        assert [k["name"] for k in kids[p["id"]]] == PREFILL_CHILDREN
        assert p["attrs"]["bucket"] in (16, 32)
        assert p["attrs"]["prompt_len"] <= p["attrs"]["bucket"]
    by_id = {r["id"]: r for r in recs}
    assert all(by_id[p["parent"]]["name"] == "serve/admit" for p in prefills)
    admitted = sum(r["attrs"]["admitted"] for r in recs
                   if r["name"] == "serve/admit")
    assert admitted == 5
    submits = [r for r in recs if r["name"] == "serve/submit"]
    assert [s["attrs"]["rid"] for s in submits] == [f"r{i}" for i in range(5)]
    assert {r["attrs"]["rid"] for r in recs if r["name"] == "serve/finish"} \
        == {f"r{i}" for i in range(5)}


def test_kv_and_prefill_counters_count_what_was_fed(model):
    eng = _engine(model)
    reqs = _requests()
    fed = _drive(eng, reqs)
    # a row decodes in a step iff it had a committed token before the step
    # or was prefilled in it; what it feeds is its context before the write
    needed = rows = 0
    for seq in eng.sched.finished:
        n_dec = len(seq.out_tokens) - 1
        needed += sum(seq.prompt_len + j for j in range(n_dec))
        rows += n_dec
    c = metrics.counter("serving.kv_tokens")
    assert c.labels(kind="needed").get() == needed
    kids = _by_parent(trace.spans())
    decodes = [r for r in trace.spans() if r["name"] == "serve/decode"
               and kids[r["id"]][0]["name"] == "serve/decode/build"]
    assert c.labels(kind="gathered").get() == \
        len(decodes) * 4 * eng.max_blocks_per_seq * eng.block_size
    # rows a dispatch and dispatches a step are the spans' to give; the
    # counter says where each row's token came from
    assert sum(d["attrs"]["rows"] for d in decodes) == rows
    assert eng.n_iterations == len(fed)
    by_feed = metrics.counter("serving.decode_rows")
    assert by_feed.labels(fed="device").get() \
        + by_feed.labels(fed="host").get() == rows
    assert by_feed.labels(fed="device").get() > 0
    assert by_feed.labels(fed="dropped").get() == 0
    assert "serving.steps" not in metrics.snapshot()
    pf = metrics.counter("serving.prefill_tokens")
    assert pf.labels(kind="real").get() == sum(r.prompt_ids.size for r in reqs)
    assert pf.labels(kind="bucket").get() == 5 * 16


def test_token_commit_stamps_and_the_request_record(model):
    eng = _engine(model)
    _drive(eng, _requests(3, max_new=5))
    recs = {r["rid"]: r for r in request_timeline.current().records()}
    assert set(recs) == {"r0", "r1", "r2"}
    for seq in eng.sched.finished:
        rec = recs[seq.rid]
        stamps = rec["token_t_ns"]
        assert len(stamps) == len(seq.out_tokens) == rec["new_tokens"]
        # the prefill's token and the first decoded one are committed apart
        assert stamps[1] > stamps[0] > rec["t_submit_ns"]
        assert stamps == sorted(stamps)
        assert rec["ttft_ms"] == pytest.approx(
            (stamps[0] - rec["t_submit_ns"]) / 1e6, abs=1e-3)
        assert set(rec["phases"]) >= {"queue", "prefill", "decode",
                                      "detokenize"}
    # rows of one decode step share one clock read
    second = sorted(recs[r]["token_t_ns"][1] for r in recs)
    assert second[0] == second[-1]


def test_span_exits_feed_the_histograms_and_the_policy_window(model):
    eng = _engine(model)
    _drive(eng, _requests())
    recs = trace.spans()
    kids = _by_parent(recs)
    launched = [r for r in recs if r["name"] == "serve/decode"
                and kids[r["id"]][0]["name"] == "serve/decode/build"]
    assert metrics.histogram("serving.decode_step_ms").get()["count"] \
        == len(launched) == len(eng._decode_ms)
    # the prefill's duration goes from its span to the request's account
    pre = {r["attrs"]["rid"]: r for r in recs if r["name"] == "serve/prefill"}
    for seq in eng.sched.finished:
        assert 0 < seq.phase_s["prefill"] * 1e9 < pre[seq.rid]["dur_ns"]
    # an iteration's time: the start of its build, or the arrival of the
    # tokens of the iteration it was queued behind if that came later, to
    # its own tokens' arrival
    before, wait = [r for r in recs if r["name"] == "serve/decode/wait"][-2:]
    began = max(launched[-1]["t0_ns"], before["t0_ns"] + before["dur_ns"])
    assert before["t0_ns"] > launched[-1]["t0_ns"]
    assert eng._decode_ms[-1] == pytest.approx(
        (wait["t0_ns"] + wait["dur_ns"] - began) / 1e6)
    # the p99 gauge is sorted for a reader only: no policy, no exporter
    assert metrics.gauge("serving.decode_p99_ms").get() == 0


def test_engine_tokens_equal_off_and_on_and_off_leaves_no_record(model):
    outs = {}
    for mode in ("off", "metrics", "trace"):
        _mode(mode)
        trace.clear()
        metrics.reset_all()
        eng = _engine(model)
        _drive(eng, _requests(6, max_new=6, seed=3))
        outs[mode] = {s.rid: list(s.out_tokens) for s in eng.sched.finished}
        if mode == "off":
            assert trace.spans() == []
            assert len(eng._decode_ms) == 0
            # no commit stamp either: the hot path reads no clock for them
            assert all(s.token_t_ns == [] for s in eng.sched.finished)
            recs = request_timeline.current().records()
            assert len(recs) >= 6 and all(
                "token_t_ns" not in r and "t_submit_ns" not in r
                and r["ttft_ms"] > 0 for r in recs[-6:])
            assert metrics.histogram("serving.decode_step_ms").get()[
                "count"] == 0
        else:
            assert any(r["name"] == "serve/step" for r in trace.spans())
    assert outs["off"] == outs["metrics"] == outs["trace"]
    assert len(outs["off"]) == 6


def test_shed_policy_still_sees_decode_time_with_telemetry_off(model):
    _mode("off")
    eng = _engine(model, shed_policy=ShedPolicy(max_p99_decode_ms=1e-6))
    for r in _requests(4, max_new=8):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    assert len(eng._decode_ms) > 0 and eng.mode != "healthy"
    assert trace.spans() == []


# -- the train step ----------------------------------------------------------

def _train_step():
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.nn import functional as F
    from paddle_tpu.optimizer import AdamW
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))

    def loss_fn(model, params, batch):
        x, y = batch
        return F.cross_entropy(functional_call(model, params, x), y).mean()

    return make_sharded_train_step(net, AdamW(1e-3), loss_fn)


def _batch(seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((8, 8)).astype(np.float32),
            rng.integers(0, 4, (8,)).astype(np.int64))


def test_trainstep_span_tree_and_step_record():
    tl = step_monitor.reset_default()
    ts = _train_step()
    for s in range(3):
        ts.step(_batch(s))
    recs = trace.spans()
    kids = _by_parent(recs)
    roots = [r for r in recs if r["name"] == "step"]
    assert [r["attrs"]["step"] for r in roots] == [1, 2, 3]
    want = [["step/h2d", "step/checks", "step/compile", "step/end"],
            ["step/h2d", "step/checks", "step/device", "step/end"],
            ["step/h2d", "step/checks", "step/device", "step/end"]]
    for root, names, rec in zip(roots, want, tl.steps()):
        mine = [k for k in kids[root["id"]]
                if not k["name"].startswith(("jit/", "host/"))]
        assert [k["name"] for k in mine] == names
        assert sum(k["dur_ns"] for k in mine) <= root["dur_ns"]
        # the step record is placed on the ring's clock and timed by the
        # same stamps: root's start to where its own bookkeeping starts
        assert rec["t0_ns"] == root["t0_ns"]
        assert rec["total_ms"] == pytest.approx(
            (mine[-1]["t0_ns"] - root["t0_ns"]) / 1e6)
        assert set(rec["phases"]) == {n.split("/")[1] for n in names[:-1]}
        for k in mine[:-1]:
            assert rec["phases"][k["name"].split("/")[1]] == pytest.approx(
                k["dur_ns"] / 1e6)
    # the first dispatch's compile is reported inside its step/compile
    comp = [k for k in kids[roots[0]["id"]] if k["name"] == "step/compile"][0]
    assert any(r["name"] == "jit/compile" and r["parent"] == comp["id"]
               for r in recs)


def test_trainstep_off_is_bitwise_and_leaves_no_record():
    results = {}
    for mode in ("off", "metrics"):
        _mode(mode)
        trace.clear()
        tl = step_monitor.reset_default()
        ts = _train_step()
        losses = [np.asarray(ts.step(_batch(s))) for s in range(3)]
        results[mode] = (losses,
                         {k: np.asarray(v) for k, v in ts.params.items()})
        if mode == "off":
            assert trace.spans() == [] and tl.steps() == []
    for a, b in zip(results["off"][0], results["metrics"][0]):
        np.testing.assert_array_equal(a, b)
    for k, v in results["off"][1].items():
        np.testing.assert_array_equal(v, results["metrics"][1][k])


# -- host hooks --------------------------------------------------------------

def test_fresh_jit_adds_one_compile_span_and_a_repeat_none():
    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0

    x = jnp.ones((7, 3))
    jax.block_until_ready(x)
    trace.clear()
    metrics.reset_all()
    fresh(x).block_until_ready()
    first = trace.spans()
    comp = [r for r in first if r["name"] == "jit/compile"]
    assert len(comp) == 1 and "fresh" in comp[0]["attrs"]["fn"]
    assert comp[0]["attrs"]["event"].endswith("backend_compile_duration")
    assert "jit/lower" in {r["name"] for r in first}
    assert metrics.counter("jit.compiles").get() == 1
    assert "jit.seconds" not in metrics.snapshot()   # the spans hold them
    trace.clear()
    fresh(x).block_until_ready()
    assert [r for r in trace.spans() if r["name"].startswith("jit/")] == []
    assert metrics.counter("jit.compiles").get() == 1


def test_short_traces_leave_no_span():
    """Tracing a model reports thousands of sub-millisecond traces of the
    small functions inside it; they would push a run out of the ring."""
    ev = "/jax/core/compile/jaxpr_trace_duration"
    low = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    floor = step_monitor.JIT_TRACE_MIN_NS / 1e9
    trace.clear()
    step_monitor._on_jit_event(ev, floor / 2, fun_name="small")
    step_monitor._on_jit_event(ev, floor * 2, fun_name="large")
    step_monitor._on_jit_event(low, floor / 2, fun_name="small")
    got = [(r["name"], r["attrs"]["fn"]) for r in trace.spans()]
    assert got == [("jit/trace", "large"), ("jit/lower", "small")]


def test_forced_collection_adds_one_gc_span():
    gc.collect()
    trace.clear()
    with trace.span("holder") as holder:
        assert gc.collect() >= 0
    got = [r for r in trace.spans() if r["name"] == "host/gc"]
    assert len(got) == 1
    assert got[0]["attrs"]["generation"] == 2
    assert got[0]["attrs"]["collected"] >= 0
    assert got[0]["parent"] == holder.id
    assert got[0]["dur_ns"] <= holder.dur_ns
    _mode("off")
    trace.clear()
    gc.collect()
    assert trace.spans() == []


def test_hooks_install_once():
    from jax._src import monitoring
    n_gc = len(gc.callbacks)
    n_jit = len(monitoring.get_event_duration_listeners())
    step_monitor.install_host_hooks()
    assert len(gc.callbacks) == n_gc
    assert len(monitoring.get_event_duration_listeners()) == n_jit
    assert gc.callbacks.count(step_monitor._on_gc) == 1


# -- generation by diffusion over blocks ------------------------------------

def _block_engine():
    from paddle_tpu.text.models.sdar_moe import (SdarMoeForCausalLM,
                                                 sdar_moe_tiny)
    paddle.seed(5)
    m = SdarMoeForCausalLM(sdar_moe_tiny(initializer_range=0.2))
    m.eval()
    return ServingEngine(m, block_size=8, num_blocks=33, max_batch=4,
                         max_seq_len=64, prefill_buckets=[16, 32],
                         decode_buckets=[4])


def test_a_diffusion_pass_keeps_the_decode_spans_and_counts_its_passes():
    """A pass of a block-diffusion model is a ``serve/decode`` span with the
    same ``build|checks|launch`` and ``wait|commit`` children as a decode
    step (``benchmark/lib/program_spans.py`` reads it unchanged), launched
    before the last one is taken; and on whole blocks with no confidence over
    the threshold ``passes = 4 x blocks + blocks``."""
    eng = _block_engine()
    metrics.reset_all()
    rng = np.random.default_rng(1)
    # prompts and answers of whole blocks: every block has four positions
    reqs = [Request(rid=f"r{i}", prompt_ids=rng.integers(0, 500, 4 * p),
                    max_new_tokens=4 * n)
            for i, (p, n) in enumerate([(1, 2), (2, 1), (3, 3), (2, 2),
                                        (1, 1)])]
    _drive(eng, reqs)
    recs = trace.spans()
    kids = _by_parent(recs)
    n_take = n_launch = 0
    for st in (r for r in recs if r["name"] == "serve/step"):
        mine = kids[st["id"]]
        assert {k["name"] for k in mine} <= STEP_CHILDREN
        assert sum(k["dur_ns"] for k in mine) <= st["dur_ns"]
        decodes = [k for k in mine if k["name"] == "serve/decode"]
        for k in decodes:
            parts = kids[k["id"]]
            names = [d["name"] for d in parts if d["name"] != "serve/finish"]
            assert names in (DECODE_TAKE, DECODE_LAUNCH)
            n_take += names == DECODE_TAKE
            n_launch += names == DECODE_LAUNCH
            # the children add up inside their parent, in order
            for a, b in zip(parts, parts[1:]):
                assert a["t0_ns"] + a["dur_ns"] <= b["t0_ns"]
            assert sum(d["dur_ns"] for d in parts) <= k["dur_ns"]
            assert k["attrs"]["width"] == 4 and 1 <= k["attrs"]["rows"] <= 4
        if len(decodes) == 2:        # launched, then the last one taken
            assert [d["name"] for d in kids[decodes[0]["id"]]] == \
                DECODE_LAUNCH
    assert n_take == n_launch > 0
    snap = metrics.snapshot()
    passes = {s["labels"]["kind"]: s["value"]
              for s in snap["serving.diffusion_passes"]["series"]}
    unmasked = {s["labels"]["rule"]: s["value"]
                for s in snap["serving.diffusion_unmasked"]["series"]}
    blocks = snap["serving.diffusion_blocks"]["series"][0]["value"]
    assert blocks == sum(r.max_new_tokens for r in reqs) // 4 == 9
    assert unmasked == {"threshold": 0, "schedule": 4 * blocks}
    assert passes == {"denoise": 4 * blocks, "commit": blocks}
    rows = snap["serving.diffusion_pass_rows"]["series"][0]["value"]
    assert rows == passes["denoise"] + passes["commit"]
    assert rows == sum(
        k["attrs"]["rows"] for r in recs if r["name"] == "serve/step"
        for k in kids[r["id"]] if k["name"] == "serve/decode"
        and [d["name"] for d in kids[k["id"]]][:1] == ["serve/decode/build"])
    kv = {s["labels"]["kind"]: s["value"]
          for s in snap["serving.kv_tokens"]["series"]}
    # a pass attends its context and its block; off the chip the whole table
    assert 0 < kv["needed"] < kv["gathered"]
    assert kv["gathered"] == n_take * 4 * eng.max_blocks_per_seq * 8
    # no first token from a prefill: the first-token stamp is a commit's
    for r in request_timeline.current().records():
        assert r["ttft_ms"] is not None and r["new_tokens"] % 4 == 0
