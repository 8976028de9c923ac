"""The per-layer metrics that read the program's own spans and counters
(``benchmark/lib/program_spans.py``): every entry has its reader, the CPU
rehearsal of both cells gives each a finite value or nothing, and the clock
mapping and the naming of idle gaps are checked on the recorded trace with a
ring made up for it."""

import gzip
import math
import os

import pytest

from perfbench_tiny import (CLOSED, ROOT, SEED, SERVE_CELL, TRAIN, TRAIN_CELL,
                            manifest, tiny_cell)

from benchmark import run as R
from benchmark.lib import program_spans as PS
from benchmark.lib import readers
from benchmark.lib import trace as TR

SERVE_NEW = ["engine.host_self_ms_per_step", "engine.sched_ms_per_step",
             "engine.decode_build_ms_per_step",
             "engine.decode_checks_ms_per_step",
             "engine.decode_commit_ms_per_step", "engine.gap_named_share",
             "engine.ttft_commit_p95_ms",
             "engine.kv_read_useful_share", "engine.prefill_useful_share",
             "host.gc_pause_max_ms.serve"]
TRAIN_NEW = ["trainstep.dispatch_ms", "host.gc_pause_max_ms.train"]
BOTH_NEW = ["setup.trace_lower_s", "setup.compile_or_cache_s"]


@pytest.mark.parametrize("name", SERVE_NEW + TRAIN_NEW + BOTH_NEW)
def test_entry_has_reader_source_and_cells(name):
    entry = {m["name"]: m for m in manifest()["per_layer"]}[name]
    want = "program_counter" if name.endswith("useful_share") \
        else "program_span"
    assert entry["source"] == want
    cells = ([SERVE_CELL] if name in SERVE_NEW else []) + \
        ([TRAIN_CELL] if name in TRAIN_NEW else [])
    # a later cell may be listed too; these have to be
    assert set(cells or [SERVE_CELL, TRAIN_CELL]) <= set(entry["workloads"])
    read = R.load_reader(ROOT, name)
    assert callable(read)
    # a program with nothing to read: nothing, not an error
    empty = readers.Ctx(run={"kind": "closed_loop", "t_open": 0.0,
                             "t_close": 0.0, "steps": []},
                        cfg={}, mix={}, cell={}, chips=1, peaks=None)
    if want == "program_span":
        assert read(empty) is None


def _finite(x):
    if isinstance(x, dict):
        return all(_finite(v) for v in x.values())
    return x is None or not isinstance(x, float) or math.isfinite(x)


@pytest.fixture(scope="module")
def rehearsed():
    out = {}
    for mix, like in ((CLOSED, SERVE_CELL), (TRAIN, TRAIN_CELL)):
        res = R.run_cell(tiny_cell(mix, like), SEED, 1.5, True,
                         require_chip=False)
        assert res["correct"] is True, res["compared"]
        out[like] = res["metrics"]
    return out


@pytest.mark.parametrize("name", SERVE_NEW + TRAIN_NEW + BOTH_NEW)
def test_rehearsal_gives_a_finite_value_or_nothing(rehearsed, name):
    cells = ([SERVE_CELL] if name in SERVE_NEW else
             [TRAIN_CELL] if name in TRAIN_NEW else [SERVE_CELL, TRAIN_CELL])
    for cell in cells:
        got = rehearsed[cell].get(name)
        if name == "engine.gap_named_share":
            assert got is None          # no device plane on the CPU
            continue
        assert got is not None, (cell, name)
        assert _finite(got) and got["value"] >= 0, got
    other = TRAIN_CELL if name in SERVE_NEW else SERVE_CELL
    if name not in BOTH_NEW:
        assert name not in rehearsed[other]


def test_rehearsal_parts_add_up_to_the_host(rehearsed):
    m = rehearsed[SERVE_CELL]
    host = m["engine.host_self_ms_per_step"]
    parts = [m[f"engine.{p}_ms_per_step"]["value"] for p in
             ("sched", "decode_build", "decode_checks", "decode_commit")]
    assert sum(parts) + host["by_span"]["prefill"] == pytest.approx(
        host["value"], rel=1e-9)
    assert set(host["by_span"]) == set(PS.PARTS)
    assert 0 < m["engine.kv_read_useful_share"]["value"] <= 100
    assert 0 < m["engine.prefill_useful_share"]["value"] <= 100
    # a token is committed before the step that made it returns
    assert m["engine.ttft_commit_p95_ms"]["value"] > 0
    d = rehearsed[TRAIN_CELL]["trainstep.dispatch_ms"]
    assert {"h2d", "checks", "device", "end", "self"} <= set(d["by_span"])
    assert sum(d["by_span"].values()) == pytest.approx(d["value"], rel=1e-6)


# -- the recorded trace with a ring made up for it ---------------------------

OFFSET = 1234.5          # trace clock = ring clock + OFFSET (seconds)
JITTER = [0.0, 3e-6, -2e-6, 0.0, 1e-6]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    src = os.path.join(ROOT, "benchmark/testdata/serve_2layer.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("xplane") / "serve_2layer.xplane.pb"
    with gzip.open(src, "rb") as f:
        dst.write_bytes(f.read())
    return TR.load(str(dst))


def _ns(t):
    return int(round((t - OFFSET) * 1e9))


def _ctx_and_ring(recorded, known_gap=None):
    """Five ``serve/step`` spans where the trace has its five
    ``bench.engine_step`` spans, each cut in two leaves at its middle; with
    ``known_gap`` a sixth span lies exactly over that device gap."""
    steps = [s for s in recorded.spans if s.name == "bench.engine_step"]
    win = (recorded.spans[0].start, recorded.spans[-1].end)
    ring, traced, nid = [], [], 1
    for s, j in zip(steps, JITTER):
        traced.append({"t0": s.start - OFFSET - j, "t1": s.end - OFFSET})
        a, b = _ns(s.start), _ns(s.end)
        mid = (a + b) // 2
        root = {"name": "serve/step", "id": nid, "parent": None, "tid": 7,
                "t0_ns": a, "dur_ns": b - a}
        ring += [root,
                 dict(root, name="serve/first", id=nid + 1, parent=nid,
                      dur_ns=mid - a),
                 dict(root, name="serve/second", id=nid + 2, parent=nid,
                      t0_ns=mid, dur_ns=b - mid)]
        nid += 3
    if known_gap is not None:
        a, b = _ns(known_gap[0]), _ns(known_gap[1])
        ring.append({"name": "serve/submit", "id": nid, "parent": None,
                     "tid": 7, "t0_ns": a, "dur_ns": b - a})
    run = {"kind": "closed_loop", "t_open": win[0] - OFFSET - 1.0,
           "t_close": win[1] - OFFSET + 1.0, "traced": {"steps": traced}}
    ctx = readers.Ctx(run=run, cfg={}, mix={}, cell={}, chips=1, peaks=None,
                      trace=recorded, win=win)
    return ctx, ring


def _gaps(recorded, win):
    busy = TR.union(TR.clip(TR.ivs(recorded.devices[0].ops), *win))
    return [g for g in TR.subtract([win], busy) if g[1] - g[0] >= PS.MIN_GAP]


def test_clock_offset_recovered_and_residual_reported(recorded, monkeypatch):
    ctx, ring = _ctx_and_ring(recorded)
    monkeypatch.setattr(PS, "_ring", lambda: ring)
    off, residual = PS.clock_offset(ctx)
    assert off == pytest.approx(OFFSET, abs=1e-9)
    assert residual == pytest.approx(3e-6, abs=1e-9)


def test_gaps_inside_the_steps_are_named_and_the_rest_is_not(recorded,
                                                             monkeypatch):
    ctx, ring = _ctx_and_ring(recorded)
    monkeypatch.setattr(PS, "_ring", lambda: ring)
    got = PS.gap_named(ctx)
    gaps = _gaps(recorded, ctx.win)
    steps = TR.union(TR.ivs(s for s in recorded.spans
                            if s.name == "bench.engine_step"))
    inside = sum(TR.total(TR.clip(steps, a, b)) for a, b in gaps)
    total = sum(b - a for a, b in gaps)
    assert got["idle_s"] == pytest.approx(total, rel=1e-6)
    assert got["value"] == pytest.approx(100.0 * inside / total, abs=1e-3)
    assert 50 < got["value"] < 100
    by = got["by_span"]
    assert by["serve/first"] + by["serve/second"] == pytest.approx(
        inside, rel=1e-5)
    assert by["_no_span_"] == pytest.approx(total - inside, rel=1e-4)
    assert got["clock_residual_us"] == pytest.approx(3.0, abs=1e-3)


def test_a_gap_under_a_known_span_goes_to_it(recorded, monkeypatch):
    win = (recorded.spans[0].start, recorded.spans[-1].end)
    steps = TR.union(TR.ivs(s for s in recorded.spans
                            if s.name == "bench.engine_step"))
    # the longest stretch of idle device that no engine step covers
    outside = TR.subtract(_gaps(recorded, win), steps)
    gap = max(outside, key=lambda g: g[1] - g[0])
    plain_ctx, plain_ring = _ctx_and_ring(recorded)
    monkeypatch.setattr(PS, "_ring", lambda: plain_ring)
    before = PS.gap_named(plain_ctx)
    ctx, ring = _ctx_and_ring(recorded, known_gap=gap)
    monkeypatch.setattr(PS, "_ring", lambda: ring)
    got = PS.gap_named(ctx)
    assert got["by_span"]["serve/submit"] == pytest.approx(
        gap[1] - gap[0], rel=1e-4)
    assert got["value"] > before["value"]


def test_window_cut_self_time_and_counters(monkeypatch):
    """The serving window cuts the ring by the driver's own clock; a step's
    parts follow the span names; a counter that the program lacks reads as
    nothing."""
    def rec(name, nid, parent, t0, dur):
        return {"name": name, "id": nid, "parent": parent, "tid": 1,
                "t0_ns": t0, "dur_ns": dur}
    ms = 1_000_000
    ring = [
        rec("jit/trace", 90, None, 0, 40 * ms),
        rec("jit/trace", 91, None, 10 * ms, 10 * ms),      # nested: once
        rec("jit/lower", 92, None, 50 * ms, 5 * ms),
        rec("jit/compile", 93, None, 60 * ms, 30 * ms),
        rec("serve/step", 1, None, 100 * ms, 100 * ms),    # before the window
        rec("serve/step", 2, None, 1000 * ms, 100 * ms),
        rec("serve/expire_shed", 3, 2, 1001 * ms, 1 * ms),
        rec("serve/admit", 4, 2, 1002 * ms, 20 * ms),
        rec("serve/prefill", 5, 4, 1003 * ms, 18 * ms),
        rec("serve/prefill/wait", 6, 5, 1005 * ms, 14 * ms),
        rec("serve/ensure_blocks", 7, 2, 1022 * ms, 1 * ms),
        rec("serve/decode", 8, 2, 1024 * ms, 72 * ms),
        rec("serve/decode/build", 9, 8, 1024 * ms, 2 * ms),
        rec("serve/decode/checks", 10, 8, 1026 * ms, 1 * ms),
        rec("serve/decode/launch", 11, 8, 1027 * ms, 1 * ms),
        rec("serve/decode/wait", 12, 8, 1028 * ms, 60 * ms),
        rec("serve/decode/commit", 13, 8, 1088 * ms, 6 * ms),
        rec("host/gc", 14, 13, 1089 * ms, 3 * ms),
        rec("serve/gauges", 15, 2, 1097 * ms, 2 * ms),
    ]
    monkeypatch.setattr(PS, "_ring", lambda: ring)
    ctx = readers.Ctx(run={"kind": "closed_loop", "t_open": 0.5,
                           "t_close": 2.0}, cfg={}, mix={}, cell={}, chips=1,
                      peaks=None)
    acc = PS.load(ctx)
    assert [r["id"] for r in acc.roots] == [2]
    parts = PS.step_parts(acc, acc.roots[0])
    assert parts["host_self"] == pytest.approx(100 - 14 - 60)
    assert parts["prefill"] == pytest.approx(4)
    assert parts["decode_build"] == pytest.approx(2 + 1 + 2)   # + own time
    assert parts["decode_checks"] == pytest.approx(1)
    assert parts["decode_commit"] == pytest.approx(6)
    assert parts["sched"] == pytest.approx(4 + 1 + 2 + 1 + 2)
    assert sum(parts[p] for p in PS.PARTS) == pytest.approx(
        parts["host_self"])
    got = PS.per_step_ms(ctx, "host_self", by=PS.PARTS)
    assert got["value"] == pytest.approx(26) and got["steps"] == 1
    assert "traced_value" not in got
    assert PS.gc_pause(ctx) == {"value": 3.0, "count": 1, "total_ms": 3.0}
    assert PS.setup_seconds(ctx, ("jit/trace", "jit/lower")) == {
        "value": pytest.approx(0.045), "events": 3}
    assert PS.setup_seconds(ctx, ("jit/compile",))["value"] == \
        pytest.approx(0.030)
    assert PS.counter("no.such.family", kind="x") is None
    assert PS.share(None, 5) is None and PS.share(2, 8)["value"] == 25.0


def test_self_time_is_the_benchmarks_own_and_agrees_with_the_programs():
    from paddle_tpu.observability import trace
    def rec(i, parent, t0, dur):
        return {"name": f"s{i}", "id": i, "parent": parent, "tid": 1,
                "t0_ns": t0, "dur_ns": dur}
    # a root with two overlapping children, one with a child of its own,
    # and a span whose parent the ring has evicted
    ring = [rec(1, None, 0, 100), rec(2, 1, 10, 40), rec(3, 1, 30, 30),
            rec(4, 2, 15, 5), rec(5, 77, 200, 9)]
    kids = {}
    for r in ring:
        if r["parent"] is not None:
            kids.setdefault(r["parent"], []).append(r)
    got = PS.self_times(ring, kids)
    assert got == {1: 50, 2: 35, 3: 30, 4: 5, 5: 9}
    assert got == trace.self_times(ring)
    import inspect
    assert "paddle_tpu" not in inspect.getsource(PS.self_times)


def test_first_token_from_commit_stamps(monkeypatch):
    ms = 1_000_000
    recs = [{"t_submit_ns": (1000 + i) * ms,
             "token_t_ns": [(1000 + i + 10 + i % 5) * ms,
                            (1000 + i + 10 + i % 5 + 80) * ms]}
            for i in range(40)]
    recs.append({"t_submit_ns": 10 * ms, "token_t_ns": [20 * ms]})  # before
    monkeypatch.setattr(PS, "request_records", lambda: recs)
    ctx = readers.Ctx(run={"kind": "closed_loop", "t_open": 0.5,
                           "t_close": 2.0}, cfg={}, mix={}, cell={}, chips=1,
                      peaks=None)
    got = PS.first_token(ctx)
    assert got == {"value": pytest.approx(14.0), "samples": 40,
                   "second_token_gap_p50_ms": pytest.approx(80.0)}
    monkeypatch.setattr(PS, "request_records", lambda: recs[:5])
    assert PS.first_token(ctx) is None            # under 20 samples
    ctx.run["kind"] = "train_steps"
    assert PS.first_token(ctx) is None


def test_train_window_is_the_rings_last_steps(monkeypatch):
    ms = 1_000_000
    ring = []
    for i in range(6):                  # three checked steps, then the window
        t0 = (100 + 10 * i) * ms
        ring.append({"name": "step", "id": 10 * i + 1, "parent": None,
                     "tid": 1, "t0_ns": t0, "dur_ns": 4 * ms})
        for j, (name, dur) in enumerate((("step/h2d", 1), ("step/checks", 1),
                                         ("step/device", 1))):
            ring.append({"name": name, "id": 10 * i + 2 + j,
                         "parent": 10 * i + 1, "tid": 1,
                         "t0_ns": t0 + j * ms, "dur_ns": dur * ms})
    ring.append({"name": "jit/compile", "id": 99, "parent": 2, "tid": 1,
                 "t0_ns": 100 * ms, "dur_ns": 2 * ms})
    monkeypatch.setattr(PS, "_ring", lambda: ring)
    ctx = readers.Ctx(run={"kind": "train_steps", "steps": 3, "window_s": 1.0,
                           "traced": {"steps": 1, "window_s": 0.1}},
                      cfg={}, mix={}, cell={}, chips=1, peaks=None)
    got = PS.train_dispatch(ctx)
    assert got["steps"] == 2 and got["value"] == pytest.approx(4.0)
    assert got["traced_value"] == pytest.approx(4.0)
    assert got["by_span"] == {"self": pytest.approx(1.0),
                              "h2d": pytest.approx(1.0),
                              "checks": pytest.approx(1.0),
                              "device": pytest.approx(1.0)}
    assert PS.setup_seconds(ctx, ("jit/compile",))["value"] == \
        pytest.approx(0.002)
    ctx2 = readers.Ctx(run={"kind": "train_steps", "steps": 7,
                            "window_s": 1.0}, cfg={}, mix={}, cell={},
                       chips=1, peaks=None)
    assert PS.load(ctx2) is None        # the ring lost part of the window
