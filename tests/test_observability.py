"""Runtime telemetry subsystem tests (paddle_tpu/observability/).

Covers the metrics registry (+ the profiler.monitor forwarding shim), the
span tracer, the StepTimeline phases, the recompile sentinel (churn ->
exactly one Diagnostic with the shape diff; stable -> none;
FLAGS_telemetry=off bitwise non-intrusive on TrainStep outputs), HBM
watermarks vs the static plan, the graceful-degrade path of
profiler/statistic.device_statistics, the hapi StatsReporter wiring, and
the tools/trace_view.py aggregation."""

import json
import logging
import os
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import flags as core_flags
from paddle_tpu.observability import metrics, step_monitor, trace


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Default mode, fresh timeline + span ring per test; metric values
    reset (families persist — they are process-global by design)."""
    prev = core_flags.get_flags(["telemetry"])
    core_flags.set_flags({"telemetry": "metrics"})
    step_monitor.reset_default()
    trace.clear()
    metrics.reset_all()
    yield
    core_flags.set_flags(prev)
    step_monitor.reset_default()
    trace.clear()


def _mode(m):
    core_flags.set_flags({"telemetry": m})


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram_basics(self):
        c = metrics.counter("t.c", "help text")
        c.inc()
        c.labels(kind="a").inc(3)
        assert c.labels().get() == 1
        assert c.labels(kind="a").get() == 3
        g = metrics.gauge("t.g")
        g.set(2.5)
        g.add(0.5)
        assert g.get() == 3.0
        h = metrics.histogram("t.h")
        for v in (0.001, 1.0, 1000.0):
            h.observe(v)
        snap = h.get()
        assert snap["count"] == 3
        assert snap["max"] == 1000.0
        assert abs(snap["sum"] - 1001.001) < 1e-9

    def test_histogram_buckets_are_fixed_log_scale(self):
        b = metrics.DEFAULT_BUCKETS
        assert b == tuple(sorted(b))
        ratios = {round(b[i + 1] / b[i], 6) for i in range(len(b) - 1)}
        assert ratios == {2.0}  # one bucket per octave, deterministic
        h = metrics.histogram("t.hb").labels()
        h.observe(3.0)  # lands in the le=4.0 bucket
        cum = dict(h.cumulative())
        assert cum[4.0] == 1
        assert cum[2.0] == 0
        assert cum[float("inf")] == 1

    def test_kind_collision_rejected(self):
        metrics.counter("t.kind")
        with pytest.raises(ValueError):
            metrics.gauge("t.kind")

    def test_prometheus_text_and_snapshot(self):
        metrics.counter("t.prom.events").labels(phase="h2d").inc(2)
        metrics.histogram("t.prom.ms").observe(5.0)
        text = metrics.prometheus_text()
        assert 't_prom_events{phase="h2d"} 2' in text
        assert "# TYPE t_prom_ms histogram" in text
        assert "t_prom_ms_count" in text
        snap = metrics.snapshot()
        assert snap["t.prom.events"]["type"] == "counter"
        assert snap["t.prom.ms"]["series"][0]["value"]["count"] == 1
        json.dumps(snap)  # snapshot must be JSON-able

    def test_monitor_shim_shares_registry(self):
        from paddle_tpu.profiler import monitor
        monitor.stat_add("t.shim", 4)
        monitor.stat("t.shim").add(1)
        assert monitor.stat_get("t.shim") == 5
        assert metrics.stats_snapshot()["t.shim"] == 5
        # labeled series flatten with their label string
        metrics.gauge("t.shim2").labels(rank="3").set(7)
        snap = monitor.stats_snapshot()
        assert snap['t.shim2{rank="3"}'] == 7
        monitor.stats_reset()
        assert monitor.stat_get("t.shim") == 0

    def test_thread_safety(self):
        c = metrics.counter("t.race").labels()

        def bump():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=bump) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.get() == 8000


class TestMetricsExposition:
    """Prometheus escaping, +Inf exposition, and label-child GC — the
    surfaces the live fleet plane leans on."""

    def test_hostile_label_values_escape(self):
        hostile = 'a"b\\c\nd'
        metrics.counter("t.esc").labels(path=hostile).inc()
        text = metrics.prometheus_text()
        assert 't_esc{path="a\\"b\\\\c\\nd"} 1' in text
        # a raw newline inside a label value would split the sample line
        for line in text.splitlines():
            if line.startswith("t_esc{"):
                assert line.endswith("} 1")

    def test_histogram_exposes_explicit_inf_bucket(self):
        h = metrics.histogram("t.inf", buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(50.0)  # only the +Inf slot sees this one
        text = metrics.prometheus_text()
        assert 't_inf_bucket{le="1.0"} 1' in text
        assert 't_inf_bucket{le="2.0"} 1' in text
        assert 't_inf_bucket{le="+Inf"} 2' in text
        assert "t_inf_count 2" in text
        raw = h.labels().bucket_counts()
        assert raw["le"] == [1.0, 2.0]
        assert raw["counts"] == [1, 0, 1]  # trailing +Inf overflow slot

    def test_family_remove_and_expire(self):
        c = metrics.counter("t.gc")
        c.labels(worker="a").inc(1)
        c.labels(worker="b").inc(2)
        assert c.remove(worker="a")
        assert not c.remove(worker="a")  # second removal: nothing there
        assert 'worker="a"' not in metrics.prometheus_text()
        assert c.labels(worker="b").get() == 2
        # a removed child re-created starts from zero
        c.labels(worker="a").inc()
        assert c.labels(worker="a").get() == 1
        assert c.expire(lambda labels: labels.get("worker") == "b") == 1
        assert 'worker="b"' not in metrics.prometheus_text()

    def test_registry_expire_sweeps_by_name_and_labels(self):
        reg = metrics.Registry()
        reg.gauge("fleet.worker.step").labels(worker="x").set(1)
        reg.gauge("fleet.worker.step").labels(worker="y").set(2)
        reg.gauge("other.g").labels(worker="x").set(3)
        n = reg.expire(lambda name, labels:
                       name.startswith("fleet.") and
                       labels.get("worker") == "x")
        assert n == 1
        text = reg.prometheus_text()
        assert 'fleet_worker_step{worker="y"} 2' in text
        assert 'fleet_worker_step{worker="x"}' not in text
        assert 'other_g{worker="x"} 3' in text  # untouched family

    def test_snapshot_include_buckets(self):
        metrics.histogram("t.snapb", buckets=(1.0,)).observe(0.5)
        lean = metrics.snapshot()
        assert "buckets" not in lean["t.snapb"]["series"][0]
        full = metrics.snapshot(include_buckets=True)
        b = full["t.snapb"]["series"][0]["buckets"]
        assert b["le"] == [1.0] and b["counts"] == [1, 0]
        json.dumps(full)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

class TestTrace:
    def test_spans_only_under_trace_mode(self):
        """The contract since ISSUE 26: spans record under the default
        ``metrics`` mode too; ``off`` hands out the shared no-op and
        leaves the ring empty; ``trace`` adds only the open-span table."""
        _mode("off")
        quiet = trace.span("quiet")
        assert quiet is trace.span("quiet too")     # one shared object
        with quiet:
            pass
        assert trace.spans() == []
        _mode("metrics")
        with trace.span("outer", step=1):
            held = trace.span("inner")
            held.__enter__()
            assert trace.open_spans() == []         # no table in metrics
            held.__exit__(None, None, None)
        got = trace.spans()
        names = [s["name"] for s in got]
        assert names == ["inner", "outer"]  # completion order
        by = {s["name"]: s for s in got}
        assert by["outer"]["depth"] == 0
        assert by["inner"]["depth"] == 1
        assert by["outer"]["attrs"] == {"step": 1}
        assert by["outer"]["dur_ns"] >= by["inner"]["dur_ns"]
        _mode("trace")
        with trace.span("traced"):
            assert [s["name"] for s in trace.open_spans()] == ["traced"]
        assert trace.spans()[-1]["name"] == "traced"

    def test_chrome_and_jsonl_export(self, tmp_path):
        _mode("trace")
        with trace.span("a"):
            pass
        chrome = tmp_path / "t.json"
        n = trace.export_chrome_trace(str(chrome))
        assert n == 1
        data = json.loads(chrome.read_text())
        ev = data["traceEvents"][0]
        assert ev["name"] == "a" and ev["ph"] == "X"
        jl = tmp_path / "t.jsonl"
        assert trace.export_jsonl(str(jl)) == 1
        rec = json.loads(jl.read_text().strip())
        assert rec["kind"] == "span" and rec["name"] == "a"

    def test_open_span_exports_as_incomplete(self, tmp_path):
        """Regression (ISSUE 15 satellite): a span still open at export
        time — the signature of a hang — must be emitted flagged
        ``incomplete`` with end = export time, not silently dropped."""
        _mode("trace")
        hung = trace.span("possibly/hung", step=7)
        hung.__enter__()  # deliberately never exited before export
        with trace.span("done"):
            pass
        jl = tmp_path / "t.jsonl"
        assert trace.export_jsonl(str(jl)) == 2
        recs = [json.loads(line) for line in
                jl.read_text().strip().splitlines()]
        by = {r["name"]: r for r in recs}
        assert "incomplete" not in by["done"]
        inc = by["possibly/hung"]
        assert inc["incomplete"] is True
        assert inc["dur_ns"] >= 0 and inc["attrs"] == {"step": 7}
        # chrome export carries the flag through args
        chrome = tmp_path / "t.json"
        assert trace.export_chrome_trace(str(chrome)) == 2
        evs = {e["name"]: e
               for e in json.loads(chrome.read_text())["traceEvents"]}
        assert evs["possibly/hung"]["args"]["incomplete"] is True
        # closing it afterwards records ONE completed span, no longer
        # double-reported as open
        hung.__exit__(None, None, None)
        assert trace.open_spans() == []
        names = [s["name"] for s in trace.spans()]
        assert names.count("possibly/hung") == 1


# ---------------------------------------------------------------------------
# StepTimeline
# ---------------------------------------------------------------------------

class TestStepTimeline:
    def test_phases_accumulate_into_step_records(self):
        tl = step_monitor.StepTimeline()
        with tl.step():
            with tl.phase("h2d"):
                pass
            with tl.phase("device"):
                pass
            with tl.phase("device"):
                pass
        steps = tl.steps()
        assert len(steps) == 1
        assert set(steps[0]["phases"]) == {"h2d", "device"}
        assert steps[0]["total_ms"] >= steps[0]["phases"]["device"]
        summary = tl.summary()
        assert summary["steps"] == 1
        assert summary["phases"]["device"]["calls"] == 1  # accumulated
        assert summary["phases"]["device"]["total_ms"] > 0

    def test_off_mode_records_nothing(self):
        _mode("off")
        tl = step_monitor.StepTimeline()
        with tl.step():
            with tl.phase("device"):
                pass
        assert tl.steps() == []

    def test_export_jsonl_roundtrip_via_trace_view(self, tmp_path):
        tl = step_monitor.StepTimeline()
        for _ in range(8):
            with tl.step():
                with tl.phase("device"):
                    pass
        path = tmp_path / "steps.jsonl"
        assert tl.export_jsonl(str(path)) == 8
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
        from tools import trace_view
        steps, spans = trace_view.load_jsonl(str(path))
        assert len(steps) == 8 and spans == []
        table = trace_view.phase_table(steps, spans)
        assert table[0]["phase"] == "device"
        assert table[0]["calls"] == 8

    def test_trace_view_flags_step_anomalies(self, tmp_path):
        from tools import trace_view
        steps = [{"kind": "step", "step": i, "phases": {"device": 1.0},
                  "total_ms": 1.0} for i in range(1, 20)]
        steps[12]["total_ms"] = 10.0  # 10x the rolling median
        anomalies = trace_view.find_anomalies(steps, factor=3.0, window=8)
        assert [a["step"] for a in anomalies] == [13]
        assert anomalies[0]["slowdown_x"] == 10.0
        # early steps are never flagged (compile warm-up)
        steps[0]["total_ms"] = 50.0
        assert [a["step"] for a in
                trace_view.find_anomalies(steps)] == [13]
        # CLI end-to-end
        p = tmp_path / "s.jsonl"
        p.write_text("\n".join(json.dumps(s) for s in steps))
        assert trace_view.main([str(p), "--json"]) == 0
        assert trace_view.main([str(p), "--fail-on-anomaly"]) == 1


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------

def _tiny_train_step():
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


def _batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, 8)).astype(np.float32),
            rng.integers(0, 4, (n,)).astype(np.int64))


class TestRecompileSentinel:
    def test_shape_churn_fires_exactly_one_diagnostic_with_diff(self):
        tl = step_monitor.reset_default()
        ts = _tiny_train_step()
        for n in (8, 16, 24, 32, 40):  # 5 distinct batch signatures
            ts.step(_batch(n))
        diags = tl.sentinel.diagnostics
        assert len(diags) == 1  # fired once per callable, not per churn
        d = diags[0]
        assert d.rule == "O001" and d.severity == "warning"
        assert d.where == "sharded.TrainStep"
        # the diff names the leaf-level shape change that caused firing:
        # threshold 2 -> fires at the 3rd distinct signature, 16 -> 24
        assert "float32[16,8]" in d.message and "float32[24,8]" in d.message

    def test_stable_shapes_fire_nothing(self):
        tl = step_monitor.reset_default()
        ts = _tiny_train_step()
        for _ in range(6):
            ts.step(_batch(8))
        assert tl.sentinel.diagnostics == []
        # one compile observed, the rest hit the fast-fingerprint cache
        assert metrics.counter("telemetry.compiles").labels(
            fn="sharded.TrainStep").get() == 1

    def test_instrumented_jitted_callable_churn(self):
        tl = step_monitor.StepTimeline(recompile_threshold=2)
        f = step_monitor.instrument_jitted(
            jax.jit(lambda x: x * 2), name="dbl", timeline=tl)
        for n in (3, 4, 5):
            f(jnp.ones((n,)))
        assert len(tl.sentinel.diagnostics) == 1
        assert "dbl" in tl.sentinel.diagnostics[0].where
        # signature replay stays quiet after firing
        f(jnp.ones((3,)))
        assert len(tl.sentinel.diagnostics) == 1

    def test_instrument_jitted_preserves_aot_surface(self):
        jitted = jax.jit(lambda x: x + 1)
        f = step_monitor.instrument_jitted(jitted, name="inc")
        assert hasattr(f, "lower")
        cost = f.lower(jnp.ones((4,))).compile()
        assert cost is not None
        np.testing.assert_array_equal(np.asarray(f(jnp.ones((4,)))),
                                      np.full((4,), 2.0, np.float32))

    def test_fingerprint_diff_reports_dtype_change(self):
        a = step_monitor.fingerprint(jnp.ones((4,), jnp.float32))
        b = step_monitor.fingerprint(jnp.ones((4,), jnp.int32))
        diff = step_monitor.fingerprint_diff(a, b)
        assert "float32[4]" in diff and "int32[4]" in diff


class TestTelemetryOffBitwise:
    def test_off_mode_is_bitwise_nonintrusive_on_trainstep(self):
        results = {}
        for mode in ("off", "metrics"):
            _mode(mode)
            step_monitor.reset_default()
            ts = _tiny_train_step()
            losses = [np.asarray(ts.step(_batch(8, seed=s)))
                      for s in range(3)]
            results[mode] = (losses,
                             {k: np.asarray(v) for k, v in ts.params.items()})
        for a, b in zip(results["off"][0], results["metrics"][0]):
            np.testing.assert_array_equal(a, b)
        for k in results["off"][1]:
            np.testing.assert_array_equal(results["off"][1][k],
                                          results["metrics"][1][k])


# ---------------------------------------------------------------------------
# HBM watermarks
# ---------------------------------------------------------------------------

class _FakeDev:
    def __init__(self, live, peak):
        self._stats = {"bytes_in_use": live, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


class TestHbmWatermarks:
    def test_sample_and_peak_tracking(self):
        GB = step_monitor.GB
        tl = step_monitor.StepTimeline(device=_FakeDev(int(2 * GB),
                                                       int(3 * GB)))
        with tl.step():
            pass
        assert tl.hbm_peak_bytes == int(3 * GB)
        assert tl.steps()[0]["hbm_peak_gb"] == 3.0
        assert metrics.gauge("hbm.bytes_in_use").get() == int(2 * GB)

    def test_cpu_runtime_degrades_to_none(self):
        tl = step_monitor.StepTimeline()  # real CPU device: no stats
        assert tl.sample_hbm() is None
        with tl.step():
            pass
        assert "hbm_peak_gb" not in tl.steps()[0]

    def test_check_plan_cross_checks_static_budget(self):
        GB = step_monitor.GB
        tl = step_monitor.StepTimeline(device=_FakeDev(int(10 * GB),
                                                       int(12 * GB)))
        tl.sample_hbm()
        # generous plan: no finding
        assert tl.check_plan({"device_gb": 14.0}) is None
        # plan says 8 GB, measured peak 12 GB -> O002
        d = tl.check_plan({"device_gb": 8.0})
        assert d is not None and d.rule == "O002"
        assert "12.00 GB" in d.message and "8.00 GB" in d.message
        assert d in tl.all_diagnostics()

    def test_check_plan_against_real_hbm_budget_plan(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir))
        from tools import hbm_budget
        # L24 offloaded Adam fits at batch 2 (hbm_budget's validated point)
        plan = hbm_budget.gpt_plan(layers=24, offload="moments", batch=2)
        assert plan["fits"]
        GB = step_monitor.GB
        tl = step_monitor.StepTimeline(
            device=_FakeDev(int(plan["device_gb"] * GB),
                            int((plan["device_gb"] + 3) * GB)))
        tl.sample_hbm()
        assert tl.check_plan(plan) is not None  # 3 GB over the plan


# ---------------------------------------------------------------------------
# satellite: device_statistics graceful degrade
# ---------------------------------------------------------------------------

class TestDeviceStatisticsGraceful:
    def test_missing_log_dir_returns_none_with_diagnostic(self, tmp_path):
        from paddle_tpu.profiler.statistic import device_statistics
        diags = []
        assert device_statistics(str(tmp_path / "nope"),
                                 diagnostics=diags) is None
        # either "no parser" (bare env) or "missing dir" (parser present):
        # both degrade with an O003 diagnostic instead of raising
        assert len(diags) == 1 and diags[0].rule == "O003"

    def test_unparseable_xplane_returns_none_not_raise(self, tmp_path,
                                                       monkeypatch):
        # a parser whose import works but whose parse blows up — the shape
        # of the real tensorboard_plugin_profile ABI drift
        fake_rtd = types.ModuleType("raw_to_tool_data")

        def boom(*a, **k):
            raise RuntimeError("corrupt xplane payload")

        fake_rtd.xspace_to_tool_data = boom
        fake_conv = types.ModuleType("xprof.convert")
        fake_conv.raw_to_tool_data = fake_rtd
        fake_root = types.ModuleType("xprof")
        fake_root.convert = fake_conv
        monkeypatch.setitem(sys.modules, "xprof", fake_root)
        monkeypatch.setitem(sys.modules, "xprof.convert", fake_conv)
        monkeypatch.setitem(sys.modules, "xprof.convert.raw_to_tool_data",
                            fake_rtd)
        sess = tmp_path / "plugins" / "profile" / "sess1"
        sess.mkdir(parents=True)
        (sess / "host.xplane.pb").write_bytes(b"\x00garbage\xff")
        from paddle_tpu.profiler.statistic import device_statistics
        diags = []
        assert device_statistics(str(tmp_path), diagnostics=diags) is None
        assert len(diags) == 1
        assert diags[0].rule == "O003" and diags[0].severity == "warning"
        assert "corrupt xplane payload" in diags[0].message

    def test_summary_report_survives_broken_parser(self, tmp_path,
                                                   monkeypatch):
        fake_rtd = types.ModuleType("raw_to_tool_data")
        fake_rtd.xspace_to_tool_data = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("nope"))
        fake_conv = types.ModuleType("xprof.convert")
        fake_conv.raw_to_tool_data = fake_rtd
        fake_root = types.ModuleType("xprof")
        fake_root.convert = fake_conv
        monkeypatch.setitem(sys.modules, "xprof", fake_root)
        monkeypatch.setitem(sys.modules, "xprof.convert", fake_conv)
        monkeypatch.setitem(sys.modules, "xprof.convert.raw_to_tool_data",
                            fake_rtd)
        sess = tmp_path / "plugins" / "profile" / "s"
        sess.mkdir(parents=True)
        (sess / "x.xplane.pb").write_bytes(b"junk")
        from paddle_tpu.profiler.statistic import summary_report
        rep = summary_report([0.01, 0.012], str(tmp_path))
        assert "Overview" in rep  # host views still render


# ---------------------------------------------------------------------------
# satellite: hapi StatsReporter wiring
# ---------------------------------------------------------------------------

class TestHapiStatsWiring:
    def test_config_callbacks_installs_stats_logger_behind_flag(self):
        from paddle_tpu.hapi.callbacks import (StatsLoggerCallback,
                                               config_callbacks)
        cl = config_callbacks()
        assert any(isinstance(c, StatsLoggerCallback) for c in cl.callbacks)
        _mode("off")
        cl = config_callbacks()
        assert not any(isinstance(c, StatsLoggerCallback)
                       for c in cl.callbacks)

    def test_fit_logs_epoch_stat_snapshot(self, caplog):
        from paddle_tpu.io import TensorDataset
        from paddle_tpu.profiler.monitor import get_logger

        rng = np.random.default_rng(0)
        ds = TensorDataset([rng.standard_normal((16, 4)).astype(np.float32),
                            rng.standard_normal((16, 1)).astype(np.float32)])
        net = nn.Linear(4, 1)
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=net.parameters()),
                  nn.MSELoss())
        log = get_logger("paddle_tpu.monitor")
        with caplog.at_level(logging.INFO, logger="paddle_tpu.monitor"):
            log.addHandler(caplog.handler)
            try:
                m.fit(ds, batch_size=8, epochs=1, verbose=0)
            finally:
                log.removeHandler(caplog.handler)
        assert any("stats" in r.message and "model.train_batches"
                   in r.getMessage() for r in caplog.records)
        # the fit loop fed the step timeline too
        assert step_monitor.current().summary()["steps"] >= 2


# ---------------------------------------------------------------------------
# profiler parity: old stat surface keeps working through the shim
# ---------------------------------------------------------------------------

def test_dataloader_data_phase_recorded():
    from paddle_tpu.io import DataLoader, TensorDataset
    ds = TensorDataset([np.zeros((16, 4), np.float32)])
    before = metrics.histogram("telemetry.phase_ms").labels(
        phase="data").get()["count"]
    list(DataLoader(ds, batch_size=4))
    after = metrics.histogram("telemetry.phase_ms").labels(
        phase="data").get()["count"]
    # 4 batches + the exhaustion probe (the final next() that ends the
    # epoch is real consumer wait too)
    assert after - before == 5
