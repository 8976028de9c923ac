"""Fault-injection tests for bench.py's anomaly guard (VERDICT r4 #1).

The round-4 driver capture recorded BERT at 0.048x of baseline from a
transient device stall; these tests prove the guard now discards such
windows, retries, and — when no clean window exists — marks the result
anomalous instead of presenting it as a clean measurement. The reference
gates the same class of failure in CI (tools/check_op_benchmark_result.py
rejects out-of-tolerance runs)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from bench import guarded_min, roofline_step_seconds  # noqa: E402


def make_window_fn(times):
    """A fake measurement source yielding the given per-step times."""
    it = iter(times)

    def window_fn():
        return next(it)

    return window_fn


class TestRoofline:
    def test_compute_bound(self):
        # 1e12 FLOPs at 2e12 FLOP/s = 0.5 s; memory side faster.
        t = roofline_step_seconds(1e12, 1e9, 2e12, 800e9)
        assert t == pytest.approx(0.5)

    def test_memory_bound(self):
        t = roofline_step_seconds(1e9, 80e9, 2e12, 800e9)
        assert t == pytest.approx(0.1)

    def test_unknown_cost_disables_guard(self):
        assert roofline_step_seconds(0.0, 0.0, 2e12, 800e9) == 0.0


class TestGuardedMin:
    def test_clean_windows_min(self):
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([0.12, 0.11, 0.13]), 3, roofline_s=0.05)
        assert best == pytest.approx(0.11)
        assert not anomaly
        assert valid == [0.12, 0.11, 0.13]
        assert disc == []

    def test_stalled_window_discarded_and_retried(self):
        # Window 2 is the round-4 pathology: a 25x-off stall. The
        # guard discards it (limit = 4 * 0.05 = 0.2 s) and measures an
        # extra window so three clean ones remain.
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([0.12, 2.9, 0.11, 0.13]), 3, roofline_s=0.05)
        assert best == pytest.approx(0.11)
        assert not anomaly
        assert len(valid) == 3
        assert disc == [2.9]

    def test_all_windows_stalled_marks_anomaly(self):
        # Persistent pathology: every window 25x off. The guard reports the
        # min but flags it untrustworthy — never a silent 0.048x record.
        times = [2.9, 3.1, 2.8, 3.0, 2.95, 3.2]
        best, anomaly, valid, disc = guarded_min(
            make_window_fn(times), 3, roofline_s=0.05)
        assert anomaly
        assert best == pytest.approx(2.8)
        assert valid == []
        assert len(disc) == 6  # n_windows + max_extra attempts, all logged

    def test_failed_windows_return_none(self):
        # Trace-parse failures (None) are skipped without counting as
        # anomalies; remaining attempts still produce a clean min.
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([None, 0.12, None, 0.11, 0.13]), 3,
            roofline_s=0.05)
        assert best == pytest.approx(0.11)
        assert not anomaly

    def test_nothing_measured(self):
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([None] * 6), 3, roofline_s=0.05)
        assert best is None
        assert anomaly

    def test_no_roofline_accepts_everything(self):
        # Unknown cost => guard disabled; min over raw windows (better than
        # refusing to measure, and the emitted record says roofline_ms=None).
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([0.12, 2.9, 0.11]), 3, roofline_s=0.0)
        assert best == pytest.approx(0.11)
        assert not anomaly
        assert disc == []

    def test_custom_factor(self):
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([0.12, 0.3, 0.11, 0.13]), 3, roofline_s=0.05,
            factor=5.0)  # limit 0.25: 0.3 out, 0.13 in
        assert disc == [0.3]
        assert not anomaly

    def test_window_budget_respected(self):
        # Only n_windows + max_extra attempts ever happen: the fake source
        # raises StopIteration if a 6th draw is attempted.
        best, anomaly, valid, disc = guarded_min(
            make_window_fn([0.12, 0.11] + [9.9] * 4), 4, roofline_s=0.05,
            max_extra=2)
        assert anomaly is False  # 2 valid < 4 wanted, but valid exist
        # With fewer valid windows than requested the guard still reports
        # the clean min — partial evidence beats a discarded-only min.
        assert best == pytest.approx(0.11)


class TestEndToEndSmoke:
    def test_bench_small_emits_guard_fields(self, tmp_path):
        """BENCH_SMALL path on CPU: the emitted JSON carries the guard
        fields (anomaly, windows, roofline_ms) for every config, and the
        run persists its BENCH_r<NN>.json snapshot (here redirected to a
        tmp dir so the test never dirties the repo)."""
        import json
        import subprocess

        # the CPU is in no peak table (bench raises on an unknown
        # device_kind), so this plumbing smoke names its peaks outright
        env = dict(os.environ, BENCH_SMALL="1", BENCH_CONFIGS="gpt",
                   JAX_PLATFORMS="cpu", BENCH_SNAPSHOT_DIR=str(tmp_path),
                   BENCH_PEAK_TFLOPS="197", BENCH_PEAK_HBM_GBS="819",
                   # the serve leg gates on CPU wall-clock ratios that do
                   # not repeat under load, and a failed leg now fails
                   # the run; the engine has its own tests
                   BENCH_SERVE="0",
                   BENCH_TRACE_OUT=str(tmp_path / "timeline.jsonl"))
        out = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          os.pardir, "bench.py")],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
        assert lines, out.stdout
        rec = json.loads(lines[-1])
        assert "anomaly" in rec["extra"]
        assert "windows" in rec["extra"]
        assert "roofline_ms" in rec["extra"]
        assert rec["extra"]["anomaly"] is False
        # the per-run snapshot landed (numbering scoped to the tmp dir:
        # empty -> r01) with the n/cmd/rc/tail/parsed shape, and its
        # headline record is the primary metric line printed last
        snap_path = tmp_path / "BENCH_r01.json"
        assert snap_path.exists(), list(tmp_path.iterdir())
        snap = json.loads(snap_path.read_text())
        assert set(snap) == {"n", "cmd", "rc", "tail", "parsed"}
        assert snap["n"] == 1 and snap["rc"] == 0
        assert snap["parsed"]["metric"] == rec["metric"]
        assert lines[-1] in snap["tail"]


class TestSnapshotNumbering:
    def test_next_n_from_committed_snapshots(self, tmp_path):
        """In a git checkout, NN derives from the last COMMITTED
        BENCH_r<NN> snapshot + 1 — reruns in a dirty tree must not walk
        the counter."""
        import subprocess

        from bench import _next_snapshot_n

        def git(*args):
            subprocess.run(["git", "-c", "user.name=t", "-c",
                            "user.email=t@t", *args], cwd=tmp_path,
                           check=True, capture_output=True)

        git("init", "-q")
        (tmp_path / "BENCH_r02.json").write_text("{}")
        (tmp_path / "BENCH_r04.json").write_text("{}")
        git("add", "BENCH_r02.json", "BENCH_r04.json")
        git("commit", "-q", "-m", "snapshots")
        # an uncommitted rerun's snapshot does not move the counter
        (tmp_path / "BENCH_r09.json").write_text("{}")
        assert _next_snapshot_n(str(tmp_path)) == 5

    def test_next_n_falls_back_to_directory_scan(self, tmp_path):
        from bench import _next_snapshot_n

        assert _next_snapshot_n(str(tmp_path)) == 1
        (tmp_path / "BENCH_r07.json").write_text("{}")
        (tmp_path / "BENCH_r03.json").write_text("{}")
        assert _next_snapshot_n(str(tmp_path)) == 8

    def test_write_snapshot_schema_and_parsed_line(self, tmp_path):
        import json

        from bench import _write_snapshot

        stdout = ('warmup noise\n'
                  '{"metric": "bert", "value": 1.0}\n'
                  '{"metric": "gpt", "value": 2.0}\n'
                  'not json trailer\n')
        path = _write_snapshot(str(tmp_path), stdout, 0, "python bench.py")
        snap = json.loads(open(path).read())
        assert os.path.basename(path) == "BENCH_r01.json"
        assert set(snap) == {"n", "cmd", "rc", "tail", "parsed"}
        assert snap["parsed"] == {"metric": "gpt", "value": 2.0}
        assert snap["tail"].endswith("not json trailer\n")


class TestFreshBatches:
    def test_measure_guarded_cycles_args_seq(self):
        """args_seq: every step (warmup included) consumes the NEXT batch
        from the pool — the de-memorized GPT probe (VERDICT r5 weak #3)."""
        import jax.numpy as jnp

        from bench import _measure_guarded

        seen = []

        def step(state, a):
            seen.append(int(a))
            return jnp.float32(0.0), state

        seq = [(i,) for i in range(5)]
        m = _measure_guarded(step, None, seq[0], steps=4, roofline_s=0.0,
                             n_windows=1, args_seq=seq)
        assert m["used_s"] is not None
        assert seen[:5] == [0, 1, 2, 3, 4]
        assert len(set(seen)) == 5  # the whole pool was visited

    def test_gpt_batches_distinct(self):
        from bench import _gpt_batches

        pool = _gpt_batches(2, 16, 64, pool=6)
        assert len(pool) == 6
        ids = [bytes(memoryview(b[0].tobytes())) for b in pool]
        assert len(set(ids)) == 6  # no repeated batch in the pool
