"""CPU rehearsals of one run per traffic kind at a tiny size: the result
object's keys, and that a run off the chip is never called a measurement
(the entry refuses; the rehearsal path names the CPU and leaves every device
metric out)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench_tiny import (CLOSED, OPEN, ROOT, SEED, SERVE_CELL, TRAIN,
                            TRAIN_CELL, manifest, tiny_cell)

from benchmark import run as R

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
CASES = {"train_steps": (TRAIN, TRAIN_CELL, 0),
         "closed_loop": (CLOSED, SERVE_CELL, 1),
         "open_loop": (OPEN, SERVE_CELL, 0)}


@pytest.mark.parametrize("kind", sorted(CASES))
def test_rehearsal_result_line(kind):
    mix, like, trace = CASES[kind]
    cell = tiny_cell(mix, like)
    res = R.run_cell(cell, SEED, 1.5, bool(trace), require_chip=False)
    json.dumps(res)                               # one JSON object
    assert KEYS <= set(res)
    assert list(res)[-1] == "compared"            # the comparison comes last
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    for row in res["compared"].values():
        assert set(row) == {"value", "limit"}
    by_name = {m["name"]: m for m in
               manifest()["end_to_end"] + manifest()["per_layer"]}
    for name, got in res["metrics"].items():
        assert by_name[name]["source"] != "device_trace", name
        assert got["unit"] == by_name[name]["unit"]
        assert got["value"] == got["value"]       # a number, not NaN
    for name in res["metrics"]:
        assert "roofline" not in name and "mfu" not in name
        assert "idle" not in name
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "engine.decode_batch_mean" in res["metrics"]
    else:
        assert "setup_s" in res["metrics"]
        assert len(res["metrics"]) >= 2
    if kind != "train_steps":
        assert res["compared"]["served_logit_gap_max"]["value"] is not None


def test_entry_refuses_without_an_accelerator():
    """The command as the driver runs it: on a machine where JAX finds no
    accelerator it exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", TRAIN_CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not out.stdout.strip()


def test_same_seed_same_traffic_other_seed_same_sizes():
    from benchmark.lib import traffic as T
    a = T.request_stream(CLOSED, 512, 2**31 + 5)
    b = T.request_stream(CLOSED, 512, 2**31 + 5)
    c = T.request_stream(CLOSED, 512, 6)
    n = CLOSED["n_sizes"]
    ra, rb, rc = ([next(s) for _ in range(n)] for s in (a, b, c))
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(ra, rb))
    sizes = lambda rs: sorted((len(p), o) for p, o in rs)
    assert sizes(ra) == sizes(rc) == sorted(T.size_set(CLOSED))
    assert [len(p) for p, _ in ra] != [len(p) for p, _ in rc]
    ga = T.arrival_gaps(OPEN, 3)
    gb = T.arrival_gaps(OPEN, 4)
    xa, xb = ([next(g) for _ in range(n)] for g in (ga, gb))
    assert sorted(xa) == pytest.approx(sorted(xb))
    assert sum(xa) / n == pytest.approx(1.0 / OPEN["rate_rps"])
    pool = T.train_batches(TRAIN, 512, 9)
    rows = {tuple(r) for ids, _ in pool for r in ids}
    assert len(rows) == TRAIN["pool"] * TRAIN["batch"]     # all rows differ
