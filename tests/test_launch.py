"""Launcher + real multi-process bootstrap tests (SURVEY §4: the analog of
the reference's TestDistBase (test_dist_base.py:962) localhost spawn tests).

Runs tests/dist_trainer_script.py through ``paddle_tpu.distributed.launch``
twice — one process with 8 virtual CPU devices, and two processes with 4
each rendezvousing over a real coordinator — and asserts loss parity.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "dist_trainer_script.py")


def _run_launch(nproc, local_devices, log_dir):
    env = dict(os.environ)
    env["TEST_LOCAL_DEVICES"] = str(local_devices)
    env.pop("XLA_FLAGS", None)  # trainer script sets its own device count
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc), "--log_dir", str(log_dir), SCRIPT]
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=600)
    logs = {}
    for rank in range(nproc):
        path = os.path.join(log_dir, f"workerlog.{rank}")
        assert os.path.exists(path), f"missing per-rank log {path}"
        with open(path) as f:
            logs[rank] = f.read()
    assert proc.returncode == 0, (proc.stdout, proc.stderr, logs)
    m = re.search(r"LOSSES (.*)", logs[0])
    assert m, f"rank0 printed no losses: {logs[0][-2000:]}"
    return json.loads(m.group(1))


def test_single_vs_two_process_loss_parity(tmp_path):
    one = _run_launch(1, 8, str(tmp_path / "one"))
    two = _run_launch(2, 4, str(tmp_path / "two"))
    assert one["world"] == 1 and two["world"] == 2
    assert one["rank"] == 0 and two["rank"] == 0
    np.testing.assert_allclose(one["losses"], two["losses"], rtol=1e-5)
    # training progressed
    assert two["losses"][-1] < two["losses"][0]


def test_launch_propagates_failure(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import sys; sys.exit(3)\n")
    env = dict(os.environ)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
           str(bad)]
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 3
