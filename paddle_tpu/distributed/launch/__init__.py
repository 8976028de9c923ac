"""Distributed launcher — ``python -m paddle_tpu.distributed.launch``.

Reference design: ``python/paddle/distributed/launch/main.py`` with
``Controller`` (``launch/controllers/controller.py:192``) building
Job/Pod/Container abstractions, exporting per-rank env, spawning local
trainer processes, tailing per-rank ``workerlog.N`` files and watching for
failures; rendezvous via an HTTP/ETCD master.

TPU-native design: JAX is multi-controller with one process per *host* (not
per device), and rendezvous is ``jax.distributed.initialize`` against a
coordinator address — so the launcher's job collapses to: pick/propagate the
coordinator endpoint, spawn one process per node-local replica with the
reference's env-var contract (``PADDLE_TRAINER_ID`` / ``PADDLE_TRAINERS_NUM``
/ ``PADDLE_MASTER`` / ``PADDLE_TRAINER_ENDPOINTS``), write per-rank logs, and
watch/propagate failures. ``init_parallel_env`` (env.py) consumes the same
contract on the trainer side.
"""

from __future__ import annotations

import glob
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

__all__ = ["LaunchConfig", "Container", "Pod", "launch", "free_port"]


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class LaunchConfig:
    """CLI surface (subset of ref launch/main.py relevant to collective
    training; PS-mode flags are N/A on TPU)."""
    nproc_per_node: int = 1
    nnodes: int = 1
    node_rank: int = 0
    master: Optional[str] = None          # host:port coordinator
    log_dir: Optional[str] = None
    envs: Dict[str, str] = field(default_factory=dict)


@dataclass
class Container:
    """One trainer process (ref launch/job/container.py)."""
    rank: int
    local_rank: int
    cmd: List[str]
    env: Dict[str, str]
    log_path: Optional[str] = None
    proc: Optional[subprocess.Popen] = None
    _log_f: Optional[object] = None

    def start(self):
        out = None
        if self.log_path:
            self._log_f = open(self.log_path, "w")
            out = self._log_f
        self.proc = subprocess.Popen(self.cmd, env=self.env, stdout=out,
                                     stderr=subprocess.STDOUT if out else None)

    def poll(self) -> Optional[int]:
        return self.proc.poll() if self.proc else None

    def terminate(self, grace: float = 5.0):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self._log_f:
            self._log_f.close()
            self._log_f = None


class Pod:
    """The node-local set of containers (ref launch/job/pod.py); `deploy` +
    `watch` mirror ControllerBase.run/watch."""

    def __init__(self, containers: Sequence[Container]):
        self.containers = list(containers)

    def deploy(self):
        for c in self.containers:
            c.start()

    def watch(self, poll_interval: float = 0.5) -> int:
        """Block until all containers exit cleanly or any fails; on failure
        terminate the rest and return its exit code."""
        try:
            while True:
                codes = [c.poll() for c in self.containers]
                bad = [rc for rc in codes if rc not in (None, 0)]
                if bad:
                    for c in self.containers:
                        c.terminate()
                    return bad[0]
                if all(rc == 0 for rc in codes):
                    return 0
                time.sleep(poll_interval)
        except KeyboardInterrupt:
            for c in self.containers:
                c.terminate()
            return 130

    def stop(self):
        for c in self.containers:
            c.terminate()


def local_tpu_chips() -> List[str]:
    """Device nodes of the TPU chips attached to this host. Read from
    /dev, never through JAX: a launcher that touched JAX would hold the
    chip its children need."""
    return sorted(glob.glob("/dev/accel[0-9]*")
                  + glob.glob("/dev/vfio/[0-9]*"))


def check_one_process_per_chip(nproc: int, env: Dict[str, str]) -> None:
    """Refuse N > 1 local processes that would all open this host's TPU.

    A chip belongs to one process at a time and nothing here gives each
    child a chip of its own, so N children would fail or hang on the
    device lock. On a TPU host one process drives every local chip
    through the mesh. Children whose environment pins the CPU platform
    (the chipless drills, the CPU test pods) are unaffected."""
    if nproc <= 1:
        return
    if env.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        return
    chips = local_tpu_chips()
    if chips:
        raise RuntimeError(
            f"{nproc} processes per node on a host with {len(chips)} TPU "
            "chip(s): a chip belongs to one process at a time, and one "
            "process drives all local chips (build the mesh over "
            "jax.devices() with create_hybrid_mesh). Launch one process "
            "per host, or set JAX_PLATFORMS=cpu for chipless workers.")


def build_pod(cfg: LaunchConfig, training_script: str,
              script_args: Sequence[str]) -> Pod:
    check_one_process_per_chip(cfg.nproc_per_node,
                               {**os.environ, **cfg.envs})
    world = cfg.nnodes * cfg.nproc_per_node
    master = cfg.master
    if world > 1 and not master:
        if cfg.nnodes > 1:
            raise ValueError("--master host:port is required for multi-node")
        master = f"127.0.0.1:{free_port()}"
    endpoints = [f"127.0.0.1:{free_port()}" for _ in range(cfg.nproc_per_node)]

    containers = []
    for lr in range(cfg.nproc_per_node):
        rank = cfg.node_rank * cfg.nproc_per_node + lr
        env = dict(os.environ)
        env.update(cfg.envs)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_LOCAL_RANK": str(lr),
            "PADDLE_CURRENT_ENDPOINT": endpoints[lr],
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        })
        if master:
            env["PADDLE_MASTER"] = master
        cmd = [sys.executable, "-u", training_script, *script_args]
        log_path = None
        if cfg.log_dir:
            os.makedirs(cfg.log_dir, exist_ok=True)
            log_path = os.path.join(cfg.log_dir, f"workerlog.{rank}")
            # rank-aware get_logger() in the trainee tees here too
            env["PADDLE_LOG_DIR"] = cfg.log_dir
        containers.append(Container(rank=rank, local_rank=lr, cmd=cmd,
                                    env=env, log_path=log_path))
    return Pod(containers)


def launch(cfg: LaunchConfig, training_script: str,
           script_args: Sequence[str] = (),
           max_restarts: int = 0, elastic_dir: Optional[str] = None) -> int:
    if max_restarts > 0 or elastic_dir:
        from ..fleet.elastic import ElasticManager, FileHeartbeatStore
        store = FileHeartbeatStore(elastic_dir) if elastic_dir else None
        mgr = ElasticManager(
            pod_factory=lambda: build_pod(cfg, training_script, script_args),
            pod_id=str(cfg.node_rank), store=store,
            max_restarts=max_restarts)
        return mgr.run()
    pod = build_pod(cfg, training_script, script_args)
    pod.deploy()
    return pod.watch()
