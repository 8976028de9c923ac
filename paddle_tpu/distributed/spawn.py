"""paddle.distributed.spawn parity.

Ref: ``python/paddle/distributed/spawn.py`` — start ``nprocs`` training
processes running ``func(*args)`` with the distributed env contract set per
rank, join them, and surface the first failure. Uses the multiprocessing
spawn context (fresh interpreters: no inherited accelerator runtime state,
the same reason the reference forces spawn for CUDA).
"""

from __future__ import annotations

import multiprocessing as mp
import os
from typing import Optional, Sequence

from .launch import check_one_process_per_chip, free_port

__all__ = ["spawn"]


def _entry(func, args, rank, nprocs, master, endpoints, env, queue):
    os.environ.update(env)
    os.environ.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
    })
    try:
        result = func(*args)
        # If the func used the global store, this process may be hosting it
        # for the others — synchronize teardown before exiting.
        from .store import finalize_global_store
        finalize_global_store()
        queue.put((rank, "ok", result))
    except BaseException as e:  # surface the traceback to the parent
        import traceback
        queue.put((rank, "error",
                   "".join(traceback.format_exception(type(e), e,
                                                      e.__traceback__))))
        raise


def spawn(func, args: Sequence = (), nprocs: int = 1, join: bool = True,
          daemon: bool = False, **options):
    """Launch ``nprocs`` processes running ``func(*args)``.

    Returns the context (list of processes) when ``join=False``; otherwise
    joins and raises if any child failed. Child results are available from
    ``context.results`` (rank-ordered) after join.
    """
    ctx = mp.get_context("spawn")
    master = f"127.0.0.1:{free_port()}"
    endpoints = [f"127.0.0.1:{free_port()}" for _ in range(nprocs)]
    env = {k: v for k, v in options.pop("envs", {}).items()}
    check_one_process_per_chip(nprocs, {**os.environ, **env})
    queue = ctx.Queue()
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_entry,
                        args=(func, tuple(args), rank, nprocs, master,
                              endpoints, env, queue),
                        daemon=daemon)
        p.start()
        procs.append(p)

    class Context:
        def __init__(self):
            self.processes = procs
            self.results = [None] * nprocs

        def join(self, timeout: Optional[float] = None):
            import queue as queue_mod
            import time as time_mod
            deadline = time_mod.monotonic() + (timeout or 600)
            statuses = {}
            while len(statuses) < nprocs:
                try:
                    rank, status, payload = queue.get(timeout=1.0)
                    statuses[rank] = (status, payload)
                    continue
                except queue_mod.Empty:
                    pass
                # A child that died without reporting (segfault, os._exit,
                # OOM-kill) never queues — watch liveness alongside.
                for r, p in enumerate(procs):
                    if r not in statuses and not p.is_alive() \
                            and p.exitcode not in (0, None):
                        for other in procs:
                            other.terminate()
                        raise RuntimeError(
                            f"spawned process rank {r} died with exit code "
                            f"{p.exitcode} before reporting a result")
                if time_mod.monotonic() > deadline:
                    for p in procs:
                        p.terminate()
                    raise TimeoutError(
                        f"spawn join timed out; reported: "
                        f"{sorted(statuses)} of {nprocs}")
            for p in self.processes:
                p.join(timeout=30)
            errors = []
            for rank in sorted(statuses):
                status, payload = statuses[rank]
                if status == "error":
                    errors.append(f"--- rank {rank} ---\n{payload}")
                else:
                    self.results[rank] = payload
            if errors:  # report every failing rank, not just the first
                raise RuntimeError(
                    f"{len(errors)} spawned process(es) failed:\n"
                    + "\n".join(errors))
            return self

    context = Context()
    if join:
        context.join()
    return context
