"""Auto-tuner tests (ref auto_tuner/: GridSearch + prune rules + recorder +
trial loop) on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_tuner import (AutoTuner, GridSearch,
                                               HistoryRecorder)


def test_grid_search_prunes_invalid():
    cfg = {"num_devices": 8, "hidden_size": 64, "num_heads": 4,
           "num_layers": 4, "global_batch_size": 8,
           "micro_batch_size": [1, 2]}
    gs = GridSearch(cfg)
    assert gs.all_cfgs, "search space empty"
    for c in gs.all_cfgs:
        prod = c["dp_degree"] * c["mp_degree"] * c["pp_degree"] * \
            c["sharding_degree"]
        assert prod == 8
        assert c["mp_degree"] <= 4  # heads=4 prunes mp=8
    # mp=8 would not divide num_heads=4
    assert not any(c["mp_degree"] == 8 for c in gs.all_cfgs)


def test_recorder_best_and_csv(tmp_path):
    r = HistoryRecorder()
    r.add_cfg(job_id=1, dp_degree=8, throughput=10.0)
    r.add_cfg(job_id=2, dp_degree=4, throughput=25.0)
    r.add_cfg(job_id=3, dp_degree=2, throughput=None, error="OOM")
    best, empty = r.get_best()
    assert not empty and best["job_id"] == 2
    p = str(tmp_path / "history.csv")
    r.store_history(p)
    rows, missing = r.load_history(p)
    assert not missing and len(rows) == 3


def test_tuner_finds_runnable_config():
    """End-to-end: time a real jitted DP/MP matmul step per config and pick
    the best; infeasible configs (simulated OOM) must be recorded, not
    fatal."""
    from paddle_tpu.distributed.topology import create_hybrid_mesh

    d = 32

    def model_fn(mesh, cfg):
        from jax.sharding import NamedSharding, PartitionSpec as P
        w = jax.device_put(np.ones((d, d), np.float32),
                           NamedSharding(mesh, P(None, "mp")))
        x = jax.device_put(np.ones((8, d), np.float32),
                           NamedSharding(mesh, P("dp", None)))

        @jax.jit
        def step(state, x):
            w = state
            y = jnp.tanh(x @ w)
            return w - 1e-4 * jnp.mean(y) * w

        return step, w, (x,)

    tuner_cfg = {"num_devices": 8, "hidden_size": d, "num_heads": 4,
                 "num_layers": 2, "global_batch_size": 8,
                 "micro_batch_size": [1],
                 "dp_degree": [1, 2, 4, 8], "mp_degree": [1, 2, 4, 8],
                 "model_fn": model_fn, "trial_steps": 2}
    tuner = AutoTuner(tuner_cfg)
    best = tuner.tune(max_trials=6)
    assert best is not None and best["throughput"] > 0
    # (dp, mp) with product 8: (2,4), (4,2), (8,1); (1,8) pruned by heads=4
    assert len(tuner.recorder.history) == 3
    assert all(h["dp_degree"] * h["mp_degree"] == 8
               for h in tuner.recorder.history)


def test_tuner_records_failures():
    def bad_trial(cfg):
        raise MemoryError("Ran out of memory in memory space hbm")

    tuner = AutoTuner({"num_devices": 8, "dp_degree": [8], "mp_degree": [1]},
                      trial_fn=bad_trial)
    best = tuner.tune()
    assert best is None
    assert tuner.recorder.history[0]["error"] is not None
