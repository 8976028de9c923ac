"""What every family's cells share of the system under test: the compile
cache, the mesh, the ``TrainStep`` assembly around a family's model and loss,
what the comparison reads from the optimizer's state, the engine's warm-up
and the requests. With the families' adapters
(``benchmark/families/<model>/adapter.py``) this is all of the benchmark that
imports the program; nothing here decides a metric.

``fam`` is a ``lib.family.Family``: ``fam.adapter`` builds the model and maps
the leaf names, ``fam.weights`` names the leaves.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else a fixed directory inside the checkout.
    The program's own ``enable_compile_cache`` picks the same directory, so
    both agree whoever is called first."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_mesh(mesh_axes, chips: int):
    """A one-device ``dp`` mesh, or the hybrid mesh a cell's file names."""
    from jax.sharding import Mesh
    devs = jax.devices()[:chips]
    if not mesh_axes:
        return Mesh(np.asarray(devs[:1]), ("dp",))
    from paddle_tpu.distributed.topology import create_hybrid_mesh
    return create_hybrid_mesh(devices=devs, **mesh_axes)


def replicated(mesh):
    """The sharding that puts a whole copy on every chip of a sharded cell's
    mesh; None (the default device) on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec()) if mesh.size > 1 else None


def build_train_step(fam, cfg, weights, opt_cfg, mesh):
    """``make_sharded_train_step`` over the family's model with resident
    AdamW and fp32 masters; the Layer tree reads the step's own buffers, so
    one set of parameters is resident."""
    from paddle_tpu.framework.sharded import (make_sharded_train_step,
                                              shard_params)
    from paddle_tpu.optimizer import AdamW
    model = fam.adapter.build_model(cfg, remat=True)
    fam.adapter.load_weights(model, cfg, weights)
    model.train()
    if mesh.size > 1:
        shard_params(model, mesh)
    opt = AdamW(learning_rate=opt_cfg["learning_rate"],
                beta1=opt_cfg["beta1"], beta2=opt_cfg["beta2"],
                epsilon=opt_cfg["epsilon"],
                weight_decay=opt_cfg["weight_decay"], multi_precision=True)
    ts = make_sharded_train_step(model, opt, fam.adapter.loss_fn, mesh=mesh)
    ts.sync_to_model()
    return ts


def reset_train_step(ts, fam, cfg, weights) -> None:
    """Put fresh weights and a zero optimizer state into a live step (the
    compiled program is kept): for reading many seeds in one process. The old
    state is dropped first; two do not fit."""
    import gc
    fam.adapter.load_weights(ts.model, cfg, weights)
    ts.params, ts.opt_state = {}, None
    gc.collect()
    params = {n: jax.device_put(v, ts.pshardings[n])
              for n, v in fam.adapter.to_program(cfg, weights).items()}
    # born sharded like the step's own state: built eagerly, every moment of
    # a sharded cell would land whole on chip 0 (10.5 GB at 24 layers)
    init = jax.jit(ts.optimizer.init, out_shardings=ts._state_shardings) \
        if ts.mesh.size > 1 else ts.optimizer.init
    ts.load_state_dict({"params": params, "opt_state": init(params),
                        "buffers": {}, "step_count": 0, "lr_sched": None})
    ts.sync_to_model()


def train_state_norms(ts, fam, cfg, beta1: float, start_seed=None):
    """What the comparison reads from the optimizer's state: per compared
    leaf the norm of the first gradient as the optimizer got it (moment1
    after one step over 1 - beta1) or, given the seed the weights came from,
    the norm of master - start. The start is made again inside the same
    program, leaf by leaf, so that no second copy of the model is resident
    and the memory peak stays the program's own."""
    states = ts.opt_state["param_states"]
    program_name = fam.adapter.program_name

    def norms(leaf_of):
        out = {}
        for n in fam.weights.leaf_names(cfg):
            for part, a in fam.weights.compared_parts(n, leaf_of(n)).items():
                out[part] = jnp.sqrt(jnp.sum(jnp.square(a)))
        return out

    @jax.jit
    def first_grad(states):
        return norms(lambda n: states[program_name(n)]["moment1"]
                     / (1.0 - beta1))

    @jax.jit
    def delta(states, key):
        w0 = W.weights_from_key(key, fam.weights, cfg, jnp.bfloat16)
        return norms(lambda n: states[program_name(n)]["master"]
                     - W.get_leaf(w0, n).astype(jnp.float32))

    out = first_grad(states) if start_seed is None \
        else delta(states, W.seed_key(start_seed))
    return {k: float(v) for k, v in out.items()}


def warm_engine(eng, cfg, eng_cfg) -> None:
    """Run every program this traffic uses once: each prefill bucket with one
    prompt that lands in it, each decode bucket with as many rows as reach
    it. Nothing else is warmed. An adapter whose engine runs other programs
    (chunks, extends after a prefix hit) brings a ``warm_engine`` of its own
    with these arguments."""
    rng = np.random.default_rng(0)
    n = 0

    def go(lengths):
        nonlocal n
        for length in lengths:
            ids = rng.integers(0, cfg["vocab_size"], size=length)
            eng.submit(make_request(f"warm{n}", ids, 2))
            n += 1
        while eng.sched.n_pending:
            eng.step()

    edges = [0] + sorted(eng_cfg["prefill_buckets"])
    go([max(lo + 1, 2) for lo in edges[:-1]])
    rows, prev = len(edges) - 1, 0
    for width in sorted(eng_cfg["decode_buckets"]):
        if not prev < rows <= width:
            go([2] * (prev + 1))
        prev = width


def make_request(rid: str, prompt_ids, max_new_tokens: int):
    from paddle_tpu.serving import Request
    return Request(rid, np.asarray(prompt_ids, np.int32),
                   max_new_tokens=int(max_new_tokens))


def request_ok(seq) -> bool:
    from paddle_tpu.serving import Status
    return getattr(seq, "status", None) is Status.FINISHED


def request_failed(seq) -> bool:
    """Refused at admission, or retired in any way but finished."""
    from paddle_tpu.serving import Rejected, Status, TERMINAL_STATUSES
    if isinstance(seq, Rejected):
        return True
    return seq.status in TERMINAL_STATUSES and seq.status is not Status.FINISHED
