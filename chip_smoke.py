#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Default run (no arguments, as the driver runs it): one TPU v5e chip, one
process, four phases through the entry points a user calls — each prints
one JSON line, and a phase that fails fails the run:

  device  one ``tpu`` device; versions; the compile cache in use
  train   the GPT the repo benchmarks at its 1.3B widths (hidden 2048,
          16x128 heads, MLP 8192, vocab 50304, seq 2048, batch 4, bf16
          params + fp32-master AdamW, remat) at the deepest depth
          ``tools/hbm_budget.py`` says fits resident, through
          ``make_sharded_train_step`` on a one-device mesh; flash kernel in
          the compiled step; kernel-vs-dense forward-loss agreement
  trace   three more steps of that live step under ``jax.profiler.trace``;
          ``profiler.statistic.device_statistics`` must read the trace
  serve   ``ServingEngine`` over the same GPT at full depth (24 layers),
          8 greedy requests, two checked against ``model.generate``

(trace runs before serve: it reuses the train phase's live compiled step,
and 12 GB of resident train state cannot sit through the serve phase.)

``--chips 4`` runs only the multi-chip path and what it is compared with:
full-depth GPT with resident AdamW under ``TrainStep`` on a
``sharding=2 x mp=2`` mesh, against a forward-only loss on device 0.

``--tiny`` shrinks every size for the CPU rehearsal and the test suite; it
changes no code path, and the device it reports is the one it ran on.
Off the chip ``ok`` is false and the exit code non-zero, whatever passed.

The last stdout line is ``{"ok": ..., "device": {"platform", "kind",
"count"}}``; when JAX finds no accelerator at all (or this file is run
without the package beside it) no result line is printed and the exit
code is non-zero. Times printed here are smoke output, not benchmark
results.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok, why=""):
    """A smoke check (not ``assert``: ``python -O`` must not skip it)."""
    if not ok:
        raise AssertionError(why)


class Sizes:
    """Real sizes, and the --tiny cut of each (same code path)."""

    def __init__(self, tiny: bool):
        if tiny:
            self.hidden, self.heads, self.ffn, self.vocab = 128, 2, 512, 512
            self.seq, self.batch, self.full_depth = 128, 4, 2
            self.prompt_lo, self.prompt_hi, self.new_tokens = 8, 64, 4
            self.block_size, self.prefill_buckets = 8, [32, 64]
        else:
            self.hidden, self.heads, self.ffn = 2048, 16, 8192
            self.vocab, self.seq, self.batch, self.full_depth = \
                50304, 2048, 4, 24
            self.prompt_lo, self.prompt_hi, self.new_tokens = 128, 1024, 32
            self.block_size, self.prefill_buckets = 16, [512, 1024]
        self.n_requests, self.decode_buckets = 8, [8]

    def gpt_cfg(self, layers: int, remat: bool):
        from paddle_tpu.text.models.gpt import GPTConfig
        return GPTConfig(
            vocab_size=self.vocab, hidden_size=self.hidden,
            num_layers=layers, num_heads=self.heads,
            intermediate_size=self.ffn, max_position_embeddings=self.seq,
            hidden_dropout=0.0, attention_dropout=0.0, recompute=remat)

    def resident_depth(self):
        """Deepest GPT that tools/hbm_budget.py says fits one chip with
        resident (not offloaded) AdamW, and its plan."""
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        from hbm_budget import gpt_plan
        for layers in range(self.full_depth, 0, -1):
            plan = gpt_plan(layers=layers, hidden=self.hidden,
                            heads=self.heads, seq=self.seq, batch=self.batch,
                            vocab=self.vocab, optimizer="adamw",
                            offload="off", remat=True)
            if plan["fits"]:
                return layers, plan
        raise RuntimeError("hbm_budget: no depth fits this chip")


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------

def build_gpt(sizes: Sizes, layers: int, remat: bool, seed: int):
    """The repo's GPT, random weights from ``seed``, bf16 (AMP O2)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTForCausalLM
    paddle.seed(seed)
    model = GPTForCausalLM(sizes.gpt_cfg(layers, remat))
    model.astype(paddle.bfloat16)
    return model


def make_batch(sizes: Sizes, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes.vocab, size=(sizes.batch, sizes.seq),
                       dtype=np.int32)
    labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
    return ids, labels.astype(np.int32)


def loss_fn(model, params, batch):
    from paddle_tpu.framework.functional import functional_call
    ids, labels = batch
    return functional_call(model, params, ids, labels, training=True)


def forward_loss(model, params, batch, use_pallas: bool) -> float:
    """Forward-only loss on ``params``, with the Pallas kernels on or off
    (a fresh jit per call: the flag is read at trace time)."""
    import jax
    from paddle_tpu.core import flags
    prev = flags.get_flags(["use_pallas_kernels"])
    flags.set_flags({"use_pallas_kernels": int(use_pallas)})
    try:
        fn = jax.jit(lambda p, b: loss_fn(model, p, b))
        return float(fn(params, batch))
    finally:
        flags.set_flags(prev)


def placed_batch(ts, batch):
    """The batch as ``ts.step`` places it (for ``ts.compile_step``)."""
    import jax
    from paddle_tpu.framework.sharded import batch_sharding
    sh = batch_sharding(ts.mesh, ts.data_axes, 2)
    return tuple(jax.device_put(x, sh) for x in batch)


def timed_steps(ts, batch, n: int):
    """n steps ending in block_until_ready; (losses, ms per step)."""
    import jax
    losses = []
    t0 = time.perf_counter()
    for _ in range(n):
        losses.append(ts.step(batch))
    jax.block_until_ready(ts.params)
    ms = (time.perf_counter() - t0) * 1e3 / n
    return [float(x) for x in losses], ms


def check_losses(losses, vocab: int):
    """Finite, first loss within a few percent of ln(vocab), falling on
    the repeated batch."""
    want = math.log(vocab)
    check(all(math.isfinite(x) for x in losses),
          losses)
    check(abs(losses[0] - want) / want < 0.05,
          (losses[0], want))
    check(losses[-1] < losses[0],
          losses)


class CacheCounter:
    """Counts JAX's persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def phase_device():
    import jax
    import jaxlib
    from paddle_tpu.core.chip import enable_compile_cache
    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    cache_dir = enable_compile_cache()
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache=cache_dir,
        compile_cache_entries=(len(os.listdir(cache_dir))
                               if cache_dir and os.path.isdir(cache_dir)
                               else 0))
    return device


def phase_train(sizes: Sizes, seed: int, cache):
    import jax
    from jax.sharding import Mesh
    import numpy as np
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.ops.flash_attention import _use_pallas
    from paddle_tpu.optimizer import AdamW

    on_tpu = jax.default_backend() == "tpu"
    depth, plan = sizes.resident_depth()
    model = build_gpt(sizes, depth, remat=True, seed=seed)
    model.train()
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, multi_precision=True)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    ts = make_sharded_train_step(model, opt, loss_fn, mesh=mesh)
    # the Layer tree now reads the step's own buffers: the construction
    # copies are dropped, so one set of params is resident, as budgeted
    ts.sync_to_model()
    batch = make_batch(sizes, seed)
    n_params = sum(int(np.prod(p.shape)) for p in ts.params.values())

    # the flash kernel's first numerical check on a chip under this JAX:
    # same params, same batch, kernels on vs the dense reference
    loss_kernel = forward_loss(model, ts.params, batch, use_pallas=True)
    loss_dense = forward_loss(model, ts.params, batch, use_pallas=False)
    tol = 2e-3  # relative; bf16 activations, f32 loss
    check(abs(loss_kernel - loss_dense) <= tol * abs(loss_dense),
          (loss_kernel, loss_dense))

    q = jax.ShapeDtypeStruct(
        (sizes.batch, sizes.seq, sizes.heads, sizes.hidden // sizes.heads),
        jax.numpy.bfloat16)
    uses_kernel = _use_pallas(q, q)
    check(uses_kernel == on_tpu,
          (uses_kernel, on_tpu))

    hits0 = cache.hits
    t0 = time.perf_counter()
    compiled, _ = ts.compile_step(placed_batch(ts, batch))
    compile_s = time.perf_counter() - t0
    from_cache = cache.hits > hits0
    in_step = "tpu_custom_call" in compiled.as_text()
    check(in_step == on_tpu,
          f"flash tpu_custom_call in compiled step: {in_step}, tpu: {on_tpu}")
    del compiled
    t0 = time.perf_counter()
    first = float(ts.step(batch))
    first_step_s = time.perf_counter() - t0

    losses, step_ms = timed_steps(ts, batch, 3)
    losses = [first] + losses
    check_losses(losses, sizes.vocab)
    stats = jax.devices()[0].memory_stats() or {}
    say("train", depth=depth, n_params=n_params, hbm_plan=plan["rows_gb"],
        hbm_plan_device_gb=plan["device_gb"], hidden=sizes.hidden,
        heads=sizes.heads, head_dim=sizes.hidden // sizes.heads,
        ffn=sizes.ffn, vocab=sizes.vocab, seq=sizes.seq, batch=sizes.batch,
        entry="framework.sharded.make_sharded_train_step",
        flash_custom_call_in_step=in_step, use_pallas=uses_kernel,
        loss_kernel=loss_kernel, loss_dense=loss_dense, loss_rel_tol=tol,
        losses=losses, ln_vocab=math.log(sizes.vocab),
        compile_s=round(compile_s, 2), step_from_compile_cache=from_cache,
        first_step_s=round(first_step_s, 2), step_ms=round(step_ms, 2),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return ts, batch


def phase_trace(ts, batch):
    import glob
    import shutil

    import jax
    from paddle_tpu.profiler.statistic import device_statistics
    log_dir = os.path.join(OUT_DIR, "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(log_dir):
        for _ in range(3):
            ts.step(batch)
        jax.block_until_ready(ts.params)
    wall_ms = (time.perf_counter() - t0) * 1e3 / 3
    diags = []
    stats = device_statistics(log_dir, top=5, diagnostics=diags)
    by_cat, top_ops = stats if stats else ({}, [])
    device_ms = sum(by_cat.values()) / 3 if by_cat else None
    xplanes = glob.glob(os.path.join(log_dir, "plugins/profile/*/*.xplane.pb"))
    if jax.default_backend() == "tpu" and not device_ms:
        # say what the chip wrote before failing: planes and their lines
        pd = jax.profiler.ProfileData.from_file(xplanes[0])
        for plane in pd.planes:
            say("trace_plane", name=plane.name,
                lines=[(ln.name, sum(1 for _ in ln.events))
                       for ln in plane.lines][:12])
        raise AssertionError(
            f"device trace not read: {[d.message for d in diags]}")
    trace_mb = sum(os.path.getsize(os.path.join(d, f))
                   for d, _, fs in os.walk(log_dir) for f in fs) / 2**20
    if trace_mb > 24:  # the output directory brings back 64 MiB at most
        shutil.rmtree(log_dir, ignore_errors=True)
    say("trace", log_dir=os.path.relpath(log_dir, ROOT),
        trace_mb=round(trace_mb, 1), kept=trace_mb <= 24,
        parser="xprof hlo_stats (profiler.statistic.device_statistics)",
        device_ms_per_step=device_ms, wall_ms_per_step=round(wall_ms, 2),
        by_category_ms={k: round(v / 3, 3) for k, v in by_cat.items()},
        top_ops=[{"ms": round(o["ms"] / 3, 3), "category": o["category"],
                  "op": o["op"][:80]} for o in top_ops],
        note="smoke output, not a benchmark result")


def phase_serve(sizes: Sizes, seed: int, cache):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.serving import Request, ServingEngine, Status

    model = build_gpt(sizes, sizes.full_depth, remat=False, seed=seed)
    max_len = sizes.prompt_hi + sizes.new_tokens
    blocks_per_seq = -(-max_len // sizes.block_size)
    eng = ServingEngine(
        model, block_size=sizes.block_size,
        num_blocks=sizes.n_requests * blocks_per_seq + 1,
        max_batch=sizes.n_requests, max_seq_len=max_len,
        prefill_buckets=sizes.prefill_buckets,
        decode_buckets=sizes.decode_buckets)
    check(not (eng.prefix_on or eng.chunk_tokens or eng.spec_gamma),
          "a serve_* throughput tier is switched on")

    rng = np.random.default_rng(seed)
    lens = rng.integers(sizes.prompt_lo, sizes.prompt_hi + 1,
                        size=sizes.n_requests)
    prompts = [rng.integers(0, sizes.vocab, size=int(n), dtype=np.int32)
               for n in lens]
    hits0, misses0 = cache.hits, cache.misses
    t0 = time.perf_counter()
    seqs = [eng.submit(Request(f"r{i}", p, max_new_tokens=sizes.new_tokens))
            for i, p in enumerate(prompts)]
    n_iters = 0
    while eng.sched.n_pending:
        eng.step()
        n_iters += 1
        check(n_iters < 10 * sizes.n_requests * sizes.new_tokens,
              "engine loop does not drain")
    serve_s = time.perf_counter() - t0
    for s in seqs:
        check(s.status is Status.FINISHED,
              (s.request.rid, s.status))
        check(len(s.out_tokens) == sizes.new_tokens,
              s.request.rid)
    report = eng.compile_report()
    check(report["within_budget"] and not report["o001_fired"],
          report)

    # two requests against model.generate greedy, token for token; where a
    # near-tie flips between the two batch shapes, the two logit rows at
    # the first divergence must agree within tol and the margin is shown
    tol = 0.05  # absolute, on bf16 logits of magnitude ~1
    checks = []
    for i in (0, sizes.n_requests - 1):
        got = np.asarray(seqs[i].out_tokens)
        ref = np.asarray(model.generate(
            prompts[i][None, :], max_new_tokens=sizes.new_tokens)
        )[0, len(prompts[i]):]
        same = bool((got == ref).all())
        row = {"rid": f"r{i}", "prompt_len": int(lens[i]),
                 "token_exact": same}
        if not same:
            j = int(np.argmax(got != ref))
            prefix = np.concatenate([prompts[i], ref[:j]])[None, :]
            logits = np.asarray(functional_call(
                model, None, jnp.asarray(prefix), training=False)
            )[0, -1].astype(np.float32)
            margin = float(abs(logits[got[j]] - logits[ref[j]]))
            row.update(first_divergence=j, engine_token=int(got[j]),
                         generate_token=int(ref[j]), logit_margin=margin,
                         logit_tol=tol)
            check(margin <= tol, row)
        checks.append(row)
    stats = jax.devices()[0].memory_stats() or {}
    say("serve", layers=sizes.full_depth, requests=sizes.n_requests,
        completed=len(seqs), prompt_lens=[int(n) for n in lens],
        new_tokens=sizes.new_tokens, engine_iterations=n_iters,
        programs_compiled=(report["prefill_signatures"]
                           + report["decode_signatures"]),
        prefill_buckets=report["prefill_buckets"],
        decode_buckets=report["decode_buckets"],
        compile_cache_hits=cache.hits - hits0,
        compile_cache_misses=cache.misses - misses0,
        serve_s_incl_compile=round(serve_s, 2), vs_generate=checks,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# --chips 4: the sharded step and its one-device comparison, nothing else
# ---------------------------------------------------------------------------

def phase_multichip(sizes: Sizes, seed: int, cache):
    import jax
    import numpy as np
    from paddle_tpu.analysis import hlo_check
    from paddle_tpu.distributed.topology import create_hybrid_mesh
    from paddle_tpu.framework.functional import get_params
    from paddle_tpu.framework.sharded import (make_sharded_train_step,
                                              shard_params)
    from paddle_tpu.optimizer import AdamW

    devs = jax.devices()[:4]
    model = build_gpt(sizes, sizes.full_depth, remat=True, seed=seed)
    model.train()
    batch = make_batch(sizes, seed)
    # what the sharded step is compared with: a forward-only loss on
    # device 0 alone (the model was born there), same params and batch
    loss_one = forward_loss(model, get_params(model), batch,
                            use_pallas=True)

    mesh = create_hybrid_mesh(sharding=2, mp=2, devices=devs)
    # hand the Layer tree its sharded arrays, so the single-device copy is
    # dropped before the optimizer state is built
    shard_params(model, mesh)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, multi_precision=True)
    ts = make_sharded_train_step(model, opt, loss_fn, mesh=mesh)
    ts.sync_to_model()

    t0 = time.perf_counter()
    compiled, _ = ts.compile_step(placed_batch(ts, batch))
    compile_s = time.perf_counter() - t0
    facts = hlo_check.collect_hlo_facts(compiled)
    in_step = "tpu_custom_call" in compiled.as_text()
    check(in_step == (jax.default_backend() == "tpu"),
          in_step)
    del compiled
    # the collectives the plan declares are in the compiled step: FSDP
    # gathers its shards, and grads / TP partial sums are reduced. Kinds
    # the plan does not declare (X001) are printed as a finding
    kinds = {k for k, n in facts.collectives.items() if n > 0}
    check("all-gather" in kinds,
          facts.collectives)
    check(kinds & {"all-reduce", "reduce-scatter"},
          facts.collectives)
    undeclared = sorted(kinds - hlo_check.expected_collective_kinds(ts.plan))

    first = float(ts.step(batch))
    tol = 5e-3  # relative: bf16, and the mp=2 matmuls reduce in another order
    check(abs(first - loss_one) <= tol * abs(loss_one),
          (first, loss_one))
    losses, step_ms = timed_steps(ts, batch, 3)
    losses = [first] + losses
    check_losses(losses, sizes.vocab)

    in_use = []
    for d in devs:
        st = d.memory_stats() or {}
        in_use.append(st.get("bytes_in_use"))
    if all(b is not None for b in in_use):
        check(max(in_use) <= 1.25 * min(in_use),
              f"state is not spread evenly over the chips: {in_use}")
    say("multichip", mesh={"sharding": 2, "mp": 2},
        layers=sizes.full_depth,
        device_ids=[int(d.id) for d in mesh.devices.flatten()],
        loss_one_device=loss_one, loss_sharded_step0=first,
        loss_rel_tol=tol, losses=losses,
        compile_s=round(compile_s, 2), step_ms=round(step_ms, 2),
        flash_custom_call_in_step=in_step, collectives=facts.collectives,
        undeclared_collective_kinds=undeclared,
        bytes_in_use_per_device=in_use)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="shrunken sizes for the CPU rehearsal and tests")
    args = ap.parse_args(argv)

    import jax
    sizes = Sizes(args.tiny)
    cache = CacheCounter()
    os.makedirs(OUT_DIR, exist_ok=True)
    device = phase_device()
    ok = False
    try:
        if not args.tiny:
            check(device["platform"] == "tpu",
                  f"no TPU: jax.devices() is {jax.devices()}")
        check(device["count"] >= args.chips,
              device)
        if args.chips == 4:
            phase_multichip(sizes, args.seed, cache)
            device["count"] = 4
        else:
            ts, batch = phase_train(sizes, args.seed, cache)
            phase_trace(ts, batch)
            del ts, batch
            gc.collect()  # the train state must be gone before serving
            phase_serve(sizes, args.seed, cache)
        # `ok` is a statement about the chip: never true off it
        ok = device["platform"] == "tpu"
    finally:
        say("cache", compile_cache_hits=cache.hits,
            compile_cache_misses=cache.misses)
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
