#!/usr/bin/env python
"""Lint the traced graphs of the repo's example models (and, with --all,
the Pallas kernel configs and the source tree) with paddle_tpu.analysis.

    python tools/lint_graph.py --model bert          # one model, CPU, fast
    python tools/lint_graph.py --all                 # models + kernels + AST
    python tools/lint_graph.py --model gpt --min-severity info
    python tools/lint_graph.py --matrix              # tier-flag matrix gate
    python tools/lint_graph.py --matrix --json       # machine-readable
    python tools/lint_graph.py --hlo                 # compiled-HLO X-rules
    python tools/lint_graph.py --passes              # pass-pipeline G-rules

Exits nonzero when any error-severity diagnostic is found — the CI gate
that needs no TPU. Clean models print their diagnostic count (0) and the
jaxpr size, so regressions in graph hygiene show up in review.

``--matrix`` enumerates every supported combination of the six tier
flags (offload_optimizer × comm_overlap × multislice × cp_nested_ring ×
pallas_conv × remat), builds each composition's StepPlan on the 8-device
virtual mesh,
and verifies it with ``analysis/plan_check`` (sharding-flow S-rules +
donation-lifetime D-rules) + ``analysis/comm_check`` hop plans +
``tools/hbm_budget.py`` capacity, AOT-compiles each trace-distinct step
and runs the compiled-HLO X-rules (``analysis/hlo_check`` — skip with
``--no-hlo``) — then runs the ten multichip dryrun scenarios. ``--hlo`` runs the
X-rules standalone over the representative composed steps plus a seeded
X001 self-test. ``--passes`` runs the step-compiler pass-pipeline
verifier standalone: the ordered pass list and per-pass contract hashes,
every tier combo (both sentinel arms) composed plan-only through
``framework/step_pipeline.py`` and checked with the G-rules
(``analysis/pass_check``), plus seeded self-tests that G001/G002/G004
each fire on a bad composition. ``--json`` switches stdout to one
machine-readable report for CI (schema v3: v2's ``schema_version`` +
per-family ``rule_index``, plus the ``passes`` section — ordered pass
list, contract hashes, per-combo composed-plan hash — so CI can diff
pipeline composition across PRs).
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The overlap model lints a decomposed collective over an 8-device virtual
# mesh (same provisioning as tests/conftest.py); no-op if jax is already
# initialized (the in-process selfcheck run has its own 8 devices).
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_layer(layer, args, where):
    from paddle_tpu.framework.functional import functional_call, get_params
    from paddle_tpu.analysis import lint_jaxpr
    layer.eval()  # inference view: dropout off, no host RNG pulls
    params = get_params(layer)
    closed = jax.make_jaxpr(
        lambda p, *a: functional_call(layer, p, *a))(params, *args)
    diags = lint_jaxpr(closed, where=where)
    return diags, len(closed.jaxpr.eqns)


def lint_bert():
    from paddle_tpu.text.models.bert import Bert, bert_tiny
    ids = jnp.zeros((2, 128), jnp.int32)
    return _lint_layer(Bert(bert_tiny()), (ids,), "bert")


def lint_gpt():
    from paddle_tpu.text.models.gpt import GPT, gpt_tiny
    ids = jnp.zeros((2, 128), jnp.int32)
    return _lint_layer(GPT(gpt_tiny()), (ids,), "gpt")


def lint_mlp():
    from paddle_tpu import nn
    net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 10))
    return _lint_layer(net, (jnp.zeros((4, 64), jnp.float32),), "mlp")


def lint_offload():
    """The offload streaming-update block program (framework/offload.py):
    must stay free of in-graph memory-kind transfers (J012) — all
    host<->device movement happens at dispatch level."""
    from paddle_tpu import nn
    from paddle_tpu.analysis import lint_jaxpr
    from paddle_tpu.framework import offload
    from paddle_tpu.framework.functional import get_params
    from paddle_tpu.optimizer import AdamW

    net = nn.Sequential(nn.Linear(32, 64), nn.Tanh(), nn.Linear(64, 8))
    params = get_params(net)
    opt = AdamW(learning_rate=1e-3)
    su = offload.StreamingUpdate(opt)
    state = su.init_state(params)
    grads = {k: jnp.ones_like(v) for k, v in params.items()}
    names = offload.group_by_block(list(params))[0][1]
    p_blk = {n: params[n] for n in names}
    g_blk = {n: grads[n] for n in names}
    st_blk = {n: {k: jax.device_put(v, params[n].sharding)
                  for k, v in state["param_states"][n].items()}
              for n in names}
    closed = jax.make_jaxpr(su._block_fn.__wrapped__)(
        p_blk, g_blk, st_blk, state["step"], jnp.float32(1e-3))
    diags = lint_jaxpr(closed, donate_argnums=(0, 1, 2), where="offload")
    return diags, len(closed.jaxpr.eqns)


def lint_overlap():
    """The decomposed-collective-matmul programs (distributed/overlap.py):
    a Megatron-SP column+row pair through the bidirectional ppermute
    pipelines, traced fwd+grad and linted (J012/J013/J014 — the
    decomposed loops must not themselves trip the overlap rules), plus
    the static ICI accounting (C001-C003) of each hop plan at a
    production-ish shape."""
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.analysis import (comm_check, lint_jaxpr)
    from paddle_tpu.distributed import overlap

    if jax.device_count() < 2:
        print("  (skipped: needs >=2 devices for the mp mesh; "
              "run under the 8-device virtual CPU platform)")
        return [], 0
    n = 8 if jax.device_count() >= 8 else 2
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(1, 1, 1, 1, n),
                ("pp", "dp", "sharding", "sep", "mp"))
    b, s, d, f = 2, 8 * n, 16, 32
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((d, f)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((f, d)), jnp.float32)

    def sp_pair(x, w1, w2):
        h = overlap.allgather_matmul(x, w1, mesh=mesh, chunks=1)
        h = jax.nn.gelu(h)
        return overlap.matmul_reduce_scatter(h, w2, mesh=mesh, chunks=1)

    def loss(x, w1, w2):
        return jnp.sum(sp_pair(x, w1, w2) ** 2)

    closed = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(1, 2)))(
        x, w1, w2)
    diags = lint_jaxpr(closed, where="overlap")
    # static hop-plan accounting at a production-ish shape (GPT-1.3B
    # layer through mp=4: B*S_local*K chunks well over the latency floor)
    for spec in (
            comm_check.spec_for_allgather_matmul(
                8, 512, 2048, 2048, 4, 2),
            comm_check.spec_for_matmul_reduce_scatter(
                8, 512, 2048, 2048, 4, 2)):
        cd = comm_check.check_comm_spec(spec)
        print(f"  comm spec {spec.name}: {spec.hops} hops x "
              f"{spec.bytes_per_hop / 2**20:.2f} MiB, "
              f"{len(cd)} diagnostic(s)")
        for d in cd:
            print("    " + d.format())
        diags += cd
    return diags, len(closed.jaxpr.eqns)


def lint_fault():
    """The fault-drill configuration (paddle_tpu/fault/): the drill
    trainer's composed train step traced + jaxpr-linted + verified
    against its declared StepPlan (same gate every other tier gets), the
    GUARDED step (FLAGS_health_sentinel=on — fused stats + in-graph
    update gate) through the identical rules, the quick drill's
    deterministic FaultPlan statically validated (F002), and the health
    tier's own static rules: the Guardian policy table (F004) and the
    SDC canary cadence (F005)."""
    import numpy as np
    from paddle_tpu.analysis import lint_jaxpr, plan_check
    from paddle_tpu.fault import _trainer, drill, guardian, health, injection

    ts, batches = _trainer.build_step("quick")
    closed, donate = ts.trace_step(batches[0])
    diags = lint_jaxpr(closed, donate_argnums=donate, where="fault")
    diags += plan_check.check_plan(ts.plan, closed, donate_argnums=donate,
                                   where="fault")
    cfg = drill.quick_config()
    plan = injection.FaultPlan.from_seed(
        cfg["seed"], cfg["total_steps"], n_kills=cfg["n_kills"],
        kinds=cfg["kinds"])
    pd = injection.check_plan(plan, cfg["total_steps"])
    print(f"  fault plan {plan!r}: {len(pd)} diagnostic(s)")
    diags += pd

    # the guarded step: sentinel fused in, same jaxpr + plan gates
    gts, gbatches = _trainer.build_step("quick", health=True)
    ids, labels = gbatches[0]
    gbatch = (ids, labels, np.asarray([1.0], np.float32))
    gclosed, gdonate = gts.trace_step(gbatch)
    gd = lint_jaxpr(gclosed, donate_argnums=gdonate, where="fault.guarded")
    gd += plan_check.check_plan(gts.plan, gclosed, donate_argnums=gdonate,
                                where="fault.guarded")
    print(f"  guarded step (sentinel fused): {len(gclosed.jaxpr.eqns)} "
          f"eqns, {len(gd)} diagnostic(s)")
    diags += gd

    # health-tier static rules over the quick drill's configuration
    hcfg = drill.quick_health_config()
    hd = health.check_health_plan(guardian.DEFAULT_POLICIES)
    hd += health.check_canary(3, hcfg["total_steps"])
    print(f"  health plan (F004) + canary cadence (F005): "
          f"{len(hd)} diagnostic(s)")
    diags += hd
    hplan = injection.FaultPlan.from_seed(
        hcfg["seed"], hcfg["total_steps"], n_kills=hcfg["n_kills"],
        kinds=hcfg["kinds"])
    hplan = drill._dodge_resume_boundaries(
        hplan, hcfg["ckpt_every"], hcfg["total_steps"])
    hpd = injection.check_plan(hplan, hcfg["total_steps"])
    print(f"  health drill plan {hplan!r}: {len(hpd)} diagnostic(s)")
    diags += hpd
    return diags, len(closed.jaxpr.eqns) + len(gclosed.jaxpr.eqns)


def lint_serving():
    """The serving engine's bucketed executables (paddle_tpu/serving/):
    prefill (flash forward + paged KV scatter), decode (paged gather +
    single-query attention + in-program KV write), and — with the three
    ISSUE-13 throughput tiers armed — extend (chunked/suffix prefill),
    verify (speculative decode-gamma), and the ModelDrafter's draft
    step, each traced at its smallest buckets through the jaxpr linter;
    plus the declared dispatch plan (prefill/chunk/draft/verify/decode/
    spill/restore donation sequence with the COW-shared page discipline,
    rule D005) verified by plan_check and the compiled decode + verify
    modules through the X pass."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import lint_jaxpr, plan_check
    from paddle_tpu.serving import ModelDrafter, ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    cfg = gpt_tiny(vocab_size=128, hidden_size=48, num_layers=2,
                   num_heads=4, max_position_embeddings=64)
    model = GPTForCausalLM(cfg)
    paddle.seed(1)
    drafter = GPTForCausalLM(gpt_tiny(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
        max_position_embeddings=64))
    # all three tiers armed: the full plan (incl. D005's cow_shared
    # declaration) and every executable family get verified
    eng = ServingEngine(model, block_size=4, num_blocks=32, max_batch=4,
                        prefix_cache=True, chunked_prefill=8,
                        speculative=2, drafter=ModelDrafter(drafter))
    diags, n_eqns = [], 0
    traced = eng.trace_steps()
    for name, (closed, donate) in traced.items():
        d = lint_jaxpr(closed, donate_argnums=donate,
                       where=f"serving.{name}")
        print(f"  serving.{name}: {len(closed.jaxpr.eqns)} eqns, "
              f"{len(d)} diagnostic(s)")
        diags += d
        n_eqns += len(closed.jaxpr.eqns)
    pd = plan_check.check_plan(eng.plan, traced["decode"][0],
                               donate_argnums=traced["decode"][1],
                               where="serving")
    print(f"  serving plan ({len(eng.plan.nodes)} nodes, cow_shared="
          f"{eng.plan.flags.get('cow_shared_buffers')!r}): "
          f"{len(pd)} diagnostic(s)")
    diags += pd
    # compiled-HLO pass (X-rules): the single-partition decode and
    # verify modules must build with zero collectives and both
    # page-pool donations realized as aliases
    from paddle_tpu.analysis import hlo_check
    for label, (compiled, donated) in (
            ("decode", eng.compile_decode()),
            ("verify", eng.compile_extend(verify=True))):
        facts = hlo_check.collect_hlo_facts(compiled)
        xd = hlo_check.check_hlo(eng.plan, facts, donated_leaves=donated,
                                 where=f"serving.{label}.hlo")
        print(f"  serving.{label} compiled HLO: {facts.to_json()}, "
              f"{len(xd)} diagnostic(s)")
        diags += xd
    return diags, n_eqns


def _multislice_micro_step(mode: str = "hierarchical"):
    """A tiny GPT TrainStep on the 2-slice x 4-device virtual mesh with
    the 2-tier grad reduction active (shared by --model multislice and
    the --matrix multislice component)."""
    import paddle_tpu as paddle
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed.multislice import SliceTopology
    from paddle_tpu.distributed.topology import set_hybrid_mesh
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    dp = 4 if jax.device_count() >= 8 else jax.device_count() // 2
    topo = SliceTopology(2, dp=dp)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_flash_attention=False)

    def loss_fn(m, p, b):
        ids, labels = b
        return functional_call(m, p, ids, labels, training=True)

    set_flags({"multislice": mode})
    set_hybrid_mesh(topo.mesh)
    ts = make_sharded_train_step(GPTForCausalLM(cfg), AdamW(1e-3), loss_fn,
                                 mesh=topo.mesh, fsdp_axis=None)
    ids = jnp.zeros((2 * dp, 16), jnp.int32)
    return topo, ts, (ids, ids)


def lint_multislice():
    """The multi-slice tier (distributed/multislice): the hierarchical
    2-tier TrainStep traced on the 2-slice virtual mesh through the jaxpr
    linter (incl. J015 — the reduction must not put a DCN collective in a
    loop body) and the S/D plan rules, the recorded hop plan through the
    C-rules (C001-C005), plus a self-test that the naive flat-over-DCN
    plan DOES fire C004 — the rule exists to catch exactly that plan."""
    from paddle_tpu.analysis import comm_check, lint_jaxpr, plan_check
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed.topology import set_hybrid_mesh

    if jax.device_count() < 4:
        print("  (skipped: needs >=4 devices for the 2-slice mesh; "
              "run under the 8-device virtual CPU platform)")
        return [], 0
    try:
        topo, ts, batch = _multislice_micro_step("hierarchical")
        closed, donate = ts.trace_step(batch)
        diags = lint_jaxpr(closed, donate_argnums=donate,
                           where="multislice")
        diags += plan_check.check_plan(ts.plan, closed,
                                       donate_argnums=donate,
                                       where="multislice")
        for where, spec in ts.plan.comm_specs:
            cd = comm_check.check_comm_spec(spec)
            print(f"  comm spec {spec.name} [{spec.link}] axis="
                  f"{spec.axis}: {spec.hops} hops x "
                  f"{spec.bytes_per_hop / 1024:.1f} KiB, "
                  f"{len(cd)} diagnostic(s)")
            diags += [d for d in cd if d.severity == "error"]
    finally:
        set_flags({"multislice": "off"})
        set_hybrid_mesh(None)
    # production-shape hop plan: a 100 MiB DCN bucket over 2 slices of 64
    # chips — every stage must clear the C002/C005 latency floors
    bucket = 100 << 20
    for spec in (comm_check.spec_for_slice_reduce_scatter(bucket, 64),
                 comm_check.spec_for_dcn_allreduce(
                     bucket // 64, 2, reduced_from_bytes=bucket,
                     ici_size=64),
                 comm_check.spec_for_slice_all_gather(bucket, 64)):
        cd = comm_check.check_comm_spec(spec)
        print(f"  production {spec.name} [{spec.link}]: "
              f"{spec.payload_bytes / 2**20:.2f} MiB payload, "
              f"{len(cd)} diagnostic(s)")
        for d in cd:
            print("    " + d.format())
        diags += cd
    # C004 self-test: the naive plan (full bucket over DCN) must fire
    naive = comm_check.spec_for_dcn_allreduce(
        bucket, 2, reduced_from_bytes=bucket, ici_size=64)
    fired = [d for d in comm_check.check_comm_spec(naive)
             if d.rule == "C004"]
    print(f"  C004 on the naive flat-over-DCN plan: "
          f"{'fires' if fired else 'MISSING'}")
    if not fired:
        from paddle_tpu.analysis.jaxpr_lint import Diagnostic
        diags.append(Diagnostic(
            rule="C004", name="dcn-volume-blowup", severity="error",
            message="self-test: C004 did not fire on the naive "
                    "flat-allreduce-over-DCN hop plan",
            where="multislice"))
    return diags, len(closed.jaxpr.eqns)


MODELS = {"bert": lint_bert, "gpt": lint_gpt, "mlp": lint_mlp,
          "offload": lint_offload, "overlap": lint_overlap,
          "fault": lint_fault, "serving": lint_serving,
          "multislice": lint_multislice}

_SEV_RANK = {"info": 0, "warning": 1, "error": 2}

# --json report schema. v2 adds schema_version itself plus the
# rule_index section (family -> {count, ids -> per-id counts}) so CI can
# diff reports across PRs without re-deriving the rule families. v3 adds
# the passes section (ordered pass list, per-pass contract hashes,
# per-combo composed-plan hash) so CI can diff step-pipeline composition.
SCHEMA_VERSION = 3


def _rule_index(diags):
    """family -> {"count": N, "ids": {rule_id: count}} over a diagnostic
    list (Diagnostic objects or their to_json dicts)."""
    idx = {}
    for d in diags:
        rid = d["rule"] if isinstance(d, dict) else d.rule
        fam = idx.setdefault(rid[:1], {"count": 0, "ids": {}})
        fam["count"] += 1
        fam["ids"][rid] = fam["ids"].get(rid, 0) + 1
    return {k: {"count": v["count"],
                "ids": dict(sorted(v["ids"].items()))}
            for k, v in sorted(idx.items())}


def run(models, with_kernels=False, with_repo=False, min_severity="info",
        json_mode=False):
    """Model/kernel/repo lint pass. In json mode the human narration is
    redirected to stderr and stdout carries one parseable report."""
    if json_mode:
        import contextlib
        with contextlib.redirect_stdout(sys.stderr):
            rc, report = _run_impl(models, with_kernels, with_repo,
                                   min_severity)
        print(json.dumps(report, indent=2))
        return rc
    rc, _ = _run_impl(models, with_kernels, with_repo, min_severity)
    return rc


def _run_impl(models, with_kernels=False, with_repo=False,
              min_severity="info"):
    from paddle_tpu.analysis import check_kernel_spec, repo_lint
    from paddle_tpu.core import flags as core_flags
    all_diags = []
    report = {"models": {}}
    for name in models:
        diags, n_eqns = MODELS[name]()
        shown = [d for d in diags
                 if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]]
        print(f"== {name}: {n_eqns} eqns, {len(diags)} diagnostic(s)")
        for d in shown:
            print("  " + d.format())
        report["models"][name] = {
            "eqns": n_eqns, "diagnostics": [d.to_json() for d in diags]}
        all_diags += diags
    if with_kernels:
        report["kernels"] = []
        from paddle_tpu.analysis import spec_for_flash_packed, spec_for_flash
        from paddle_tpu.ops._pallas.flash_attention_packed import (
            _pick_blocks_packed, pack_group, HEAD_D)
        print("== pallas kernel configs")
        for sq, sk, h in ((512, 512, 12), (1024, 1024, 16)):
            g = pack_group(h) or 2
            dp = g * HEAD_D
            for bwd in (False, True):
                bq, bk = _pick_blocks_packed(sq, sk, dp, bwd=bwd)
                spec = spec_for_flash_packed(sq, sk, dp, bq, bk, g, bwd=bwd)
                diags = check_kernel_spec(spec)
                tag = f"{spec.name} sq{sq} sk{sk} g{g} blocks {bq}x{bk}"
                print(f"  {tag}: {len(diags)} diagnostic(s)")
                for d in diags:
                    print("    " + d.format())
                report["kernels"] += [d.to_json() for d in diags]
                all_diags += diags
        # the conv family at its default blocks for the byte-dominant
        # ResNet shapes (fwd + wgrad; dgrad reuses the fwd kernel spec)
        import numpy as np
        from paddle_tpu.analysis import (spec_for_conv_matmul,
                                         spec_for_conv3x3)
        from paddle_tpu.ops._pallas import conv as pconv
        print("== pallas conv configs (RESNET50_TOP3_SHAPES, bf16)")
        bf16 = np.dtype("bfloat16")
        for kind, n, h, w, cin, cout, s_ in pconv.RESNET50_TOP3_SHAPES:
            if kind == "conv1x1":
                m = n * ((h + s_ - 1) // s_) * ((w + s_ - 1) // s_)
                bm = pconv._pick_block_m(m, cin, cout, jnp.bfloat16)
                specs = [spec_for_conv_matmul(m, cin, cout, bm, dtype=bf16),
                         spec_for_conv_matmul(m, cin, cout, bm, dtype=bf16,
                                              wgrad=True)]
                cfg = f"m{m} ci{cin} co{cout} block_m {bm}"
            else:
                ho = (h + 2 - 3) // s_ + 1
                bh = pconv._pick_block_h(ho, n, h, w, cin, cout, s_,
                                         jnp.bfloat16)
                specs = [spec_for_conv3x3(n, h, w, cin, cout, bh, s_,
                                          dtype=bf16),
                         spec_for_conv3x3(n, h, w, cin, cout, bh, s_,
                                          dtype=bf16, wgrad=True)]
                cfg = f"n{n} {h}x{w} ci{cin} co{cout} s{s_} block_h {bh}"
            for spec in specs:
                diags = check_kernel_spec(spec)
                print(f"  {spec.name} {cfg}: {len(diags)} diagnostic(s)")
                for d in diags:
                    print("    " + d.format())
                report["kernels"] += [d.to_json() for d in diags]
                all_diags += diags
    if with_repo:
        print("== repo AST lint (paddle_tpu/ + tools/ + examples/ + "
              "__graft_entry__.py)")
        diags = repo_lint.lint_tree(REPO)
        for d in diags:
            if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]:
                print("  " + d.format())
        report["repo"] = [d.to_json() for d in diags]
        all_diags += diags
        from paddle_tpu.analysis import concurrency_check
        tdiags = concurrency_check.check_tree(REPO)
        print(f"== repo concurrency lint (T rules): {len(tdiags)} "
              "diagnostic(s)")
        for d in tdiags:
            if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]:
                print("  " + d.format())
        report["threads"] = [d.to_json() for d in tdiags]
        all_diags += tdiags
        unknown = core_flags.unknown_env_flags()
        if unknown:
            print(f"  note: unrecognized FLAGS_* env vars: {unknown}")
    errors = [d for d in all_diags if d.severity == "error"]
    print(f"total: {len(all_diags)} diagnostic(s), {len(errors)} error(s)")
    report["schema_version"] = SCHEMA_VERSION
    report["rule_index"] = _rule_index(all_diags)
    report["total_diagnostics"] = len(all_diags)
    report["errors"] = len(errors)
    return (1 if errors else 0), report


# ---------------------------------------------------------------------------
# --matrix: the tier-flag composition gate
# ---------------------------------------------------------------------------

# The matrix's step traces are cached by the composed-plan hash
# (pass_check.composed_plan_hash over the plan-only pipeline build):
# combos whose pipelines compose the same StepPlan trace/compile once.
# cp_nested_ring and pallas_conv live inside the loss function, not the
# pipeline, so they hash equal by construction (their components are
# checked separately below).


def _matrix_micro_step(remat: bool):
    """A tiny 2-block GPT TrainStep on the dp=2 x sharding=2 x mp=2
    hybrid mesh — every axis the composed tiers splice into, at shapes
    that trace in well under a second."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                                 set_hybrid_mesh)
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_flash_attention=False, recompute=bool(remat))
    model = GPTForCausalLM(cfg)
    mesh = create_hybrid_mesh(dp=2, sharding=2, mp=2)
    set_hybrid_mesh(mesh)

    def loss_fn(m, p, b):
        ids, labels = b
        return functional_call(m, p, ids, labels, training=True)

    ts = make_sharded_train_step(model, AdamW(1e-3), loss_fn, mesh=mesh)
    ids = jnp.zeros((4, 16), jnp.int32)
    return ts, (ids, ids)


def _matrix_step_diags(remat: bool, with_hlo: bool = True):
    """Build + trace the micro TrainStep under the current flags and run
    the full plan verification — and, with ``with_hlo``, AOT-compile the
    same step and run the X-rules over what XLA actually built; returns
    (diags, info)."""
    import time
    from paddle_tpu.analysis import hlo_check, plan_check
    from paddle_tpu.distributed.topology import set_hybrid_mesh
    try:
        ts, batch = _matrix_micro_step(remat)
        closed, donate = ts.trace_step(batch)
        diags = plan_check.check_plan(ts.plan, closed,
                                      donate_argnums=donate,
                                      where="matrix.step")
        info = {"eqns": len(closed.jaxpr.eqns),
                "plan": ts.plan.to_json()}
        if with_hlo:
            t0 = time.perf_counter()
            compiled, donated = ts.compile_step(batch)
            facts = hlo_check.collect_hlo_facts(compiled)
            diags += hlo_check.check_hlo(ts.plan, facts,
                                         donated_leaves=donated,
                                         where="matrix.step.hlo")
            info["hlo"] = dict(facts.to_json(),
                               verify_ms=round(
                                   (time.perf_counter() - t0) * 1e3, 1))
    finally:
        set_hybrid_mesh(None)
    return diags, info


def _matrix_sp_pair_diags():
    """The decomposed TP/SP pair traced fwd+grad on an mp-only mesh,
    with the comm registry recording —
    the declared-vs-actual ppermute cross-check (S001/S002) on the real
    decomposed path, plus the C-rule accounting of each recorded spec and
    the production-shape hop plans."""
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.analysis import comm_check, plan_check
    from paddle_tpu.distributed import overlap

    if jax.device_count() < 2:
        return [], {"skipped": "needs >= 2 devices"}
    n = 8 if jax.device_count() >= 8 else 2
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(1, 1, 1, 1, n),
                ("pp", "dp", "sharding", "sep", "mp"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8 * n, 16)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)

    def loss(x, w1, w2):
        h = overlap.allgather_matmul(x, w1, mesh=mesh, chunks=1)
        y = overlap.matmul_reduce_scatter(jax.nn.gelu(h), w2, mesh=mesh,
                                          chunks=1)
        return jnp.sum(y ** 2)

    with comm_check.recording() as rec:
        closed = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(1, 2)))(
            x, w1, w2)
    plan = plan_check.StepPlan(
        flags={"comm_overlap": "tp"},
        mesh_axes={str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        nodes=[plan_check.PlanNode("sp_pair", reads=("x", "w1", "w2"),
                                   writes=("loss", "grads"))],
        comm_specs=list(rec))
    diags = plan_check.check_plan(plan, closed, where="matrix.sp_pair")
    # production-shape hop plans (GPT-1.3B layer through mp=4)
    for spec in (comm_check.spec_for_allgather_matmul(
                     8, 512, 2048, 2048, 4, 2),
                 comm_check.spec_for_matmul_reduce_scatter(
                     8, 512, 2048, 2048, 4, 2)):
        diags += comm_check.check_comm_spec(spec)
    return diags, {"recorded_specs": len(rec),
                   "eqns": len(closed.jaxpr.eqns)}


def _matrix_multislice_diags(with_hlo: bool = True):
    """The multislice tier's composition check: the hierarchical 2-tier
    TrainStep traced on the 2-slice virtual mesh and verified against its
    declared StepPlan (S/D rules) + the recorded hop plan's C-rule
    errors — the micro step of the main matrix sweep has no 'slice' axis,
    so the tier is exercised here as a component (like the SP pair).
    With ``with_hlo`` the step is also AOT-compiled and X-rule-verified:
    the compiled reduce-scatter / all-reduce / all-gather kinds must all
    be justified by the recorded hierarchical-stage CommSpecs, and no
    DCN-crossing collective may sit in a compiled loop body (X005)."""
    from paddle_tpu.analysis import comm_check, hlo_check, plan_check
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed.topology import set_hybrid_mesh

    if jax.device_count() < 4:
        return [], {"skipped": "needs >= 4 devices"}
    try:
        topo, ts, batch = _multislice_micro_step("hierarchical")
        closed, donate = ts.trace_step(batch)
        diags = plan_check.check_plan(ts.plan, closed,
                                      donate_argnums=donate,
                                      where="matrix.multislice")
        for _, spec in ts.plan.comm_specs:
            diags += [d for d in comm_check.check_comm_spec(spec)
                      if d.severity == "error"]
        info = {"eqns": len(closed.jaxpr.eqns),
                "dcn_axes": topo.dcn_axes(),
                "comm_specs": len(ts.plan.comm_specs)}
        if with_hlo:
            compiled, donated = ts.compile_step(batch)
            facts = hlo_check.collect_hlo_facts(compiled)
            diags += hlo_check.check_hlo(ts.plan, facts,
                                         donated_leaves=donated,
                                         where="matrix.multislice.hlo")
            info["hlo"] = facts.to_json()
    finally:
        set_flags({"multislice": "off"})
        set_hybrid_mesh(None)
    return diags, info


def _matrix_cp_ring_diags():
    """Static hop accounting of the ring-CP tier at a long-context shape
    (S=32k over sep=4, GPT-1.3B heads): the arithmetic half of the
    cp_nested_ring composition — the nested-ring trace itself needs the
    pipeline runtime (new-jax dryrun[7])."""
    from paddle_tpu.analysis import comm_check
    spec = comm_check.spec_for_cp_ring(
        b=1, s_local=8192, heads=16, head_dim=128, n=4, itemsize=2)
    return comm_check.check_comm_spec(spec), {
        "hops": spec.hops, "mib_per_hop": round(spec.bytes_per_hop / 2**20,
                                                2)}


def _matrix_conv_diags():
    """The pallas_conv tier's kernel-config checks (P-rules) at its
    default blocks over the byte-dominant ResNet shapes."""
    import numpy as np
    from paddle_tpu.analysis import (check_kernel_spec, spec_for_conv3x3,
                                     spec_for_conv_matmul)
    from paddle_tpu.ops._pallas import conv as pconv
    diags = []
    bf16 = np.dtype("bfloat16")
    for kind, n, h, w, cin, cout, s_ in pconv.RESNET50_TOP3_SHAPES:
        if kind == "conv1x1":
            m = n * ((h + s_ - 1) // s_) * ((w + s_ - 1) // s_)
            bm = pconv._pick_block_m(m, cin, cout, jnp.bfloat16)
            diags += check_kernel_spec(
                spec_for_conv_matmul(m, cin, cout, bm, dtype=bf16))
        else:
            ho = (h + 2 - 3) // s_ + 1
            bh = pconv._pick_block_h(ho, n, h, w, cin, cout, s_,
                                     jnp.bfloat16)
            diags += check_kernel_spec(
                spec_for_conv3x3(n, h, w, cin, cout, bh, s_, dtype=bf16))
    return diags, {"shapes": len(pconv.RESNET50_TOP3_SHAPES)}


def run_dryruns():
    """The ten multichip dryrun scenarios (__graft_entry__._dryrun_base)
    in a subprocess on the 8-device virtual mesh."""
    env = dict(os.environ)
    env["_GRAFT_DRYRUN_NO_ESCALATE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    code = (f"import sys; sys.path.insert(0, {REPO!r}); "
            "import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import __graft_entry__ as g; g.dryrun_multichip(8)")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True)
    import re
    scenarios = sorted(set(
        int(m) for m in re.findall(r"dryrun_multichip\[(\d+)\]",
                                   proc.stdout)))
    ok = proc.returncode == 0 and len(scenarios) >= 10
    out = {"ok": ok, "returncode": proc.returncode, "scenarios": scenarios}
    if not ok:
        out["tail"] = (proc.stdout + proc.stderr)[-2000:]
    return out


def run_matrix(min_severity="info", json_mode=False, with_dryrun=True,
               combos=None, with_hlo=True):
    """Enumerate the tier-flag combinations, verify each composition —
    including the compiled-HLO X-rule pass per trace-distinct step,
    unless ``with_hlo=False`` — and (optionally) run the ten dryrun
    scenarios. Exits nonzero on any error-severity diagnostic or dryrun
    failure."""
    if json_mode:
        import contextlib
        with contextlib.redirect_stdout(sys.stderr):
            rc, report = _run_matrix_impl(min_severity, with_dryrun, combos,
                                          with_hlo)
        print(json.dumps(report, indent=2))
        return rc
    rc, _ = _run_matrix_impl(min_severity, with_dryrun, combos, with_hlo)
    return rc


def _run_matrix_impl(min_severity="info", with_dryrun=True, combos=None,
                     with_hlo=True):
    import tools.hbm_budget as hbm_budget
    from paddle_tpu.analysis import pass_check, plan_check
    from paddle_tpu.core import flags as core_flags
    from paddle_tpu.framework import step_pipeline
    from paddle_tpu.ops._pallas import conv as _pconv  # registers the flag
    del _pconv

    tier_names = [n for n, _ in plan_check.TIER_FLAGS]
    prev = {n: core_flags.flag(n) for n in tier_names
            if n in core_flags.get_flags()}
    # every combo — caller-supplied included — through the one
    # normalization entry point (legacy 5-flag dicts warn once there)
    combos = list(plan_check.iter_tier_combos()) if combos is None \
        else list(combos)
    combos = [plan_check.normalize_combo(c) for c in combos]
    step_cache = {}
    component_cache = {}
    report = {"combos": [], "errors": 0,
              "passes": {
                  "order": [p.contract.name for p in step_pipeline.PIPELINE],
                  "contracts": {
                      p.contract.name: pass_check.contract_hash(p.contract)
                      for p in step_pipeline.PIPELINE}}}
    n_errors = 0
    all_diags = []
    try:
        for combo in combos:
            core_flags.set_flags({
                "offload_optimizer": combo["offload_optimizer"],
                "comm_overlap": combo["comm_overlap"],
                "multislice": combo["multislice"],
                "cp_nested_ring": combo["cp_nested_ring"],
                "pallas_conv": combo["pallas_conv"],
            })
            diags = []
            entry = {"flags": dict(combo)}
            # (a0) the combo composed plan-only through the pass pipeline:
            # the G-rule gate, and the composed-plan hash that keys the
            # step trace cache + the CI composition diff
            pbuild = step_pipeline.compose(step_pipeline.plan_only_build(combo))
            diags += pbuild.diagnostics
            plan_hash = pass_check.composed_plan_hash(pbuild.plan)
            entry["passes"] = {
                "order": [c.name for c in pbuild.contracts],
                "plan_hash": plan_hash}
            # (a) the composed StepPlan, traced + verified (cached by the
            # composed-plan hash: combos whose pipelines emit the same
            # plan share one trace; cp/pallas_conv don't enter the
            # pipeline — their components are checked below)
            if plan_hash not in step_cache:
                step_cache[plan_hash] = _matrix_step_diags(
                    combo["remat"], with_hlo=with_hlo)
            sdiags, sinfo = step_cache[plan_hash]
            diags += sdiags
            entry["step"] = {"eqns": sinfo.get("eqns")}
            if "hlo" in sinfo:
                entry["step"]["hlo"] = sinfo["hlo"]
            # (b) tier components the micro step cannot carry
            if combo["comm_overlap"] != "off":
                if "sp" not in component_cache:
                    component_cache["sp"] = _matrix_sp_pair_diags()
                diags += component_cache["sp"][0]
            if combo["multislice"] != "off":
                # the micro step's mesh has no 'slice' axis (the tier is
                # inert there by design); the 2-slice composition is
                # checked once as a component
                if "multislice" not in component_cache:
                    component_cache["multislice"] = \
                        _matrix_multislice_diags(with_hlo=with_hlo)
                diags += component_cache["multislice"][0]
            if combo["cp_nested_ring"]:
                if "cp" not in component_cache:
                    component_cache["cp"] = _matrix_cp_ring_diags()
                diags += component_cache["cp"][0]
            if combo["pallas_conv"]:
                if "conv" not in component_cache:
                    component_cache["conv"] = _matrix_conv_diags()
                diags += component_cache["conv"][0]
            # (c) capacity: the flagship config this composition is held
            # to (full-depth GPT-1.3B when offloaded, L=12 otherwise)
            cap = hbm_budget.tier_plan(
                offload=combo["offload_optimizer"],
                remat=bool(combo["remat"]))
            diags += plan_check.check_capacity(cap, where="matrix.hbm")
            entry["hbm"] = {"fits": cap["fits"],
                            "device_gb": cap["device_gb"],
                            "layers": cap["config"]["layers"],
                            "batch": cap["config"]["batch"]}
            errors = [d for d in diags if d.severity == "error"]
            n_errors += len(errors)
            all_diags += diags
            entry["diagnostics"] = [d.to_json() for d in diags]
            entry["errors"] = len(errors)
            report["combos"].append(entry)
            tag = " ".join(f"{k}={combo.get(k, 'off')}"
                           for k in tier_names)
            print(f"== matrix {tag}: {len(diags)} diagnostic(s), "
                  f"{len(errors)} error(s)")
            for d in diags:
                if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]:
                    print("  " + d.format())
    finally:
        core_flags.set_flags(prev)
    if with_dryrun:
        dry = run_dryruns()
        report["dryrun"] = dry
        if dry.get("skipped"):
            print(f"== dryrun scenarios: SKIPPED ({dry['skipped']})")
        else:
            print(f"== dryrun scenarios: {dry['scenarios']} "
                  f"{'ok' if dry['ok'] else 'FAILED'}")
            if not dry["ok"]:
                n_errors += 1
                print(dry.get("tail", ""))
    report["schema_version"] = SCHEMA_VERSION
    report["rule_index"] = _rule_index(all_diags)
    report["errors"] = n_errors
    print(f"matrix total: {len(report['combos'])} combination(s), "
          f"{n_errors} error(s)")
    return (1 if n_errors else 0), report


# ---------------------------------------------------------------------------
# --passes: the step-compiler pass-pipeline verifier (G rules)
# ---------------------------------------------------------------------------

def _passes_selftests():
    """Seeded bad compositions: G001 (a pass ordered before its
    provider), G002 (conflicting buffer ownership with no declared
    handoff), G004 (an undeclared order-sensitive pair) must each fire —
    the gate that proves each rule still detects its hazard class."""
    import dataclasses
    from paddle_tpu.analysis.jaxpr_lint import Diagnostic
    from paddle_tpu.analysis.pass_check import PassContract
    from paddle_tpu.framework import step_pipeline as sp

    pipe = {p.contract.name: p for p in sp.PIPELINE}
    combo = {"offload_optimizer": "moments", "comm_overlap": "tp_zero",
             "multislice": "off", "cp_nested_ring": False,
             "pallas_conv": 0, "remat": False}

    def fired(rule, order, **kw):
        b = sp.plan_only_build(combo, **kw)
        sp.compose(b, order=order)
        return any(d.rule == rule for d in b.diagnostics)

    class _Rogue(sp.StepPass):
        # writes/donates base_grad's params with no declared handoff
        contract = PassContract(
            name="rogue", requires=("grads",), provides=("rogue",),
            terminal=("rogue",), plan_writes=("params",),
            plan_donates=("params",))

    class _NoEdgeSentinel(sp.HealthSentinelPass):
        # the genuinely order-sensitive sentinel<->offload pair with its
        # declared edge stripped
        contract = dataclasses.replace(sp.HealthSentinelPass.contract,
                                       order_after=())

    results = {
        "G001": fired("G001", [pipe["offload_stream"], pipe["base_grad"]]),
        "G002": fired("G002", [pipe["base_grad"], _Rogue(),
                               pipe["offload_stream"]]),
        "G004": fired("G004",
                      [_NoEdgeSentinel() if isinstance(
                          p, sp.HealthSentinelPass) else p
                       for p in sp.PIPELINE],
                      health_sentinel=True),
    }
    diags = []
    for rule, ok in sorted(results.items()):
        if not ok:
            diags.append(Diagnostic(
                rule=rule, name="selftest-missing", severity="error",
                message=f"self-test: {rule} did not fire on its seeded "
                        "bad composition",
                where="passes.selftest"))
    return results, diags


def run_passes(min_severity="info", json_mode=False):
    """The pass-pipeline G-rule gate standalone: the declared pipeline
    (ordered pass list + per-pass contract hashes), every tier combo in
    BOTH sentinel arms composed plan-only and G-rule-verified (256
    compositions, incl. sentinel x offload), and the seeded per-rule
    self-tests."""
    if json_mode:
        import contextlib
        with contextlib.redirect_stdout(sys.stderr):
            rc, report = _run_passes_impl(min_severity)
        print(json.dumps(report, indent=2))
        return rc
    rc, _ = _run_passes_impl(min_severity)
    return rc


def _run_passes_impl(min_severity="info"):
    from paddle_tpu.analysis import pass_check, plan_check
    from paddle_tpu.framework import step_pipeline as sp
    all_diags = []
    report = {
        "schema_version": SCHEMA_VERSION,
        "passes": {
            "order": [p.contract.name for p in sp.PIPELINE],
            "contracts": {
                p.contract.name: pass_check.contract_hash(p.contract)
                for p in sp.PIPELINE}},
        "combos": [],
    }
    print("== pass pipeline: "
          + " -> ".join(report["passes"]["order"]))
    for name, h in report["passes"]["contracts"].items():
        print(f"  contract {name}: {h}")
    n_hashes = set()
    for combo in plan_check.iter_tier_combos():
        for sentinel in (False, True):
            b = sp.plan_only_build(combo, health_sentinel=sentinel)
            sp.compose(b)
            h = pass_check.composed_plan_hash(b.plan)
            n_hashes.add(h)
            errors = [d for d in b.diagnostics if d.severity == "error"]
            report["combos"].append({
                "flags": dict(combo, health_sentinel=sentinel),
                "order": [c.name for c in b.contracts],
                "plan_hash": h,
                "diagnostics": [d.to_json() for d in b.diagnostics],
                "errors": len(errors)})
            all_diags += b.diagnostics
            for d in b.diagnostics:
                if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]:
                    print("  " + d.format())
    print(f"== {len(report['combos'])} compositions "
          f"(incl. sentinel arms), {len(n_hashes)} distinct plan hash(es)")
    fired, st_diags = _passes_selftests()
    print("== passes self-tests (each rule must fire on its seeded "
          "bad composition)")
    for rule, ok in sorted(fired.items()):
        print(f"  {rule}: {'fires' if ok else 'MISSING'}")
    report["selftests"] = fired
    all_diags += st_diags
    errors = [d for d in all_diags if d.severity == "error"]
    report["rule_index"] = _rule_index(all_diags)
    report["total_diagnostics"] = len(all_diags)
    report["errors"] = len(errors)
    print(f"passes total: {len(all_diags)} diagnostic(s), "
          f"{len(errors)} error(s)")
    return (1 if errors else 0), report


# ---------------------------------------------------------------------------
# --threads: the host-concurrency verifier (T rules)
# ---------------------------------------------------------------------------

# Seeded-positive fixtures: one per T rule, each MUST fire — the gate
# that proves the rule still detects the hazard class it was built for.
THREADS_FIXTURES = {
    "T001": ("t001.py", """
import threading
class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0
    def inc(self):
        with self._lock:
            self.n += 1
    def reset(self):
        self.n = 0
"""),
    "T002": ("t002.py", """
import threading
class TwoLocks:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
    def ab(self):
        with self._a:
            with self._b:
                pass
    def ba(self):
        with self._b:
            with self._a:
                pass
"""),
    "T003": ("t003.py", """
import os
import threading
class Journal:
    def __init__(self):
        self._lock = threading.Lock()
        self.f = None
    def write(self):
        with self._lock:
            os.fsync(self.f.fileno())
"""),
    "T004": ("t004.py", """
import threading
class Spawner:
    def spawn(self):
        t = threading.Thread(target=self._work)
        t.start()
        self._t = t
    def arm(self):
        self._timer = threading.Timer(1.0, self._work)
        self._timer.start()
    def _work(self):
        pass
"""),
    "T005": ("serving/engine.py", """
class Engine:
    def _finish(self, seq):
        self.detokenizer(seq)
        self.journal.done(seq.rid, [])
"""),
}


def _threads_selftests():
    """Run every fixture through the analyzer; a rule that does NOT fire
    on its seeded positive is itself an error."""
    from paddle_tpu.analysis import concurrency_check
    from paddle_tpu.analysis.jaxpr_lint import Diagnostic
    diags, fired = [], {}
    for rule, (relpath, src) in sorted(THREADS_FIXTURES.items()):
        got = concurrency_check.check_source(src, relpath)
        fired[rule] = any(d.rule == rule for d in got)
        if not fired[rule]:
            diags.append(Diagnostic(
                rule=rule, name="selftest-missing", severity="error",
                message=f"self-test: {rule} did not fire on its seeded "
                        f"positive fixture {relpath}",
                where="threads.selftest"))
    return fired, diags


def run_threads(min_severity="info", json_mode=False):
    """The T-rule pass standalone: the seeded per-rule self-tests (every
    rule must fire on its positive fixture) + the whole-repo sweep
    (which must be clean) + the repo-wide static lock acquisition graph
    cycle check."""
    if json_mode:
        import contextlib
        with contextlib.redirect_stdout(sys.stderr):
            rc, report = _run_threads_impl(min_severity)
        print(json.dumps(report, indent=2))
        return rc
    rc, _ = _run_threads_impl(min_severity)
    return rc


def _run_threads_impl(min_severity="info"):
    from paddle_tpu.analysis import concurrency_check
    all_diags = []
    report = {"schema_version": SCHEMA_VERSION}
    fired, st_diags = _threads_selftests()
    print("== threads self-tests (each rule must fire on its fixture)")
    for rule, ok in sorted(fired.items()):
        print(f"  {rule}: {'fires' if ok else 'MISSING'}")
    report["selftests"] = fired
    all_diags += st_diags
    repo_diags = concurrency_check.check_tree(REPO)
    print(f"== repo concurrency lint (T rules over paddle_tpu/ + tools/ "
          f"+ examples/): {len(repo_diags)} diagnostic(s)")
    for d in repo_diags:
        if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]:
            print("  " + d.format())
    report["repo"] = [d.to_json() for d in repo_diags]
    all_diags += repo_diags
    # the cross-module static acquisition graph: cycles anywhere in the
    # tree, including across files one module's T002 pass cannot see
    mods = concurrency_check.collect_module_facts(REPO)
    edges = concurrency_check.acquisition_graph(mods)
    cycles = concurrency_check.find_lock_cycles(edges)
    cycles = [c for c in cycles if len(c) >= 3]
    print(f"== static lock graph: {len(edges)} edge(s), "
          f"{len(cycles)} cycle(s)")
    report["lock_graph"] = {
        "edges": len(edges), "cycles": [" -> ".join(c) for c in cycles]}
    if cycles:
        from paddle_tpu.analysis.jaxpr_lint import Diagnostic
        for c in cycles:
            all_diags.append(Diagnostic(
                rule="T002", name="lock-order-inversion", severity="error",
                message="cross-module lock acquisition cycle "
                        + " -> ".join(c),
                where="threads.graph"))
    errors = [d for d in all_diags if d.severity == "error"]
    report["rule_index"] = _rule_index(all_diags)
    report["total_diagnostics"] = len(all_diags)
    report["errors"] = len(errors)
    print(f"threads total: {len(all_diags)} diagnostic(s), "
          f"{len(errors)} error(s)")
    return (1 if errors else 0), report


# ---------------------------------------------------------------------------
# --hlo: the compiled-HLO verifier, standalone
# ---------------------------------------------------------------------------

def run_hlo(min_severity="info", json_mode=False):
    """AOT-compile the representative composed steps and run the X-rules
    (analysis/hlo_check) over what XLA actually built: the hybrid-mesh
    micro TrainStep, the serving decode executable, the 2-slice
    multislice step, plus a seeded undeclared-collective self-test (X001
    must fire on GSPMD resharding nothing declared — the rule exists to
    catch exactly that)."""
    if json_mode:
        import contextlib
        with contextlib.redirect_stdout(sys.stderr):
            rc, report = _run_hlo_impl(min_severity)
        print(json.dumps(report, indent=2))
        return rc
    rc, _ = _run_hlo_impl(min_severity)
    return rc


def _hlo_seeded_x001_selftest():
    """X001 must fire on a compiled resharding all-gather nothing
    declared (replicated params, an intermediate constrained onto a mesh
    axis: GSPMD gathers it back — the sneaked-in collective)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.analysis import hlo_check, plan_check

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("dp",))
    repl = NamedSharding(mesh, P())

    def sneaky(w, x):
        h = jax.lax.with_sharding_constraint(
            x @ w, NamedSharding(mesh, P(None, "dp")))
        return jnp.tanh(h) @ w

    compiled = jax.jit(sneaky, in_shardings=(repl, repl),
                       out_shardings=repl).lower(
        jnp.ones((16, 16)), jnp.ones((8, 16))).compile()
    plan = plan_check.StepPlan(mesh_axes={"dp": n})
    diags = hlo_check.check_hlo(plan, compiled, where="hlo.selftest")
    return [d for d in diags if d.rule == "X001"]


def _run_hlo_impl(min_severity="info"):
    from paddle_tpu.analysis import hlo_check
    from paddle_tpu.analysis.jaxpr_lint import Diagnostic
    all_diags = []
    report = {"targets": {}, "schema_version": SCHEMA_VERSION}

    def verify(name, compiled, plan, donated):
        import time
        t0 = time.perf_counter()
        facts = hlo_check.collect_hlo_facts(compiled)
        diags = hlo_check.check_hlo(plan, facts, donated_leaves=donated,
                                    where=f"hlo.{name}")
        ms = round((time.perf_counter() - t0) * 1e3, 1)
        print(f"== hlo {name}: {facts.to_json()}, verify {ms} ms, "
              f"{len(diags)} diagnostic(s)")
        for d in diags:
            if _SEV_RANK[d.severity] >= _SEV_RANK[min_severity]:
                print("  " + d.format())
        report["targets"][name] = dict(facts.to_json(), verify_ms=ms,
                                       diagnostics=[d.to_json()
                                                    for d in diags])
        all_diags.extend(diags)

    # (a) the hybrid-mesh micro TrainStep (the --matrix micro model)
    from paddle_tpu.distributed.topology import set_hybrid_mesh
    try:
        ts, batch = _matrix_micro_step(False)
        ts.trace_step(batch)  # fills plan.comm_specs
        compiled, donated = ts.compile_step(batch)
        verify("train_step", compiled, ts.plan, donated)
    finally:
        set_hybrid_mesh(None)
    # (b) the serving decode executable at its smallest bucket
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    cfg = gpt_tiny(vocab_size=128, hidden_size=48, num_layers=2,
                   num_heads=4, max_position_embeddings=64)
    eng = ServingEngine(GPTForCausalLM(cfg), block_size=4, num_blocks=32,
                        max_batch=4)
    compiled, donated = eng.compile_decode()
    verify("serving_decode", compiled, eng.plan, donated)
    # (c) the 2-slice multislice step (hierarchical reduction compiled)
    if jax.device_count() >= 4:
        from paddle_tpu.core.flags import set_flags
        try:
            topo, ms_ts, ms_batch = _multislice_micro_step("hierarchical")
            ms_ts.trace_step(ms_batch)
            compiled, donated = ms_ts.compile_step(ms_batch)
            verify("multislice_step", compiled, ms_ts.plan, donated)
        finally:
            set_flags({"multislice": "off"})
            set_hybrid_mesh(None)
    # (d) X001 self-test: the seeded undeclared collective must fire
    fired = _hlo_seeded_x001_selftest()
    print(f"== hlo X001 on the seeded undeclared resharding gather: "
          f"{'fires' if fired else 'MISSING'}")
    report["x001_selftest_fires"] = bool(fired)
    if not fired:
        all_diags.append(Diagnostic(
            rule="X001", name="undeclared-compiled-collective",
            severity="error",
            message="self-test: X001 did not fire on a compiled "
                    "resharding all-gather with nothing declared",
            where="hlo.selftest"))
    errors = [d for d in all_diags if d.severity == "error"]
    report["rule_index"] = _rule_index(all_diags)
    report["errors"] = len(errors)
    print(f"hlo total: {len(all_diags)} diagnostic(s), "
          f"{len(errors)} error(s)")
    return (1 if errors else 0), report


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", choices=sorted(MODELS), action="append",
                   help="model graph(s) to lint (repeatable)")
    p.add_argument("--all", action="store_true",
                   help="lint every model + pallas kernel configs + repo AST")
    p.add_argument("--matrix", action="store_true",
                   help="verify every tier-flag combination's composed "
                        "StepPlan (+ compiled-HLO X-rules) + the ten "
                        "dryrun scenarios")
    p.add_argument("--hlo", action="store_true",
                   help="compiled-HLO verifier (X-rules) over the "
                        "representative composed steps + the X001 "
                        "seeded self-test")
    p.add_argument("--threads", action="store_true",
                   help="host-concurrency verifier (T-rules): per-rule "
                        "seeded self-tests + the repo sweep + the "
                        "static lock-order graph")
    p.add_argument("--passes", action="store_true",
                   help="step-compiler pass-pipeline verifier (G-rules): "
                        "contract hashes, every tier combo composed "
                        "plan-only, + seeded G001/G002/G004 self-tests")
    p.add_argument("--no-dryrun", action="store_true",
                   help="with --matrix: skip the multichip dryrun scenarios")
    p.add_argument("--no-hlo", action="store_true",
                   help="with --matrix: skip the compiled-HLO X-rule pass")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report on stdout (narration "
                        "moves to stderr)")
    p.add_argument("--min-severity", choices=["info", "warning", "error"],
                   default="info", help="only print findings at or above")
    a = p.parse_args(argv)
    if a.matrix:
        return run_matrix(min_severity=a.min_severity, json_mode=a.json,
                          with_dryrun=not a.no_dryrun,
                          with_hlo=not a.no_hlo)
    if a.hlo:
        return run_hlo(min_severity=a.min_severity, json_mode=a.json)
    if a.passes:
        return run_passes(min_severity=a.min_severity, json_mode=a.json)
    if a.threads:
        return run_threads(min_severity=a.min_severity, json_mode=a.json)
    if a.all:
        models = sorted(MODELS)
    else:
        models = a.model or ["bert"]
    return run(models, with_kernels=a.all, with_repo=a.all,
               min_severity=a.min_severity, json_mode=a.json)


if __name__ == "__main__":
    sys.exit(main())
