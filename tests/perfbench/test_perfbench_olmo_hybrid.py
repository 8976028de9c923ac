"""The Olmo-Hybrid family through the harness's own entry at a tiny size on
the CPU: its cell's files are found by name, the configuration is the
catalog's but for the cut, the family's counts are what its shapes say, a run
reports the new cell's metrics (the state counter's among them), the two new
readers read made-up records, and the comparison passes a sound run and
catches five planted faults: the state reset every decode step, beta without
its factor 2, the convolution's tail dropped between prefill and decode, the
decay ignored, the full layer's q/k norm left out. Nothing here is a
measurement."""

import contextlib
import json
import os

import numpy as np
import pytest

from perfbench_tiny import ROOT, SEED, manifest

from benchmark import run as R
from benchmark.lib import peaks as P
from benchmark.lib import readers
from benchmark.lib import trace as TR
from benchmark.lib.family import load_family

CELL = "serve.olmo-hybrid-7b-l4.reason1k-closed256"
# The published config.json (the source's numbers), but the 32 layer types:
# the configuration keeps each key as published unless it is listed as cut
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
# Limit as perfbench_tiny.TINY_LIMITS are set, from readings (CPU runs at
# these sizes in float32, seeds SEED..SEED+2, six requests compared):
# the honest program's served gap 0.0 on every reading; the state reset
# 0.062-0.170, beta halved 0.032-0.047, the tail dropped 0.019-0.043, the
# decay ignored 0.025-0.127, no q/k norm 0.96-1.27.
LIMITS = {"served_logit_gap_max": 0.005}

MIX = {"kind": "closed_loop", "clients": 3, "preroll_s": 0.3,
       "n_sizes": 16, "pairing_seed": 7,
       "prompt_len": {"dist": "log_uniform", "lo": 5, "hi": 40},
       "output_len": {"dist": "uniform", "lo": 5, "hi": 14},
       "engine": {"max_batch": 4, "max_seq_len": 64, "block_size": 16,
                  "prefill_buckets": [48], "decode_buckets": [4]}}


def tiny_cfg():
    with open(os.path.join(ROOT,
                           "benchmark/configs/olmo-hybrid-7b-l4.json")) as f:
        cfg = json.load(f)
    # one period, d_v = 2 d_k as published, a full layer's head of 128 (its
    # pool then takes the layout it has on the chip)
    cfg.update(hidden_size=256, intermediate_size=96, num_attention_heads=2,
               num_key_value_heads=2, linear_num_key_heads=2,
               linear_num_value_heads=2, linear_key_head_dim=16,
               linear_value_head_dim=32, vocab_size=4096,
               max_position_embeddings=128, a_log_offset=[-4.0, -1.0])
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    return cfg


def tiny_cell(sample=3):
    man = manifest()
    mine = lambda ms: [m for m in ms if CELL in m.get("workloads", [CELL])]
    cfg = tiny_cfg()
    return R.Cell("tiny.olmo_hybrid", cfg, MIX, 1, load_family(ROOT, cfg),
                  {"check": {"sample": sample}, "limits": LIMITS},
                  mine(man["end_to_end"]), mine(man["per_layer"]))


def test_the_cells_files_are_found_by_name():
    cell = R.load_cell(ROOT, CELL)
    assert cell.family.name == "olmo_hybrid" and cell.chips == 1
    mix, eng = cell.mix, cell.mix["engine"]
    assert (mix["kind"], mix["clients"], mix["n_sizes"],
            mix["pairing_seed"]) == ("closed_loop", 256, 32, 7)
    assert mix["prompt_len"] == {"dist": "log_uniform", "lo": 128,
                                 "hi": 1024}
    assert mix["output_len"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert (eng["max_batch"], eng["max_seq_len"], eng["block_size"],
            eng["num_blocks"]) == (256, 2048, 16, 24576)
    assert eng["prefill_buckets"] == [256, 512, 1024]
    assert eng["decode_buckets"] == [256]
    assert not (eng["prefix_cache"] or eng["chunked_prefill"]
                or eng["speculative"])
    assert cell.extra["check"]["sample"] == 6
    assert "served_logit_gap_max" in cell.extra["limits"]
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"serve.tokens_per_s", "serve.itl_p95_ms", "setup_s",
            "kernels.gdn_decode_roofline", "engine.state_read_useful_share",
            "engine.kv_read_useful_share", "engine.decode_batch_mean",
            "model.decode_step_ms", "model.prefill_ms", "serve.step_mfu",
            "kernels.decode_step_roofline", "device.idle_share.serve",
            "host.gc_pause_max_ms.serve", "setup.trace_lower_s",
            "setup.compile_or_cache_s"} <= names
    assert not names & {"kernels.paged_attention_roofline",
                        "kernels.block_paged_attention_roofline",
                        "kernels.mla_decode_roofline",
                        "diffusion.passes_per_block",
                        "diffusion.rows_per_pass",
                        "moe.held_assignments_per_token"}
    # the pool holds the live set with room to spare (no preemption in a
    # window), and the longest request fits
    from benchmark.lib.traffic import size_set
    sizes = size_set(mix)
    live = np.mean([p + o / 2 for p, o in sizes]) * mix["clients"]
    assert live < 0.6 * eng["num_blocks"] * eng["block_size"]
    assert max(p + o for p, o in sizes) <= eng["max_seq_len"]


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    cfg = R.load_cell(ROOT, CELL).cfg
    assert sorted(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == 4
    assert cfg["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    for key in ("nope", "norm_placement", "state_float32", "positions",
                "decay_offset", "initializer"):
        assert key in cfg["assumed"], key
    assert "8-stage" in cfg["deployment"]
    assert cfg["precision"]["state"] == "float32"
    man = next(c for c in manifest()["configs"]
               if c["name"] == "olmo-hybrid-7b-l4")
    assert man["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert sorted(man["reduced"]) == sorted(cfg["reduced"])
    for key, want in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == want, key
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 8


def test_the_familys_counts_are_what_its_shapes_say():
    cell = R.load_cell(ROOT, CELL)
    cfg, needs, W = cell.cfg, cell.family.needs, cell.family.weights
    shapes = W.leaf_shapes(cfg)

    def size(name):
        shape = shapes
        for part in name.split("."):
            shape = shape[int(part)] if isinstance(shape, list) \
                else shape[part]
        return int(np.prod(shape))
    held = sum(size(n) for n in W.leaf_names(cfg))
    assert needs.n_params(cfg) == held
    assert 1.602e9 < held < 1.604e9                     # 1.603 B
    per_layer = [sum(size(n) for n in W.leaf_names(cfg)
                     if n.startswith(f"layers.{i}.")) for i in range(4)]
    assert 215.5e6 < per_layer[0] < 215.7e6           # a linear layer
    assert 185.7e6 < per_layer[3] < 185.9e6           # the full one
    assert needs.state_bytes(cfg) == 96 * 30 * 192 * 4  # 2.21 MB
    assert needs.tail_bytes(cfg) == 3 * 11520 * 2
    assert needs.kv_bytes_per_token(cfg) == 2 * 30 * 128 * 2
    f, b = needs.gdn_decode_call_needs(cfg, 256)
    assert f == 256 * 7 * 30 * 96 * 192
    assert b == 256 * (2 * 96 * 30 * 192 * 4 + 4 * 30 * (2 * 96 + 2 * 192
                                                          + 2))
    # bound by its bytes on a v5e
    pk = P.peaks_of("TPU v5 lite")
    assert f / pk.flops_bf16 < b / pk.hbm_bytes_s
    ctx = [750] * 256
    f_step, b_step = needs.decode_step_needs(cfg, ctx)
    state = 256 * 3 * 2 * (needs.state_bytes(cfg) + needs.tail_bytes(cfg))
    assert b_step == needs.weight_bytes(cfg) + 256 * 750 * 15360 + state
    # a step of 256 rows: weights ~2.44 GB, state ~3.40 GB, K/V ~2.95 GB
    assert 2.43e9 < needs.weight_bytes(cfg) < 2.45e9
    assert 3.39e9 < 256 * 3 * 2 * needs.state_bytes(cfg) < 3.41e9
    assert f_step == pytest.approx(256 * (2 * needs.matmul_params(cfg)
                                          + 750 * 4 * 128 * 30
                                          + 3 * needs.state_flops_per_token(
                                              cfg)))
    assert needs.serve_flops(cfg, [], ctx) == pytest.approx(f_step)
    one = needs.serve_flops(cfg, [(10, 0)], [])
    assert one == pytest.approx(
        10 * (2 * needs.matmul_params(cfg) - 2 * 3840 * 100352
              + 3 * needs.state_flops_per_token(cfg))
        + 2 * 3840 * 100352 + 4 * 128 * 30 * 55)
    assert list(W.a_log_offset(cfg)[[0, -1]]) == [-6.5, -1.875]
    assert all((W.a_log_offset(cfg) * 32) % 1 == 0)
    with pytest.raises(NotImplementedError, match="no training"):
        cell.family.reference.Reference(cfg).train_step(None, None, None,
                                                        None)


@pytest.fixture(scope="module")
def made_once():
    """The runs of this file share their weights (by seed) and their
    reference's compiled layers (by precision): each run would otherwise
    draw and compile them anew, twice, for the same tiny configuration."""
    from benchmark.lib import weights as WL
    ref_mod = load_family(ROOT, tiny_cfg()).reference
    make_weights, Reference = WL.make_weights, ref_mod.Reference
    drawn, refs = {}, {}

    def weights_once(fam_weights, cfg, seed, dtype=None, **kw):
        key = (json.dumps(cfg, sort_keys=True), seed, str(dtype), str(kw))
        if key not in drawn:
            args = (fam_weights, cfg, seed) + ((dtype,) if dtype else ())
            drawn[key] = make_weights(*args, **kw)
        return drawn[key]

    def reference_once(cfg, mode="float32"):
        key = (json.dumps(cfg, sort_keys=True), mode)
        if key not in refs:
            refs[key] = Reference(cfg, mode)
        return refs[key]
    WL.make_weights, ref_mod.Reference = weights_once, reference_once
    yield
    WL.make_weights, ref_mod.Reference = make_weights, Reference


@pytest.fixture(scope="module")
def sound(made_once):
    return R.run_cell(tiny_cell(), SEED, 1.5, True, require_chip=False)


def test_tiny_run_is_correct_and_reports_the_new_cells_metrics(sound):
    res = sound
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert res["compared"]["served_logit_gap_max"]["value"] is not None
    got = res["metrics"]
    # the dense path off the chip gathers every row's slot, pad rows too
    share = got["engine.state_read_useful_share"]
    assert 0.0 < share["value"] <= 100.0 and share["unit"] == "%"
    assert "engine.kv_read_useful_share" in got
    # a CPU run has no device number: no roofline, whatever the family
    assert not any("roofline" in n or "mfu" in n for n in got)


@contextlib.contextmanager
def planted(cell, fault):
    """``cell``'s engine built with one fault of the mathematics planted."""
    real = cell.family.adapter.build_engine

    def tampering(cfg_, weights, eng_cfg):
        import jax.numpy as jnp
        eng = real(cfg_, weights, eng_cfg)
        layers = eng.model.serve_layers()
        linear = [la for la in layers
                  if getattr(la, "serve_keeps", "") == "state"]
        if fault == "state_reset":
            decode = eng._decode_fn
            n_pages = len(eng.cache.pools)
            n_all = len(eng.cache.arrays)

            def forgetting(*args):
                args = list(args)
                for i in range(1 + n_pages, 1 + n_all):
                    args[i] = jnp.zeros_like(args[i])
                return decode(*args)
            eng._decode_fn = forgetting
        elif fault == "beta_halved":
            eng.model.cfg.linear_allow_neg_eigval = False
        elif fault == "tail_dropped":
            for la in linear:
                step = la.serve_prefill_state

                def no_tail(x, n, step=step):
                    x, (state, tail) = step(x, n)
                    return x, (state, jnp.zeros_like(tail))
                la.serve_prefill_state = no_tail
        elif fault == "decay_ignored":
            for la in linear:
                mix_in = la._mix_in

                def no_decay(x, mix_in=mix_in):
                    xc, z, g, beta = mix_in(x)
                    return xc, z, jnp.zeros_like(g), beta
                la._mix_in = no_decay
        elif fault == "qk_norm_dropped":
            for la in layers:
                if getattr(la, "serve_keeps", "") != "state":
                    la.q_norm.forward = lambda x: x
                    la.k_norm.forward = lambda x: x
        return eng
    cell.family.adapter.build_engine = tampering
    try:
        yield
    finally:
        cell.family.adapter.build_engine = real


@pytest.mark.parametrize("fault", ["state_reset", "beta_halved",
                                   "tail_dropped", "decay_ignored",
                                   "qk_norm_dropped"])
def test_a_planted_fault_is_not_correct(fault, sound):
    cell = tiny_cell(sample=6)
    with planted(cell, fault):
        res = R.run_cell(cell, SEED, 1.0, False, require_chip=False)
    assert res["correct"] is False, res["compared"]
    assert res["compared"]["served_logit_gap_max"]["value"] \
        > 2 * LIMITS["served_logit_gap_max"] \
        > sound["compared"]["served_logit_gap_max"]["value"]


# -- the two new readers on made-up records -----------------------------------

def _ctx(**kw):
    args = dict(run={"traced": {"steps": []}}, cfg=tiny_cfg(), mix={},
                cell={}, chips=1, peaks=P.peaks_of("TPU v5 lite"),
                family=load_family(ROOT, {"model": "olmo_hybrid"}))
    args.update(kw)
    return readers.Ctx(**args)


def _with_counters(series, fn):
    from paddle_tpu.observability import metrics
    saved = metrics.snapshot
    metrics.snapshot = lambda *a, **k: series
    try:
        return fn()
    finally:
        metrics.snapshot = saved


def test_the_counter_reader_on_made_up_records():
    series = {"serving.state_rows": {"series": [
        {"labels": {"kind": "needed"}, "value": 750},
        {"labels": {"kind": "read"}, "value": 1000}]}}
    reader = R.load_reader(ROOT, "engine.state_read_useful_share")
    assert _with_counters(series, lambda: reader(_ctx())) == {
        "value": 75.0, "of": 1000}
    # a program without the counter (the parent commit): None, not 0
    assert _with_counters({}, lambda: reader(_ctx())) is None


def test_the_kernel_reader_pairs_calls_with_the_steps_rows():
    """Four steps, each one decode program with three calls of the kernel;
    the step records hold 200 and 100 rows."""
    reader = R.load_reader(ROOT, "kernels.gdn_decode_roofline")
    steps = [{"prefills": [], "decode_ctx": [300] * (200 if u % 2 else 100)}
             for u in range(4)]
    call = ('%gated_delta_decode.2 = f32[256,1,5760] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    other = '%fusion.7 = bf16[4,128] fusion(), kind=kLoop'
    spans, mods, ops = [], [], []
    for u in range(4):
        t = u * 1e-2
        spans.append(TR.Ev("bench.engine_step", t, 9e-3))
        mods.append(TR.Ev("jit_step", t + 1e-3, 6e-3))
        ops += [TR.Ev(call, t + 1e-3, 1e-3), TR.Ev(other, t + 2e-3, 1e-3),
                TR.Ev(call, t + 3e-3, 1e-3), TR.Ev(call, t + 4e-3, 1e-3)]
    dev = TR.Device("/device:TPU:0")
    dev.ops, dev.modules = ops, mods
    trace = TR.Trace([dev], spans)
    ctx = _ctx(run={"traced": {"steps": steps}}, trace=trace,
               win=(0.0, 4e-2))
    ctx.cfg = R.load_cell(ROOT, CELL).cfg
    got = reader(ctx)
    assert got["calls"] == 12 and got["calls_per_program"] == 3.0
    assert got["ms_per_call"] == pytest.approx(1.0)
    needs = ctx.family.needs.gdn_decode_call_needs
    want = sum(3 * max(f / ctx.peaks.flops_bf16, b / ctx.peaks.hbm_bytes_s)
               for f, b in (needs(ctx.cfg, r) for r in (100, 200, 100, 200)))
    assert got["value"] == pytest.approx(100.0 * want / 12e-3)
    assert got["bound"] == "memory"
    # nothing to read: no trace, a family without the count, no such call
    assert reader(_ctx()) is None
    assert reader(_ctx(run=ctx.run, trace=trace, win=ctx.win,
                       family=load_family(ROOT, {"model": "gpt"}))) is None
    dev.ops = [e for e in ops if "fusion" in e.name]
    assert reader(ctx) is None
