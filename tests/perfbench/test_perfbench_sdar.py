"""The SDAR-MoE family through the harness's own entry at a tiny size on the
CPU: its cell's files are found by name, the configuration is the catalog's
but for the cut, the family's counts are what its shapes say, a run reports
the new cell's metrics (the diffusion counters' among them), the three new
readers read made-up records, and the comparison passes a sound run and
catches five planted faults: unmasking left to right, no commit pass (the
cache keeps a masked state's keys and values), a causal prefill, the
threshold ignored, a token of a block altered. Nothing here is a
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

CELL = "serve.sdar-30b-a3b-ep8-l16.reason1k-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Limit as perfbench_tiny.TINY_LIMITS are set, from readings (my CPU runs, PR
# 34, at these sizes in float32, seeds SEED..SEED+2, a dozen requests each):
# the honest program's served gap 0.0 on every reading; left to right 5.7-
# 16.9, no commit pass 8.6-21.5, a causal prefill 13.3-16.9, the threshold
# ignored 7.9-14.5, an altered token 54-66 (the head is scaled by 64 here, and
# the logits with it).
LIMITS = {"served_logit_gap_max": 0.05}

MIX = {"kind": "closed_loop", "clients": 3, "preroll_s": 0.3,
       "n_sizes": 16, "pairing_seed": 7,
       "prompt_len": {"dist": "log_uniform", "lo": 5, "hi": 40},
       "output_len": {"dist": "uniform", "lo": 5, "hi": 14},
       "engine": {"max_batch": 4, "max_seq_len": 64, "block_size": 8,
                  "prefill_buckets": [16, 48], "decode_buckets": [4]}}


def tiny_cfg():
    with open(os.path.join(
            ROOT, "benchmark/configs/sdar-30b-a3b-ep8-l16.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, num_hidden_layers=2,
               vocab_size=4096, router_width=16, num_experts=4,
               experts_held_first=4, num_experts_per_tok=4,
               max_position_embeddings=128,
               # at these widths the generator's 0.018 leaves every logit
               # within a hundredth of the others; scaled until some
               # confidences pass 0.9 and both branches of the rule run
               head_init_scale=64.0)
    cfg["generation"] = dict(cfg["generation"], mask_token_id=4095)
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    return cfg


def tiny_cell(sample=3):
    man = manifest()
    mine = lambda ms: [m for m in ms if CELL in m.get("workloads", [CELL])]
    cfg = tiny_cfg()
    return R.Cell("tiny.sdar", cfg, MIX, 1, load_family(ROOT, cfg),
                  {"check": {"sample": sample}, "limits": LIMITS},
                  mine(man["end_to_end"]), mine(man["per_layer"]))


def test_the_cells_files_are_found_by_name():
    cell = R.load_cell(ROOT, CELL)
    assert cell.family.name == "sdar_moe" and cell.chips == 1
    mix, eng = cell.mix, cell.mix["engine"]
    assert (mix["kind"], mix["clients"], mix["n_sizes"], mix["preroll_s"],
            mix["pairing_seed"]) == ("closed_loop", 128, 32, 40.0, 7)
    assert mix["prompt_len"] == {"dist": "log_uniform", "lo": 128,
                                 "hi": 1024}
    assert mix["output_len"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert (eng["max_batch"], eng["max_seq_len"], eng["block_size"],
            eng["num_blocks"]) == (128, 2048, 16, 10240)
    assert eng["prefill_buckets"] == [256, 512, 1024]
    assert eng["decode_buckets"] == [128]
    assert not (eng["prefix_cache"] or eng["chunked_prefill"]
                or eng["speculative"])
    assert cell.extra["check"]["sample"] == 6
    assert "served_logit_gap_max" in cell.extra["limits"]
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"serve.tokens_per_s", "serve.itl_p95_ms", "setup_s",
            "diffusion.passes_per_block", "diffusion.rows_per_pass",
            "kernels.block_paged_attention_roofline",
            "moe.held_assignments_per_token", "serve.step_mfu",
            "kernels.decode_step_roofline"} <= names
    assert not names & {"engine.decode_batch_mean", "model.prefill_ms",
                        "kernels.paged_attention_roofline",
                        "kernels.mla_decode_roofline"}
    # the pool holds the live set with room, the longest request fits, and
    # the lengths are not all multiples of the block
    from benchmark.lib.traffic import size_set
    sizes = size_set(mix)
    live = np.mean([p + o / 2 for p, o in sizes]) * mix["clients"]
    assert live < 0.8 * eng["num_blocks"] * eng["block_size"]
    assert max(p + o for p, o in sizes) <= eng["max_seq_len"]
    assert sum(1 for p, _ in sizes if p % 4) >= len(sizes) // 2
    assert sum(1 for p, o in sizes if (p + o) % 4) >= len(sizes) // 2


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    cfg = R.load_cell(ROOT, CELL).cfg
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_width"], cfg["experts_held_first"]) == (16, 16, 128, 0)
    gen = cfg["generation"]
    assert (gen["block_length"], gen["denoising_steps"], gen["remasking"],
            gen["confidence_threshold"], gen["mask_token_id"]) == \
        (4, 4, "low_confidence_dynamic", 0.9, 151669)
    for key in ("block_length", "mask_token_id", "qk_norm_and_rope",
                "logit_shift", "masked_is_state", "answer_tail",
                "noise_schedule", "initializer"):
        assert key in cfg["assumed"], key
    assert "8-chip" in cfg["deployment"]
    assert cfg["precision"]["router"] == cfg["precision"]["confidence"] \
        == "float32"
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "SDAR-30B-A3B-Chat")
    man = next(c for c in manifest()["configs"]
               if c["name"] == "sdar-30b-a3b-ep8-l16")
    assert man["source"] == entry["source_url"]
    assert sorted(man["reduced"]) == sorted(cfg["reduced"])
    for key, want in entry["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == want, key


def test_the_familys_counts_are_what_its_shapes_say():
    cell = R.load_cell(ROOT, CELL)
    cfg, needs = cell.cfg, cell.family.needs
    assert needs.attn_params(cfg) == 2 * 2048 * 4096 + 2 * 2048 * 512
    shapes = cell.family.weights.leaf_shapes(cfg)
    held = 0
    for name in cell.family.weights.leaf_names(cfg):
        shape = shapes
        for part in name.split("."):
            shape = shape[int(part)] if isinstance(shape, list) \
                else shape[part]
        held += int(np.prod(shape))
    assert needs.n_params(cfg) == held            # 2.137 B
    assert 2.13e9 < held < 2.14e9
    assert needs.held_share(cfg) == pytest.approx(1.0)
    assert needs.kv_bytes_per_token(cfg) * 16 == 32 * 1024   # 32 KB a token
    assert needs.weight_bytes(cfg) == 2 * (held - 151936 * 2048)
    keys = [752] * 128
    flops, nbytes = needs.block_paged_call_needs(cfg, keys)
    assert flops == 128 * 752 * 4 * 4 * 128 * 32
    assert nbytes == 128 * 752 * 2048 + 128 * 2 * 4 * 32 * 128 * 2
    # bound by the bytes on a v5e: a key is read once for 128 query rows
    pk = P.peaks_of("TPU v5 lite")
    assert flops / pk.flops_bf16 < nbytes / pk.hbm_bytes_s
    ctx = [750] * 100
    f_step, b_step = needs.decode_step_needs(cfg, ctx)
    assert b_step == needs.weight_bytes(cfg) + 16 * 100 * 750 * 2048
    assert f_step == 100 * 2 * needs.matmul_params(cfg) \
        + 16 * 100 * 750 * 4 * 128 * 32
    assert needs.serve_flops(cfg, [], ctx) == pytest.approx(f_step)
    # a block's keys and values are read once, not once a token: two whole
    # blocks and a last block of two count 3 reads, up to each one's end
    assert needs.runs_end([100, 101, 102, 103, 40, 41, 7], 4) == [103, 41, 7]
    assert needs.runs_end([8, 9, 10, 11, 12, 13, 14, 15], 4) == [11, 15]
    assert needs.runs_end([], 4) == []
    ctx = [748, 749, 750, 751, 300, 301, 302, 303, 90, 91]
    f_step, b_step = needs.decode_step_needs(cfg, ctx)
    assert b_step == needs.weight_bytes(cfg) \
        + 16 * (751 + 303 + 91) * 2048
    assert f_step == 10 * 2 * needs.matmul_params(cfg) \
        + 16 * sum(ctx) * 4 * 128 * 32
    # so the step's share cannot pass 100 % on the threshold branch either:
    # a pass reads every row's keys once, a step counts at most every row's
    assert b_step - needs.weight_bytes(cfg) <= 16 * sum(
        needs.block_paged_call_needs(cfg, [752, 304, 92])[1:])
    # a prefill: no head, block-causal keys (whole blocks and a tail)
    assert needs.prefill_attn_flops(cfg, 10) == \
        4 * 128 * 32 * (4 * 4 * 3 + 2 * 10)
    one = needs.serve_flops(cfg, [(430, 0)], [])
    assert one == pytest.approx(
        430 * 2 * (needs.matmul_params(cfg) - 2048 * 151936)
        + 16 * needs.prefill_attn_flops(cfg, 430))
    with pytest.raises(NotImplementedError, match="no training"):
        cell.family.reference.Reference(cfg).train_step(None, None, None,
                                                        None)


@pytest.fixture(scope="module")
def sound():
    return R.run_cell(tiny_cell(), SEED, 1.5, True, require_chip=False)


def test_tiny_run_is_correct_and_reports_the_new_cells_metrics(sound):
    res = sound
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert res["compared"]["served_logit_gap_max"]["value"] is not None
    got = res["metrics"]
    ppb = got["diffusion.passes_per_block"]
    # a whole block on the schedule branch takes 5; first and last blocks and
    # the threshold branch take fewer
    assert 2.0 <= ppb["value"] <= 5.0 and ppb["unit"] == "passes/block"
    assert ppb["denoise"] + ppb["commit"] == pytest.approx(
        ppb["value"] * ppb["blocks"])
    assert ppb["commit"] == ppb["blocks"]
    assert 0.0 < ppb["threshold_share"] < 1.0
    rpp = got["diffusion.rows_per_pass"]
    assert 1.0 <= rpp["value"] <= 4.0 and rpp["unit"] == "seqs"
    moe = got["moe.held_assignments_per_token"]
    # 4 of 16 experts a token, 4 held: 1.0 if routing were even
    assert 0.4 < moe["value"] < 1.6
    assert "engine.kv_read_useful_share" in got
    assert "engine.decode_batch_mean" not in got
    # a CPU run has no device number: no roofline, whatever the family
    assert not any("roofline" in n or "mfu" in n for n in got)


@contextlib.contextmanager
def planted(cell, fault):
    """``cell``'s engine built with one fault of the procedure planted."""
    real = cell.family.adapter.build_engine
    import paddle_tpu.serving.engine as E
    real_unmask = E._unmask

    def tampering(cfg_, weights, eng_cfg):
        import jax.numpy as jnp
        eng = real(cfg_, weights, eng_cfg)
        width = eng_cfg["decode_buckets"][0]
        B = cfg_["generation"]["block_length"]
        decode = eng._decode_fn
        if fault == "token":

            def altered(*args):
                out, *pools = decode(*args)
                tok, flag = out[:width * B], out[width * B:2 * width * B]
                # every position that is not masked any more
                bad = jnp.where(flag > 0, tok, (tok + 1) % 4000)
                return (out.at[:width * B].set(bad), *pools)
            eng._decode_fn = altered
        elif fault == "no_commit":
            # a whole block counts as committed: its tokens go out after
            # the last denoise pass, whose input still had a masked position
            def skipping(*args):
                out, *pools = decode(*args)
                lo = 2 * width * B
                stage = out[lo:lo + width]
                return (out.at[lo:lo + width].set(
                    jnp.where(stage == 1, 2, stage)), *pools)
            eng._decode_fn = skipping
        elif fault == "causal_prefill":
            from paddle_tpu.ops.flash_attention import flash_attention
            for layer in eng.model.serve_layers():
                layer.serve_attend_prefill = lambda q, rows: flash_attention(
                    q, *rows, causal=True, training=False)
        elif fault == "threshold_ignored":
            E._unmask = lambda logits, masked, k, thr: real_unmask(
                logits, masked, k, 2.0)
        elif fault == "left_to_right":

            def first_masked(logits, masked, k, thr):
                x0, _, by_thr = real_unmask(logits, masked, k, 2.0)
                earlier = jnp.cumsum(masked, axis=-1) - masked
                return x0, jnp.logical_and(masked, earlier < k), by_thr
            E._unmask = first_masked
        return eng
    cell.family.adapter.build_engine = tampering
    try:
        yield
    finally:
        cell.family.adapter.build_engine = real
        E._unmask = real_unmask


def _planted(fault, seed=SEED):
    # 40 requests: ignoring the threshold shows only where a confidence
    # crosses 0.9 between two states, and a dozen requests missed it on one
    # reading in five
    cell = tiny_cell(sample=40)
    with planted(cell, fault):
        return R.run_cell(cell, seed, 4.0, False, require_chip=False)


@pytest.mark.parametrize("fault", ["left_to_right", "no_commit",
                                   "causal_prefill", "threshold_ignored",
                                   "token"])
def test_a_planted_fault_is_not_correct(fault, sound):
    res = _planted(fault)
    assert res["correct"] is False, res["compared"]
    assert res["compared"]["served_logit_gap_max"]["value"] \
        > 2 * LIMITS["served_logit_gap_max"] \
        > sound["compared"]["served_logit_gap_max"]["value"]


# -- the three new readers on made-up records --------------------------------

def _ctx(**kw):
    args = dict(run={"traced": {"steps": []}}, cfg=tiny_cfg(), mix={},
                cell={}, chips=1, peaks=P.peaks_of("TPU v5 lite"),
                family=load_family(ROOT, {"model": "sdar_moe"}))
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


def _family(name, values):
    return {"series": [{"labels": {k: str(v) for k, v in labels.items()},
                        "value": value} for labels, value in values]}


def test_the_counter_readers_on_made_up_records():
    series = {
        "serving.diffusion_passes": _family("p", [
            ({"kind": "denoise"}, 400), ({"kind": "commit"}, 100)]),
        "serving.diffusion_blocks": _family("b", [({}, 100)]),
        "serving.diffusion_unmasked": _family("u", [
            ({"rule": "threshold"}, 100), ({"rule": "schedule"}, 300)]),
        "serving.diffusion_pass_rows": _family("r", [({}, 500)]),
        "serving.decode_step_ms": _family("h", [({}, {"count": 5,
                                                      "sum": 60.0})]),
    }
    ppb = _with_counters(series, lambda: R.load_reader(
        ROOT, "diffusion.passes_per_block")(_ctx()))
    assert ppb["value"] == 5.0 and ppb["threshold_share"] == 0.25
    rpp = _with_counters(series, lambda: R.load_reader(
        ROOT, "diffusion.rows_per_pass")(_ctx()))
    assert rpp == {"value": 100.0, "rows": 500, "launches": 5}
    # a program without the counters (the parent commit): None, not 0
    for name in ("diffusion.passes_per_block", "diffusion.rows_per_pass"):
        assert _with_counters({}, lambda: R.load_reader(ROOT, name)(
            _ctx())) is None


def test_the_kernel_reader_rebuilds_the_rows_of_a_pass():
    """Two rows, blocks of 4: row A commits in steps 5 and 10, row B in step
    7; each step launched one program that holds two calls of the kernel."""
    reader = R.load_reader(ROOT, "kernels.block_paged_attention_roofline")

    def mod(ctx, by_threshold=0):
        series = {"serving.diffusion_unmasked": _family("u", [
            ({"rule": "threshold"}, by_threshold),
            ({"rule": "schedule"}, 300)])}
        return _with_counters(series, lambda: reader(ctx))
    n = 16
    steps = [{"prefills": [], "decode_ctx": []} for _ in range(n)]
    steps[5]["decode_ctx"] = [100, 101, 102, 103]
    steps[10]["decode_ctx"] = [104, 105, 106, 107]
    steps[7]["decode_ctx"] = [41, 42]            # a first block, P % 4 = 1
    call = ('%block_paged_attention.3 = bf16[4,128,128] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    other = '%fusion.7 = bf16[4,128] fusion(), kind=kLoop'
    spans, mods, ops = [], [], []
    for u in range(n):
        t = u * 1e-2
        spans.append(TR.Ev("bench.engine_step", t, 9e-3))
        mods.append(TR.Ev("jit_step", t + 1e-3, 6e-3))
        ops += [TR.Ev(call, t + 1e-3, 1e-3), TR.Ev(other, t + 2e-3, 1e-3),
                TR.Ev(call, t + 3e-3, 1e-3)]
    dev = TR.Device("/device:TPU:0")
    dev.ops, dev.modules = ops, mods
    trace = TR.Trace([dev], spans)
    ctx = _ctx(run={"traced": {"steps": steps}}, trace=trace,
               win=(0.0, n * 1e-2))
    got = mod(ctx)
    # steps 0-4 launched A's first block (104 keys), 5-9 its second (108),
    # 2-6 also B's (43): 10 programs of the 11 that are not the last five
    assert got["calls"] == 20 and got["calls_per_program"] == 2.0
    assert got["ms_per_call"] == pytest.approx(1.0)
    needs = ctx.family.needs.block_paged_call_needs
    pk = ctx.peaks
    want = 0.0
    for u in range(10):
        keys = [104 if u < 5 else 108] + ([43] if 2 <= u <= 6 else [])
        f, b = needs(ctx.cfg, keys)
        want += 2 * max(f / pk.flops_bf16, b / pk.hbm_bytes_s)
    assert got["value"] == pytest.approx(100.0 * want / 20e-3)
    assert got["bound"] == "memory"
    # the threshold branch unmasked something: blocks differ in their
    # passes, the rows cannot be rebuilt, and no share is better than a wrong
    # one; so too for a program without the counter (the parent commit)
    assert mod(ctx, by_threshold=3) is None
    assert _with_counters({}, lambda: reader(ctx)) is None
    # nothing to read: no trace, a family without the count, no such call
    assert mod(_ctx()) is None
    assert mod(_ctx(run=ctx.run, trace=trace, win=ctx.win,
                    family=load_family(ROOT, {"model": "gpt"}))) is None
    dev.ops = [e for e in ops if "fusion" in e.name]
    assert mod(ctx) is None
