"""The DeepSeek-V2 family through the harness's own entry at a tiny size on
the CPU: its cell's files are found by name, a run reports the new cell's
metrics (the expert counters' among them), the comparison passes a sound run
and catches two planted faults (an altered token, a dropped expert), and the
family's counts are what its shapes say. Nothing here is a measurement."""

import json
import os

import numpy as np
import pytest

from perfbench_tiny import CLOSED, ROOT, SEED, manifest

from benchmark import run as R
from benchmark.lib import peaks as P
from benchmark.lib import readers
from benchmark.lib.family import load_family

CELL = "serve.deepseek-v2-ep16-l5.ctx4k-closed"
# Limit as perfbench_tiny.TINY_LIMITS are set, from readings (my CPU runs, PR
# 30, at these sizes in float32 with the experts scaled up, see tiny_cfg): the
# program's served gap 0.0 on 12 readings of seeds SEED..SEED+3; an expert
# whose output is dropped 0.69-0.83, an altered token 1.0-1.3. (In bfloat16
# the program read 0.0-0.006 on three seeds and 0.82 on one: a router
# near-tie that fell the other way, which is why this rehearsal is float32.)
LIMITS = {"served_logit_gap_max": 0.05}


def tiny_cfg():
    with open(os.path.join(
            ROOT, "benchmark/configs/deepseek-v2-ep16-l5.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, intermediate_size=256, kv_lora_rank=64,
               q_lora_rank=96, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, moe_intermediate_size=64,
               num_attention_heads=4, num_hidden_layers=3, vocab_size=16384,
               router_width=32, n_routed_experts=4, n_group=4, topk_group=2,
               num_experts_per_tok=3,
               # at these widths the generator's 0.018 makes an expert's
               # output a thousandth of the residual, and dropping it moves
               # no served token; scaled so that it weighs about what it
               # does at the published widths
               routed_scaling_factor=1600)
    # float32 here: in bfloat16 a near-tie of the router falls differently
    # than in the float32 reference now and then (PERF.md), and which
    # requests a 1.5 s window of a loaded CPU samples is not fixed
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    return cfg


def tiny_cell(sample=3):
    man = manifest()
    mine = lambda ms: [m for m in ms if CELL in m.get("workloads", [CELL])]
    cfg = tiny_cfg()
    return R.Cell("tiny.deepseek", cfg, CLOSED, 1, load_family(ROOT, cfg),
                  {"check": {"sample": sample}, "limits": LIMITS},
                  mine(man["end_to_end"]), mine(man["per_layer"]))


def test_the_cells_files_are_found_by_name():
    cell = R.load_cell(ROOT, CELL)
    assert cell.family.name == "deepseek_v2" and cell.chips == 1
    mix, eng = cell.mix, cell.mix["engine"]
    assert (mix["kind"], mix["clients"], mix["n_sizes"],
            mix["pairing_seed"]) == ("closed_loop", 256, 128, 7)
    assert mix["prompt_len"] == {"dist": "log_uniform", "lo": 512,
                                 "hi": 4096}
    assert mix["output_len"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert (eng["max_batch"], eng["max_seq_len"], eng["block_size"],
            eng["num_blocks"]) == (256, 5120, 16, 49152)
    assert eng["prefill_buckets"] == [1024, 2048, 4096]
    assert eng["decode_buckets"] == [256]
    assert not (eng["prefix_cache"] or eng["chunked_prefill"]
                or eng["speculative"])
    assert cell.extra["check"]["sample"] == 6
    assert "served_logit_gap_max" in cell.extra["limits"]
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"serve.tokens_per_s", "serve.itl_p95_ms", "setup_s",
            "kernels.mla_decode_roofline", "moe.held_assignments_per_token",
            "kernels.decode_step_roofline", "serve.step_mfu"} <= names
    assert "kernels.paged_attention_roofline" not in names
    # the pool holds the live set: 256 rows at the mean prompt and half the
    # mean answer, with room (no preemption in a sound run)
    from benchmark.lib.traffic import size_set
    live = np.mean([p + o / 2 for p, o in size_set(mix)]) * mix["clients"]
    assert live < 0.8 * eng["num_blocks"] * eng["block_size"]
    assert max(p + o for p, o in size_set(mix)) <= eng["max_seq_len"]


def test_the_configuration_is_the_catalogs_but_for_the_share():
    cfg = R.load_cell(ROOT, CELL).cfg
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_width"]) == (5, 10, 12800, 160)
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    for key, want in {"hidden_size": 5120, "kv_lora_rank": 512,
                      "q_lora_rank": 1536, "qk_nope_head_dim": 128,
                      "qk_rope_head_dim": 64, "v_head_dim": 128,
                      "intermediate_size": 12288,
                      "moe_intermediate_size": 1536,
                      "num_attention_heads": 128, "num_experts_per_tok": 6,
                      "n_group": 8, "topk_group": 3,
                      "n_shared_experts": 2}.items():
        assert cfg[key] == want, key


def test_the_familys_counts_are_what_its_shapes_say():
    cell = R.load_cell(ROOT, CELL)
    cfg, needs = cell.cfg, cell.family.needs
    assert needs.attn_params(cfg) == (
        5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 128 * 128 * 5120)
    shapes = cell.family.weights.leaf_shapes(cfg)
    held = 0
    for name in cell.family.weights.leaf_names(cfg):
        shape = shapes
        for part in name.split("."):
            shape = shape[int(part)] if isinstance(shape, list) \
                else shape[part]
        held += int(np.prod(shape))
    assert needs.n_params(cfg) == held            # 2.20 B
    assert 2.19e9 < held < 2.21e9
    assert needs.held_share(cfg) == pytest.approx(0.375)
    assert needs.weight_bytes(cfg) == 2 * (held - 12800 * 5120)
    ctx = [2040] * 256
    flops, nbytes = needs.mla_decode_call_needs(cfg, ctx)
    assert flops == 256 * 2040 * 2 * (576 + 512) * 128
    assert nbytes == 256 * 2040 * 576 * 2 + 256 * 128 * (576 + 512) * 2
    # on the v5e's ridge: compute and memory times within a fifth
    pk = P.peaks_of("TPU v5 lite")
    assert 0.8 < (flops / pk.flops_bf16) / (nbytes / pk.hbm_bytes_s) < 1.25
    f_step, b_step = needs.decode_step_needs(cfg, ctx)
    assert b_step == needs.weight_bytes(cfg) + 5 * 256 * 2040 * 576 * 2
    assert f_step == 256 * 2 * needs.matmul_params(cfg) + 5 * flops
    one = needs.serve_flops(cfg, [(1720, 0)], [])
    assert 4.4e12 < one < 4.8e12
    assert needs.serve_flops(cfg, [], ctx) == pytest.approx(f_step)
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
    assert "engine.decode_batch_mean" in got
    assert "engine.kv_read_useful_share" in got
    moe = got["moe.held_assignments_per_token"]
    # 3 of 32 experts a token, 4 held: 0.375 if routing were even
    assert 0.1 < moe["value"] < 0.9 and moe["unit"] == "pairs/token"
    assert moe["held"] <= moe["routed"]
    assert moe["load_max_over_mean"] >= 1.0
    # a CPU run has no device number: no roofline, whatever the family
    assert not any("roofline" in n or "mfu" in n for n in got)


def _planted(fault):
    # a dozen requests: an expert of 32 is a token's first choice once in
    # 32, so three short requests can miss the one that was dropped
    cell = tiny_cell(sample=12)
    real = cell.family.adapter.build_engine
    cfg = cell.cfg

    def tampering(cfg_, weights, eng_cfg):
        eng = real(cfg_, weights, eng_cfg)
        if fault == "token":
            decode = eng._decode_fn
            width = eng_cfg["decode_buckets"][0]

            def altered(*args):
                out, *pools = decode(*args)
                out = out.at[:width].set((out[:width] + 1)
                                         % cfg["vocab_size"])
                return (out, *pools)
            eng._decode_fn = altered
        else:       # expert 0 of every expert layer gives nothing
            for fn in (eng._decode_fn, eng._prefill_fn):
                for name in list(fn.params):
                    if name.endswith("mlp.w_down"):
                        fn.params[name] = fn.params[name].at[0].set(0.0)
        return eng
    cell.family.adapter.build_engine = tampering
    try:
        return R.run_cell(cell, SEED, 3.0, False, require_chip=False)
    finally:
        cell.family.adapter.build_engine = real


@pytest.mark.parametrize("fault", ["token", "expert"])
def test_a_planted_fault_is_not_correct(fault, sound):
    res = _planted(fault)
    assert res["correct"] is False, res["compared"]
    assert res["compared"]["served_logit_gap_max"]["value"] \
        > 2 * LIMITS["served_logit_gap_max"] \
        > sound["compared"]["served_logit_gap_max"]["value"]


def test_new_readers_return_none_where_there_is_nothing_to_read():
    """On a program or a family without the counters or the count (the
    parent commit, GPT's family): None, never 0 and never an error."""
    gpt = load_family(ROOT, {"model": "gpt"})
    ctx = readers.Ctx(run={"traced": {"steps": []}}, cfg={"num_layers": 2},
                      mix={}, cell={}, chips=1,
                      peaks=P.peaks_of("TPU v5 lite"), family=gpt)
    assert R.load_reader(ROOT, "kernels.mla_decode_roofline")(ctx) is None
    ctx.family = None
    assert R.load_reader(ROOT, "kernels.mla_decode_roofline")(ctx) is None
    from paddle_tpu.observability import metrics
    saved = metrics.snapshot
    metrics.snapshot = lambda *a, **k: {}
    try:
        assert R.load_reader(ROOT, "moe.held_assignments_per_token")(
            ctx) is None
    finally:
        metrics.snapshot = saved
