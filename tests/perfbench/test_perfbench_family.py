"""A model family is files only. A fixture family (``fixture_family/gqa``: the
program's GPT with grouped-query attention) is put beside GPT's in a checkout
made for the test, of new files and links alone, and runs through the
harness's own entry: every role of it differs from GPT's (other leaves and
gains, another model and engine policy, a reference of its own, other counts).
And GPT's own roles give what ``lib/weights.py`` and ``lib/flops.py`` gave
before they were a family's (golden values read at the parent commit)."""

import gzip
import hashlib
import json
import os

import numpy as np
import pytest

from perfbench_tiny import (CLOSED, ROOT, SEED, SERVE_CELL, TRAIN, TRAIN_CELL,
                            manifest, tiny_cfg)

from benchmark import run as R
from benchmark.lib import peaks as P
from benchmark.lib import readers, system, weights
from benchmark.lib import trace as TR
from benchmark.lib.family import Family, families_found, load_family

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = {"gqa.train": ("tiny-train", TRAIN_CELL),
         "gqa.closed": ("tiny-closed", SERVE_CELL)}
# Limits as perfbench_tiny.TINY_LIMITS are set, from readings (my CPU runs, PR
# 28, seeds SEED..SEED+5 of the program): first-gradient gap 0.0024-0.0042,
# change gap 0.0060-0.0090, served gap 0.0 on five seeds and 0.0017 on one
# (an altered token reads over 0.1 at these sizes, half a batch 0.09 and
# more: perfbench_tiny). No control was read for the fixture: these tests
# are about where the harness finds a family, not what its limits catch.
LIMITS = {"gqa.train": {"grad1_leaf_gap": 0.009, "delta3_leaf_gap": 0.05},
          "gqa.closed": {"served_logit_gap_max": 0.01}}


def fixture_family():
    return Family("gqa", os.path.join(HERE, "fixture_family/gqa"))


def gqa_cfg():
    cfg = tiny_cfg()
    cfg.update(name="gqa-tiny", model="gqa", num_heads=4, num_kv_heads=2,
               hidden_size=128, head_dim=32, intermediate_size=512,
               vocab_size=16384)
    return cfg


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with both families: what the repository has as links, the
    fixture family, its configuration, mixes, cells and manifest as new
    files. Nothing that was there is edited."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmark"
    for d in ("families", "configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    os.symlink(os.path.join(ROOT, "benchmark/metrics"), bench / "metrics")
    os.symlink(os.path.join(ROOT, "benchmark/families/gpt"),
               bench / "families/gpt")
    os.symlink(os.path.join(HERE, "fixture_family/gqa"),
               bench / "families/gqa")
    (bench / "families/half").mkdir()           # not all four roles: no family
    (bench / "families/half/needs.py").write_text("")
    (bench / "configs/gqa-tiny.json").write_text(json.dumps(gqa_cfg()))
    engine = dict(CLOSED["engine"], num_blocks=4 * 8 + 1, prefix_cache=True)
    (bench / "traffic/tiny-train.json").write_text(json.dumps(TRAIN))
    (bench / "traffic/tiny-closed.json").write_text(
        json.dumps(dict(CLOSED, engine=engine)))
    for cell, limits in LIMITS.items():
        (bench / "cells" / (cell + ".json")).write_text(json.dumps(
            {"check": {"sample": 3}, "limits": limits}))
    man = manifest()
    like = {v[1]: k for k, v in CELLS.items()}
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [like[w] for w in m["workloads"]]
    man["configs"] = [{"name": "gqa-tiny", "file":
                       "benchmark/configs/gqa-tiny.json"}]
    man["workloads"] = [{"name": c, "config": "gqa-tiny", "traffic": t,
                         "chips": 1} for c, (t, _) in CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return str(root)


def test_families_are_the_directories_with_all_four_roles(checkout):
    assert families_found(ROOT) == ["gpt"]
    assert families_found(checkout) == ["gpt", "gqa"]
    fam = load_family(checkout, gqa_cfg())
    assert fam.name == "gqa" and fam.weights.is_gain("layers.0.ln1_scale")
    assert load_family(checkout, tiny_cfg()).needs is \
        load_family(ROOT, tiny_cfg()).needs        # one GPT, by either way


@pytest.mark.parametrize("model", ["llama", None, "half"])
def test_unknown_or_missing_model_names_the_families(checkout, model):
    cfg = dict(gqa_cfg(), model=model)
    if model is None:
        del cfg["model"]
    with pytest.raises(SystemExit) as e:
        load_family(checkout, cfg)
    assert "['gpt', 'gqa']" in str(e.value) and repr(model) in str(e.value)
    path = os.path.join(checkout, "benchmark/configs/gqa-tiny.json")
    saved = open(path).read()
    try:        # and so does the entry, before anything is built
        with open(path, "w") as f:
            json.dump(cfg, f)
        with pytest.raises(SystemExit, match="families"):
            R.load_cell(checkout, "gqa.train")
    finally:
        with open(path, "w") as f:
            f.write(saved)


def test_fixture_family_trains_through_the_entry(checkout):
    cell = R.load_cell(checkout, "gqa.train")
    assert cell.family.name == "gqa"
    res = R.run_cell(cell, SEED, 1.0, False, root=checkout,
                     require_chip=False)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["compared"]) >= {"grad1_leaf_gap", "delta3_leaf_gap"}
    assert {"train.tokens_per_s", "setup_s"} <= set(res["metrics"])
    # the comparison went over this family's leaves, K and V apart
    ref = R.reference_three(cell, SEED, [
        (np.zeros((2, 8), np.int32), np.ones((2, 8), np.int32))] * 3,
        system.build_mesh(None, 1))
    assert "layers.1.w_kv.k" in ref["grad"] and "lnf_scale" in ref["delta"]
    assert not any("qkv" in leaf for leaf in ref["grad"])


def test_fixture_family_serves_through_the_entry(checkout):
    cell = R.load_cell(checkout, "gqa.closed")
    warmed = cell.family.adapter.WARMED
    del warmed[:]
    res = R.run_cell(cell, SEED, 1.5, True, root=checkout,
                     require_chip=False)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]["served_logit_gap_max"]["value"] is not None
    assert res["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert "engine.decode_batch_mean" in res["metrics"]
    # the adapter's own warm-up ran, on the engine block of the mix
    assert len(warmed) == 1 and warmed[0]["prefix_cache"] is True


def test_fixture_familys_gains_come_from_its_own_rule():
    fam = fixture_family()
    w = weights.make_weights(fam.weights, gqa_cfg(), SEED)
    for name in fam.weights.leaf_names(gqa_cfg()):
        a = np.asarray(weights.get_leaf(w, name).astype("float32"))
        near = 1.0 if name.endswith("_scale") else 0.0
        assert abs(float(a.mean()) - near) < 0.02, name
        assert (a != 0).all() or not name.endswith("_scale")


# -- the readers take their counts from the cell's family ---------------------

def _ctx(fam, cfg, run, **kw):
    return readers.Ctx(run=run, cfg=cfg, mix={"batch": 4, "seq": 64},
                       cell={}, chips=1, peaks=P.peaks_of("TPU v5 lite"),
                       family=fam, **kw)


def test_whole_step_shares_follow_the_familys_needs():
    gpt, gqa = load_family(ROOT, tiny_cfg()), fixture_family()
    cfg = gqa_cfg()
    run = {"window_s": 2.0, "traced": {"window_s": 1.0, "steps": 5},
           "steps": [{"prefills": [40], "decode_ctx": [41, 9]},
                     {"prefills": [], "decode_ctx": [42, 10]}]}
    peak = P.peaks_of("TPU v5 lite").flops_bf16
    serve = R.load_reader(ROOT, "serve.step_mfu")
    train = R.load_reader(ROOT, "train.step_mfu")
    for fam in (gpt, gqa):
        need = fam.needs.serve_flops(cfg, [(40, 0)], [41, 9, 42, 10])
        assert serve(_ctx(fam, cfg, run)) == pytest.approx(
            100.0 * need / (2.0 * peak))
        assert train(_ctx(fam, cfg, run)) == pytest.approx(
            100.0 * fam.needs.train_flops_per_token(cfg, 64) * 5 * 4 * 64
            / peak)
    assert serve(_ctx(gqa, cfg, run)) < serve(_ctx(gpt, cfg, run))
    # by hand, at these sizes: a layer has 2*128*128 + 2*128*64 + 2*128*512
    assert gqa.needs.matmul_params(cfg) == 2 * 180_224 + 16384 * 128
    assert gpt.needs.matmul_params(cfg) == 2 * 196_608 + 16384 * 128


def test_decode_rooflines_follow_the_familys_needs(tmp_path):
    """On the recorded trace (five engine steps, the first with four
    prefills): the decode step's roofline share is the family's bytes over
    the same device time, so half the K/V width reads a smaller share."""
    src = os.path.join(ROOT, "benchmark/testdata/serve_2layer.xplane.pb.gz")
    dst = tmp_path / "serve_2layer.xplane.pb"
    with gzip.open(src, "rb") as f:
        dst.write_bytes(f.read())
    tr = TR.load(str(dst))
    win = (tr.spans[0].start, tr.spans[-1].end)
    steps = [{"prefills": [300, 600, 300, 600], "decode_ctx": [301] * 4}] + \
        [{"prefills": [], "decode_ctx": [302 + i] * 4} for i in range(4)]
    run = {"traced": {"window_s": win[1] - win[0], "steps": steps}}
    cfg = dict(gqa_cfg(), num_layers=2, hidden_size=2048, head_dim=128,
               num_heads=16, num_kv_heads=4, intermediate_size=8192,
               vocab_size=50304, max_position_embeddings=2048)
    read = R.load_reader(ROOT, "kernels.decode_step_roofline")
    got = {}
    for fam in (load_family(ROOT, tiny_cfg()), fixture_family()):
        ctx = _ctx(fam, cfg, run, trace=tr, win=win)
        progs = readers.decode_programs(ctx)
        assert len(progs) == 5
        spent = sum(m.dur for _, m in progs)
        least = sum(fam.needs.decode_step_needs(cfg, st["decode_ctx"])[1]
                    for st, _ in progs) / ctx.peaks.hbm_bytes_s
        got[fam.name] = read(ctx)
        assert got[fam.name]["bound"] == "memory"
        assert got[fam.name]["value"] == pytest.approx(100 * least / spent)
    assert got["gqa"]["value"] < got["gpt"]["value"]


# -- GPT's roles give what they gave before they were a family's -------------

GOLDEN = {
    "wte": "3f5bd0b3f697", "wpe": "3a812744c868", "lnf_g": "1d9a0729ea46",
    "lnf_b": "fd5577caeb90",
    "layers.0.ln1_g": "ef0e579eb13c", "layers.0.ln1_b": "a46d9d47d03c",
    "layers.0.w_qkv": "2a9c8a0f8799", "layers.0.b_qkv": "956311fa1fd2",
    "layers.0.w_o": "302327059ebb", "layers.0.b_o": "92af29cacadd",
    "layers.0.ln2_g": "1499cfc0510a", "layers.0.ln2_b": "659431ba059b",
    "layers.0.w_up": "9d6594ed4fba", "layers.0.b_up": "f9d2e9045f38",
    "layers.0.w_down": "17603fee5f5c", "layers.0.b_down": "1856f4e0861d",
    "layers.1.ln1_g": "eb6b3c94b51f", "layers.1.ln1_b": "7891a4e83a7c",
    "layers.1.w_qkv": "e7359e2d590d", "layers.1.b_qkv": "7f2c1cf7fd3d",
    "layers.1.w_o": "e6635e53375f", "layers.1.b_o": "957d49840464",
    "layers.1.ln2_g": "400a5fe24855", "layers.1.ln2_b": "26ec7466177d",
    "layers.1.w_up": "81e0d60ab335", "layers.1.b_up": "20297263ff28",
    "layers.1.w_down": "466c64884c1c", "layers.1.b_down": "7cc28f10a45c",
}


@pytest.fixture(scope="module")
def gpt_weights():
    cfg = tiny_cfg()
    fam = load_family(ROOT, cfg)
    return fam, cfg, weights.make_weights(fam.weights, cfg, SEED)


@pytest.mark.parametrize("leaf", sorted(GOLDEN))
def test_gpt_weights_of_a_seed_are_bit_identical(gpt_weights, leaf):
    """sha256 of each leaf's float32 bytes of ``make_weights(tiny_cfg(),
    SEED)`` as ``benchmark/lib/weights.py`` gave them at the parent commit
    (my CPU run, PR 28; the values are whole numbers times powers of two, the
    same on any backend)."""
    fam, cfg, w = gpt_weights
    assert sorted(fam.weights.leaf_names(cfg)) == sorted(GOLDEN)
    a = np.asarray(weights.get_leaf(w, leaf).astype("float32"))
    assert hashlib.sha256(a.tobytes()).hexdigest()[:12] == GOLDEN[leaf]
