"""The yardstick's arithmetic: FLOP and byte counts (the GPT family's
``needs`` and the kernel-level ``lib/flops.py``) against values worked by
hand for both configurations, and the trace reduction against a small trace
recorded on the chip (TPU v5e, 2-layer engine at the real widths: four
prefills and five decode steps under ``bench.*`` spans)."""

import gzip
import json
import os

import pytest

from perfbench_tiny import ROOT

from benchmark.lib import flops as F
from benchmark.lib.family import load_family
from benchmark.lib import peaks as P
from benchmark.lib import trace as TR


def cfg(name):
    with open(os.path.join(ROOT, "benchmark/configs", name + ".json")) as f:
        return json.load(f)


def needs(name):
    return load_family(ROOT, cfg(name)).needs


@pytest.mark.parametrize("name,matmul,everything", [
    ("gpt3-1.3b", 1_310_982_144, 1_315_819_520),
    ("gpt3-1.3b-l12", 707_002_368, 711_520_256),
])
def test_parameter_counts(name, matmul, everything):
    assert needs(name).matmul_params(cfg(name)) == matmul
    assert needs(name).n_params(cfg(name)) == everything


@pytest.mark.parametrize("name,per_token", [
    ("gpt3-1.3b", 8_470_167_552), ("gpt3-1.3b-l12", 4_544_151_552)])
def test_train_flops_per_token(name, per_token):
    assert needs(name).train_flops_per_token(cfg(name), 2048) == per_token


def test_decode_step_counts_real_context_not_max_seq_len():
    c, N = cfg("gpt3-1.3b"), needs("gpt3-1.3b")
    assert N.weight_bytes(c) == 2_623_250_432
    flops, nbytes = N.decode_step_needs(c, [1000] * 32)
    assert flops == 90_194_313_216
    assert nbytes == 2_623_250_432 + 32_000 * 196_608
    t, bound = F.roofline_seconds(flops, nbytes, P.peaks_of("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(10.885e-3, rel=1e-3)
    # a row's bytes follow its own context: twice the context, twice the K/V
    _, b2 = N.decode_step_needs(c, [2000] * 32)
    assert b2 - N.weight_bytes(c) == 2 * (nbytes - N.weight_bytes(c))


def test_serve_flops_of_one_prefill_and_one_decode():
    c, N = cfg("gpt3-1.3b"), needs("gpt3-1.3b")
    assert N.attn_flops_causal(c, 512) == 1_075_838_976
    assert N.serve_flops(c, [(512, 0)], [513]) == 1_265_699_586_048


@pytest.mark.parametrize("kind,flops,nbytes", [
    ("fwd", 68_719_476_736, 134_217_728),
    ("bwd_dq", 103_079_215_104, 201_326_592),
    ("bwd_dkv", 137_438_953_472, 234_881_024)])
def test_flash_call_counts(kind, flops, nbytes):
    assert F.flash_call_needs(kind, 64, 2048, 128) == (flops, nbytes)


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError):
        P.peaks_of("cpu")


# -- intervals -----------------------------------------------------------------

def test_union_subtract_clip():
    u = TR.union([(0, 2), (1, 3), (5, 6), (6, 7), (10, 11)])
    assert u == [(0, 3), (5, 7), (10, 11)]
    assert TR.total(u) == 6
    assert TR.subtract([(0, 12)], u) == [(3, 5), (7, 10), (11, 12)]
    assert TR.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == [
        (0, 1), (2, 3), (7, 8)]
    assert TR.clip([(0, 5), (8, 9)], 4, 8.5) == [(4, 5), (8, 8.5)]


def test_op_names_and_kinds():
    fus = ("%slice_bitcast_fusion.7 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} "
           "%p), kind=kLoop, calls=%fused_computation.3")
    assert TR.op_name(fus) == "slice_bitcast_fusion.7"
    assert TR.op_family(fus) == "slice_bitcast_fusion"
    assert TR.op_kind(fus) == "fusion"
    ar = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
    assert TR.is_collective(ar) and not TR.is_collective(fus)
    assert TR.is_collective("%ag = bf16[8]{0} all-gather-start(bf16[2]{0} %x)")
    cc = ('%jvp__.2 = (bf16[64,2048,128]{2,1,0}, f32[64,1,2048]{2,1,0}) '
          'custom-call(bf16[64,2048,128]{2,1,0} %a), '
          'custom_call_target="tpu_custom_call"')
    assert TR.op_kind(cc) == "custom-call" and TR.is_pallas_call(cc)


def test_gaps_go_to_the_span_that_covers_them():
    dev = TR.Device("/device:TPU:0", ops=[
        TR.Ev("%a = f32[] fusion()", 1.0, 1.0),
        TR.Ev("%b = f32[] fusion()", 3.0, 0.5),
        TR.Ev("%c = f32[] fusion()", 3.5 + 1e-7, 0.5),
        TR.Ev("%d = f32[] fusion()", 6.0, 0.5)])
    tr = TR.Trace([dev], [TR.Ev("bench.window", 0.0, 8.0),
                          TR.Ev("bench.submit", 0.0, 0.9),
                          TR.Ev("bench.engine_step", 1.9, 3.0)])
    win = tr.window()
    assert win == (0.0, 8.0)
    assert TR.busy_seconds(tr, win) == pytest.approx(2.5)
    assert TR.idle_share(tr, win) == pytest.approx(5.5 / 8, abs=1e-6)
    gaps = dict(TR.idle_gaps(tr, win))
    assert gaps["bench.submit"] == pytest.approx(1.0)          # [0, 1)
    # [2, 3) whole, and [4, 6) of which the span covers the most
    assert gaps["bench.engine_step"] == pytest.approx(3.0, abs=1e-5)
    assert gaps["_no_span_"] == pytest.approx(1.5)             # [6.5, 8)
    assert gaps["_between_ops_"] == pytest.approx(1e-7, rel=1e-3)


def test_exposed_collective_time():
    dev = TR.Device("/device:TPU:0", ops=[
        TR.Ev("%ar = f32[8]{0} all-reduce(f32[8]{0} %x)", 0.0, 2.0),
        TR.Ev("%f = f32[8]{0} fusion(f32[8]{0} %x)", 1.5, 1.0)])
    assert TR.exposed_collective_seconds(dev, (0.0, 3.0)) == pytest.approx(1.5)


# -- the recorded trace -----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    src = os.path.join(ROOT, "benchmark/testdata/serve_2layer.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("xplane") / "serve_2layer.xplane.pb"
    with gzip.open(src, "rb") as f:
        dst.write_bytes(f.read())
    return TR.load(str(dst))


def test_recorded_trace_planes_and_spans(recorded):
    assert len(recorded.devices) == 1
    dev = recorded.devices[0]
    assert len(dev.ops) == 1647 and len(dev.modules) == 13
    names = [s.name for s in recorded.spans]
    assert names == ["bench.submit"] + ["bench.engine_step"] * 5


def test_recorded_trace_busy_and_idle(recorded):
    win = (recorded.spans[0].start, recorded.spans[-1].end)
    assert win[1] - win[0] == pytest.approx(38.0367e-3, rel=1e-4)
    # the union of the op intervals, checked once against a 100 ns raster
    assert TR.busy_seconds(recorded, win) == pytest.approx(10.4386e-3,
                                                           rel=1e-4)
    assert TR.idle_share(recorded, win) == pytest.approx(0.72557, abs=1e-4)
    gaps = TR.idle_gaps(recorded, win)
    assert gaps[0][0] == "bench.engine_step"
    assert gaps[0][1] == pytest.approx(27.5948e-3, rel=1e-4)
    assert sum(g[1] for g in gaps) == pytest.approx(
        (win[1] - win[0]) - TR.busy_seconds(recorded, win), rel=1e-6)


def test_recorded_trace_programs(recorded):
    dev = recorded.devices[0]
    times = TR.program_times(dev)
    big = {k: v for k, v in times.items() if k.startswith("jit_step")}
    assert sorted(len(v) for v in big.values()) == [2, 2, 5]
    name, durs = TR.main_program(dev)
    assert len(durs) == 5                       # the decode program
    assert sum(durs) / 5 == pytest.approx(0.8644e-3, rel=1e-3)
    win = (recorded.spans[0].start, recorded.spans[-1].end)
    per_step = TR.modules_in_spans(recorded, "bench.engine_step", win)
    # first step: four prefills (buckets 512, 1024, 512, 1024), one decode
    assert [len(m) for _, m in per_step] == [5, 1, 1, 1, 1]
    first = [m.name for m in per_step[0][1]]
    assert first[0] == first[2] and first[1] == first[3]
    assert first[4] == name
    assert TR.top_ops(recorded, win, 1)[0][0] == "fusion"
