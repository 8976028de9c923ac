"""The readers of the program's name table (``benchmark/lib/device_names.py``:
``model.decode_named_share``, ``model.prefill_named_share``,
``model.decode_head_ms``) on a made-up trace built from a real table: a tiny
GPT engine served on the CPU notes its programs, and each instruction its
decode and prefill programs execute becomes an op event inside a module
event of the program's name. The seams' time sums to the programs' leaf
time, a control op is not counted, an instruction the table lacks lands in
``unmatched_ms``, and a program without a table has nothing to read.
Nothing here is a measurement."""

import numpy as np
import pytest

from perfbench_tiny import ROOT

from benchmark import run as R
from benchmark.lib import device_names as DNR
from benchmark.lib import peaks as P
from benchmark.lib import readers
from benchmark.lib import trace as TR

#: no event of their own; a control op's event spans its body's (left out)
NOT_EVENTS = ("parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "while", "conditional", "call")
LOOP = ("%while.9 = (s32[]{:T(128)}) while(%tuple.1), condition=%cond, "
        "body=%body")
STRAY = "%fusion.999 = f32[7]{0} fusion(f32[7]{0} %p.1), kind=kLoop"


@pytest.fixture(scope="module")
def programs():
    from paddle_tpu.core import flags as core_flags
    from paddle_tpu.observability import device_names
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny
    prev = core_flags.get_flags(["telemetry"])
    core_flags.set_flags({"telemetry": "metrics"})
    device_names.reset()
    model = GPTForCausalLM(gpt_tiny(vocab_size=128, hidden_size=48,
                                    num_layers=2, num_heads=4,
                                    max_position_embeddings=64))
    model.eval()
    eng = ServingEngine(model, block_size=8, num_blocks=16, max_batch=2,
                        max_seq_len=32, prefill_buckets=[16],
                        decode_buckets=[2])
    eng.serve([Request(rid="a", max_new_tokens=4,
                       prompt_ids=np.arange(1, 7))])
    yield {p.kind: p for p in device_names.table()}
    device_names.reset()
    core_flags.set_flags(prev)


def _trace(programs, kinds):
    """Each program of ``kinds`` run once after the other: its executed
    instructions as op events of 1-3 us, a loop spanning them and one
    instruction the table lacks; returns the trace and each program's
    seconds by seam scope and its leaf seconds."""
    dev = TR.Device("/device:TPU:0")
    t, want = 0.0, []
    for kind in kinds:
        prog, t0, by_seam = programs[kind], t, {}
        ops = [(text, seam) for text, (seam, _) in prog.ops.items()
               if TR.op_kind(text) not in NOT_EVENTS]
        dev.ops.append(TR.Ev(LOOP, t, 1e-3))
        for i, (text, seam) in enumerate(ops):
            dur = 1e-6 * (1 + i % 3)
            dev.ops.append(TR.Ev(text, t, dur))
            by_seam[seam] = by_seam.get(seam, 0.0) + dur
            t += dur
        dev.ops.append(TR.Ev(STRAY, t, 5e-6))
        t += 5e-6
        dev.modules.append(TR.Ev(f"{prog.module}(1234)", t0, t - t0))
        want.append((by_seam, t - t0))
        t += 1e-4
    dev.ops.sort(key=lambda e: e.start)
    return TR.Trace([dev], []), want


def _ctx(trace, win=(0.0, 1.0)):
    return readers.Ctx(run={}, cfg={}, mix={}, cell={}, chips=1,
                       peaks=P.peaks_of("TPU v5 lite"), trace=trace,
                       win=win)


def test_the_seams_sum_to_the_decode_programs_leaf_time(programs):
    trace, want = _trace(programs, ["decode", "prefill", "decode"])
    got = R.load_reader(ROOT, "model.decode_named_share")(_ctx(trace))
    by_seam, leaf = want[0]
    assert got["programs"] == 2
    assert got["leaf_ms"] == pytest.approx(1e3 * leaf)
    # the one instruction the table lacks, in each program
    assert got["unmatched_ms"] == pytest.approx(5e-3)
    assert got["unnamed_ops"] == {}
    assert got["by_scope"] == pytest.approx(
        {s: 1e3 * v for s, v in by_seam.items()})
    assert sum(got["by_scope"].values()) + got["unmatched_ms"] == \
        pytest.approx(got["leaf_ms"])
    assert got["value"] == pytest.approx(
        100.0 * (leaf - 5e-6) / leaf)
    assert {"embed", "attn/project", "attn/attend", "finish",
            "head"} <= set(got["by_scope"])
    # the four largest op families of each seam, largest first
    for seam, ops in got["by_op"].items():
        assert 0 < len(ops) <= 4 and list(ops.values()) == sorted(
            ops.values(), reverse=True)
        assert sum(ops.values()) <= got["by_scope"][seam] + 1e-9


def test_prefill_and_head_read_their_own_programs(programs):
    trace, want = _trace(programs, ["decode", "prefill"])
    pre = R.load_reader(ROOT, "model.prefill_named_share")(_ctx(trace))
    by_seam, leaf = want[1]
    assert pre["programs"] == 1
    assert pre["by_scope"] == pytest.approx(
        {s: 1e3 * v for s, v in by_seam.items()})
    head = R.load_reader(ROOT, "model.decode_head_ms")(_ctx(trace))
    dec = want[0][0]
    assert head["value"] == pytest.approx(
        1e3 * (dec.get("head", 0.0) + dec.get("sample", 0.0)))
    assert sum(head["by_op"].values()) == pytest.approx(head["value"])


def test_nothing_to_read_without_a_table_or_a_trace(programs, monkeypatch):
    trace, _ = _trace(programs, ["decode", "prefill"])
    names = ("model.decode_named_share", "model.prefill_named_share",
             "model.decode_head_ms")
    for name in names:
        assert R.load_reader(ROOT, name)(_ctx(None, None)) is None
        # a window that holds no such program
        assert R.load_reader(ROOT, name)(_ctx(trace, (5.0, 6.0))) is None
    monkeypatch.setattr(DNR, "program_table", lambda: None)
    for name in names:
        assert R.load_reader(ROOT, name)(_ctx(trace)) is None


def test_a_trace_event_and_the_tables_text_share_a_key():
    """An op event prints its operands' shapes and an async pair as
    ``async-start``; the program's text prints neither."""
    assert DNR.key("%copy-start.2 = (bf16[2]{0}, u32[]) copy-start("
                   "bf16[2]{0} %p.1), cross_program_prefetch_index=0") == \
        DNR.key("%copy-start.2 = (bf16[2]{0}, u32[]) copy-start(%p.1)") == \
        "copy-start.2 (bf16[2]{0}, u32[])"
    assert DNR.key("%slice-start = ((bf16[4]{0}), bf16[2]{0}) async-start("
                   "bf16[4]{0} %w), calls=%async_computation") == \
        DNR.key("%slice-start = ((bf16[4]{0}), bf16[2]{0}) slice-start(%w),"
                " slice={[0:2]}")
    assert DNR.key("%fusion.3 = bf16[8]{0:T(128)S(1)} fusion(%a)") == \
        "fusion.3 bf16[8]{0:T(128)S(1)}"
    assert DNR.key("SyncWait") == "SyncWait"
