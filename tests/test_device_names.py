"""The serving programs' device-side names (``observability/device_names``):
the engine's seam scopes reach every executed instruction of each family's
programs, the module names carry the program's kind, the table is built
after the fact from what the engine noted (and noted nothing under
``FLAGS_telemetry=off``), and the parser follows the computations that
execute (a loop's body, not a fusion's)."""

import gc
import json
import os
import sys
import weakref

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.family import load_family  # noqa: E402
from paddle_tpu.core import flags as core_flags  # noqa: E402
from paddle_tpu.observability import device_names as DN  # noqa: E402
from paddle_tpu.serving import Request, ServingEngine  # noqa: E402
from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny  # noqa: E402

#: instructions that are no work of their own (and carry no scope to check)
EXEMPT = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
          "while", "conditional", "call")

#: each family at a tiny size: its benchmark configuration's file and the
#: sizes shrunk (as its own tests shrink them), and the engine's shape
SMALL = {
    "deepseek": ("deepseek-v2-ep16-l5.json", dict(
        hidden_size=64, intermediate_size=128, kv_lora_rank=32,
        q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate_size=32, num_attention_heads=4,
        num_hidden_layers=2, vocab_size=512, router_width=32,
        n_routed_experts=4, experts_held_first=0, n_group=4, topk_group=2,
        num_experts_per_tok=3), dict(block_size=4, max_seq_len=32,
                                     prefill_buckets=[16, 32])),
    "sdar": ("sdar-30b-a3b-ep8-l16.json", dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        moe_intermediate_size=32, num_hidden_layers=2, vocab_size=512,
        router_width=16, num_experts=4, experts_held_first=4,
        num_experts_per_tok=4, max_position_embeddings=256),
        dict(block_size=8, max_seq_len=64, prefill_buckets=[16, 32])),
    "olmo": ("olmo-hybrid-7b-l4.json", dict(
        hidden_size=256, intermediate_size=96, num_attention_heads=2,
        num_key_value_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=32, vocab_size=512,
        max_position_embeddings=128),
        dict(block_size=16, max_seq_len=64, prefill_buckets=[16, 48])),
}


def small_model(family: str):
    if family == "gpt":
        m = GPTForCausalLM(gpt_tiny(vocab_size=128, hidden_size=48,
                                    num_layers=2, num_heads=4,
                                    max_position_embeddings=64))
        m.eval()
        return m, dict(block_size=8, max_seq_len=64, prefill_buckets=[16, 32])
    name, sizes, shape = SMALL[family]
    with open(os.path.join(ROOT, "benchmark/configs", name)) as f:
        cfg = json.load(f)
    cfg.update(sizes)
    if cfg.get("rope_scaling"):
        cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                                   original_max_position_embeddings=16)
    if "generation" in cfg:
        cfg["generation"] = dict(cfg["generation"], mask_token_id=511)
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    model = load_family(ROOT, cfg).adapter.build_model(cfg, remat=False)
    model.eval()
    return model, shape


def serve_two(model, shape):
    """Two short requests through a fresh engine of ``shape``."""
    eng = ServingEngine(model, num_blocks=40, max_batch=2,
                        decode_buckets=[2], **shape)
    rng = np.random.default_rng(3)
    eng.serve([Request(rid=f"r{i}", max_new_tokens=5,
                       prompt_ids=rng.integers(1, 100, 5 + 14 * i))
               for i in range(2)])
    return eng


@pytest.fixture(autouse=True)
def _fresh():
    prev = core_flags.get_flags(["telemetry"])
    core_flags.set_flags({"telemetry": "metrics"})
    DN.reset()
    yield
    core_flags.set_flags(prev)
    DN.reset()


@pytest.mark.parametrize("family", ["gpt", "deepseek", "sdar", "olmo"])
def test_table_names_every_instruction_the_programs_execute(family):
    """Each program the engine ran is in the table under its kind's module
    name, and every leaf instruction it executes lies under a seam scope."""
    model, shape = small_model(family)
    eng = serve_two(model, shape)
    progs = DN.table()
    decode = "block_decode" if family == "sdar" else "decode"
    assert {p.kind for p in progs} == {"prefill", decode}
    for p in progs:
        assert p.module == f"jit_serve_{p.kind}"
        leaf = {t: s for t, s in p.ops.items()
                if DN.opcode(t) not in EXEMPT}
        assert [t for t, (seam, _) in leaf.items() if not seam] == []
        seams = {seam for seam, _ in leaf.values()}
        assert {"embed", "attn/project", "attn/attend", "finish",
                "head"} <= seams
        if family == "olmo":
            assert "state" in seams
    # a program a signature the sentinels saw
    kinds = [p.kind for p in progs]
    assert kinds.count("prefill") == len(
        eng._sent_prefill._seen["serving.prefill"])
    assert kinds.count(decode) == len(eng._sent_decode._seen["serving.decode"])


def test_the_table_is_the_dispatched_programs_with_no_compile():
    """The noted shapes lower to the very program each dispatch compiled,
    so building the table compiles nothing (what it reads is what ran)."""
    import jax
    model, shape = small_model("gpt")
    serve_two(model, shape)
    compiles = []

    def on_event(event, *_, **__):
        if "backend_compile" in event:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        assert {p.kind for p in DN.table()} == {"prefill", "decode"}
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


def test_nothing_is_noted_under_telemetry_off():
    core_flags.set_flags({"telemetry": "off"})
    model, shape = small_model("gpt")
    serve_two(model, shape)
    assert DN.table() == []


def test_a_note_holds_neither_the_engine_nor_its_weights():
    """What is noted is the program's trace, so a deleted engine frees its
    model, and the table is still built from the notes afterwards."""
    model, shape = small_model("gpt")
    eng = serve_two(model, shape)
    gone = weakref.ref(model), weakref.ref(eng)
    del model, eng
    gc.collect()
    assert [r() for r in gone] == [None, None]
    assert {p.kind for p in DN.table()} == {"prefill", "decode"}


def test_scopes_of_an_op_name():
    assert DN.scopes("jit(serve_decode)/finish/moe/experts/dot_general") \
        == ("finish", "moe/experts")
    assert DN.scopes("jit(serve_prefill)/state/write/scatter") \
        == ("state/write", "")
    assert DN.scopes("jit(serve_decode)/state/gdn/step/mul") \
        == ("state", "gdn/step")
    assert DN.scopes("jit(serve_decode)/attn/project/jit(_rope)/mul") \
        == ("attn/project", "")
    assert DN.scopes("jit(serve_decode)/sample/argmax") == ("sample", "")
    assert DN.scopes("params['w']") == ("", "")


HLO = """HloModule jit_serve_decode, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg = f32[4]{0} negate(%p), metadata={op_name="jit(serve_decode)/head/neg"}
}

%body (b: (s32[], f32[4])) -> (s32[], f32[4]) {
  %b = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%b), index=0
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  %v = f32[4]{0} get-tuple-element(%b), index=1
  ROOT %t = (s32[], f32[4]{0}) tuple(%next, %v)
}

%cond (c: (s32[], f32[4])) -> pred[] {
  %c = (s32[], f32[4]{0}) parameter(0)
  %j = s32[] get-tuple-element(%c), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}

ENTRY %main (w: f32[4]) -> f32[4] {
  %w = f32[4]{0} parameter(0), metadata={op_name="params['w']"}
  %copy.1 = f32[4]{0} copy(%w), metadata={op_name="params['w']"}
  %fusion = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_decode)/head/neg"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]{0}) tuple(%zero, %fusion)
  %while = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(serve_decode)/state/while"}
  %out = f32[4]{0} get-tuple-element(%while), index=1
  ROOT %sum = f32[4]{0} add(%out, %out), metadata={op_name="jit(serve_decode)/other/add"}
}
"""


def test_parse_follows_the_computations_that_execute():
    """The entry and a loop's body and condition are read, a fusion's body
    is not; an instruction the compiler made takes its user's scopes, one
    inside a loop the compiler made its loop's; an op traced outside every
    seam stays unnamed."""
    p = DN.parse("decode", HLO)
    assert p.module == "jit_serve_decode"
    by_name = {t.split(" = ")[0]: s for t, s in p.ops.items()}
    assert "%neg" not in by_name and "%p" not in by_name
    assert by_name["%copy.1"] == ("head", "")           # its user's
    assert by_name["%next"] == ("state", "")            # its loop's
    assert by_name["%lt"] == ("state", "")
    assert by_name["%sum"] == ("", "")
    assert all(", metadata=" not in t for t in p.ops)
