"""Olmo-Hybrid (gated-delta-rule linear layers beside a full attention layer)
through the serving seam, at a small size on the CPU, against the
benchmark's plain reference (``benchmark/families/olmo_hybrid/reference.py``:
float32, the linear layers token by token, no kernels, no cache, imports
nothing of the program), on seeded weights from the benchmark's generator.

(a) the chunked form of the rule gives the recurrent form's outputs and final
state, through a padded prompt; (b) the decode kernel in interpret mode gives
the jnp step's, a pad row's null slot and a row whose slot is not its batch
index included; (c) the model's forward and the engine's prefill and decode
through both caches give the reference's logits and tokens; (d) slots are
given back on finish, cancel and failure, and a spill and restore of a state
is bitwise, (c) and (d) at a convolution tail of whole rows of 128 lanes and
at one whose last row is padded; (e) a row preempted with its token in flight resumes from the
state of its committed tokens; (f) a model without state layers has the
programs it had; (g) the tiers that would need a state at a shared point are
refused.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import correct  # noqa: E402
from benchmark.lib import weights as LW  # noqa: E402
from benchmark.lib.family import load_family  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.ops.gated_delta import (  # noqa: E402
    chunk_gated_delta, gated_delta_decode, gated_delta_step, l2_normalize)
from paddle_tpu.serving import Request, ServingEngine, Status  # noqa: E402

SEED = 2**31 + 37
MAX_SEQ = 64


def small_cfg(**over):
    """The benchmark's configuration file with every width shrunk but the
    full layer's head (128, so that its pool takes the layout it has on the
    chip): one period, 2 heads of d_k 16 and d_v 32 in the linear layers."""
    with open(os.path.join(ROOT,
                           "benchmark/configs/olmo-hybrid-7b-l4.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=256, intermediate_size=96, num_attention_heads=2,
               num_key_value_heads=2, linear_num_key_heads=2,
               linear_num_value_heads=2, linear_key_head_dim=16,
               linear_value_head_dim=32, vocab_size=512,
               max_position_embeddings=128, a_log_offset=[-4.0, -1.0])
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    cfg.update(over)
    return cfg


def _build(**over):
    cfg = small_cfg(**over)
    fam = load_family(ROOT, cfg)
    w = LW.make_weights(fam.weights, cfg, SEED, dtype=jnp.float32)
    ref = fam.reference.Reference(cfg)
    return cfg, fam, w, ref


@pytest.fixture(scope="module")
def built():
    return _build()


#: the convolution tail's width ``(K - 1) * C``: 384, whole rows of 128
#: lanes (``built``), and 336, whose last row is padded (d_v 24: C = 112)
TAILS = {"tail384": None, "tail336": dict(linear_value_head_dim=24)}


@pytest.fixture(scope="module", params=list(TAILS))
def by_tail(request, built):
    over = TAILS[request.param]
    return _build(**over) if over else built


def model_of(built):
    cfg, fam, w, _ = built
    model = fam.adapter.build_model(cfg, remat=False)
    fam.adapter.load_weights(model, cfg, w)
    model.eval()
    return model


def engine(model, **kw):
    args = dict(block_size=16, num_blocks=32, max_batch=4,
                max_seq_len=MAX_SEQ, prefill_buckets=[16, 48],
                decode_buckets=[4])
    args.update(kw)
    return ServingEngine(model, **args)


def prompts(n, seed=0, lo=3, hi=30):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=int(rng.integers(lo, hi)))
            for _ in range(n)]


def _count(name: str) -> float:
    """A counter's value summed over its series (0 before its first)."""
    fam = metrics.snapshot().get(name) or {}
    return sum(s["value"] for s in fam.get("series", ()))


def served_gap(built, seq) -> float:
    """The widest gap between a served token's reference logit and the
    reference's best at its position."""
    _, _, w, ref = built
    toks = seq.out_tokens
    logits = ref.served_logits(w, seq.request.prompt_ids, toks, MAX_SEQ,
                               len(toks))
    return float(correct.served_gaps(logits, toks).max())


def _rule_inputs(b, s, h, dk, dv, seed=0):
    rng = np.random.default_rng(seed)
    q = l2_normalize(jnp.asarray(rng.normal(size=(b, s, h, dk)),
                                 jnp.float32)) / np.sqrt(dk)
    k = l2_normalize(jnp.asarray(rng.normal(size=(b, s, h, dk)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 0.7, size=(b, s, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, size=(b, s, h)), jnp.float32)
    return q, k, v, g, beta


def test_the_chunked_form_is_the_recurrent_form_through_padding():
    b, s, h, dk, dv, n = 2, 100, 3, 16, 32, 77
    q, k, v, g, beta = _rule_inputs(b, s, h, dk, dv)
    real = (jnp.arange(s) < n)[None, :, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    o, state = chunk_gated_delta(q, k, v, g, beta)
    st = jnp.zeros((b, dk, h * dv), jnp.float32)
    outs = []
    for t in range(n):
        o_t, st = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], st)
        outs.append(o_t)
    np.testing.assert_allclose(np.asarray(o[:, :n]),
                               np.asarray(jnp.stack(outs, 1)),
                               rtol=2e-4, atol=2e-5)
    # the padding past n left the state as the n-th token left it
    np.testing.assert_allclose(np.asarray(state), np.asarray(st),
                               rtol=2e-4, atol=2e-5)


def test_the_decode_kernel_is_the_jnp_step_by_slot():
    from paddle_tpu.ops._pallas.gated_delta_decode import (
        gated_delta_decode_pallas)
    b, h, dk, dv, layers, slots_n = 5, 3, 16, 64, 2, 7
    q, k, v, g, beta = (x[:, 0] for x in _rule_inputs(b, 1, h, dk, dv, 1))
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=(layers, slots_n, dk, h * dv)),
                       jnp.float32)
    # rows' slots are not their batch index; rows 1 and 4 are pad rows
    slots = jnp.asarray([3, 0, 5, 1, 0], jnp.int32)
    o_ref, p_ref = gated_delta_decode(q, k, v, g, beta, pool, slots, layer=1)
    o, p = gated_delta_decode_pallas(q, k, v, g, beta, pool, slots, layer=1,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=1e-5,
                               atol=1e-6)
    live = [3, 5, 1]
    np.testing.assert_allclose(np.asarray(p)[1, live],
                               np.asarray(p_ref)[1, live], rtol=1e-5,
                               atol=1e-6)
    assert not np.asarray(o)[[1, 4]].any()          # a pad row returns 0
    # nothing else of the pool moved: the other layer, the free slots, and
    # the null slot (the kernel neither reads nor writes it)
    untouched = np.ones((layers, slots_n), bool)
    untouched[1, live] = False
    np.testing.assert_array_equal(np.asarray(p)[untouched],
                                  np.asarray(pool)[untouched])


def test_the_models_forward_is_the_references(built):
    cfg, _, w, ref = built
    model = model_of(built)
    ids = prompts(1, seed=3, lo=40, hi=41)[0]
    got = np.asarray(model(jnp.asarray(ids[None])))[0]
    want = np.asarray(ref.logits(w, ids))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-4)


def test_the_engine_serves_the_references_tokens(by_tail):
    built = by_tail
    cfg = built[0]
    model = model_of(built)
    eng = engine(model)
    # the tail kept a slot is rows of 128 lanes, the last zero-padded
    width = (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
             + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])
    n_tail = (cfg["linear_conv_kernel_dim"] - 1) * width
    tail = model.serve_state()[1]
    assert tail.shape == (-(-n_tail // 128), 128)
    assert eng.cache.states[1].shape[2:] == tail.shape
    reqs = [Request(f"r{i}", p, max_new_tokens=int(n)) for i, (p, n) in
            enumerate(zip(prompts(5, seed=4), [9, 14, 4, 20, 11]))]
    out = eng.serve(reqs)
    for r in reqs:
        seq = out[r.rid]
        assert seq.status is Status.FINISHED
        assert len(seq.out_tokens) == r.max_new_tokens
        assert served_gap(built, seq) < 1e-4, r.rid
    # every slot and block given back; pad rows' reads counted off the chip
    assert eng.cache.slots.n_used == 0 and eng.cache.allocator.n_used == 0
    fam = metrics.snapshot()["serving.state_rows"]["series"]
    by = {s["labels"]["kind"]: s["value"] for s in fam}
    assert 0 < by["needed"] <= by["read"]


def test_slots_are_given_back_on_cancel_and_failure(built):
    model = model_of(built)
    eng = engine(model)
    a, b = (eng.submit(Request(f"c{i}", p, max_new_tokens=24))
            for i, p in enumerate(prompts(2, seed=5)))
    for _ in range(3):
        eng.step()
    assert {a.state_slot, b.state_slot} == {1, 2}
    assert eng.cache.slots.n_used == 2
    eng._cancel(a, Status.SHED, "test")
    assert a.state_slot == 0 and eng.cache.slots.n_used == 1
    # a prefill that fails isolates its request and gives its slot back
    real = eng._prefill_fn

    def broken(*args):
        raise RuntimeError("device error")
    eng._prefill_fn = broken
    c = eng.submit(Request("c2", prompts(1, seed=6)[0], max_new_tokens=4))
    eng.step()
    assert c.status is Status.FAILED and c.state_slot == 0
    eng._prefill_fn = real
    while eng.sched.n_pending:
        eng.step()
    assert b.status is Status.FINISHED
    assert eng.cache.slots.n_used == 0


def test_a_spilled_state_comes_back_bitwise(by_tail):
    built = by_tail
    model = model_of(built)
    eng = engine(model)
    seq = eng.submit(Request("s", prompts(1, seed=7)[0], max_new_tokens=24))
    for _ in range(4):
        eng.step()
    ahead, eng._ahead = eng._ahead, None      # take the launch in flight
    eng._decode_collect(ahead)
    before = eng.cache.read_state(seq.state_slot)
    eng._preempt(seq)
    assert seq.status is Status.PREEMPTED and seq.state_slot == 0
    assert eng.cache.slots.n_used == 0
    for host, dev in zip(seq.host_state, before):
        np.testing.assert_array_equal(np.asarray(host), dev)
    assert seq.spilled_bytes >= eng.cache.bytes_per_slot > 0
    assert eng._try_admit()                    # restored into a fresh slot
    assert seq.state_slot > 0 and seq.host_state is None
    for got, want in zip(eng.cache.read_state(seq.state_slot), before):
        np.testing.assert_array_equal(got, want)
    while eng.sched.n_pending:
        eng.step()
    assert served_gap(built, seq) < 1e-4


def test_a_row_preempted_with_its_token_in_flight_resumes_right(built):
    """The launch in flight has advanced the row's state by a token the host
    has not taken: preempting it takes that launch first, so the state that
    is spilled and restored holds the committed tokens only."""
    model = model_of(built)
    eng = engine(model)
    seqs = [eng.submit(Request(f"f{i}", p, max_new_tokens=16))
            for i, p in enumerate(prompts(3, seed=8))]
    for _ in range(5):
        eng.step()
    victim = seqs[1]
    assert eng._flight_row(victim) >= 0
    n_before = len(victim.out_tokens)
    eng._preempt(victim)
    assert victim.status is Status.PREEMPTED
    assert len(victim.out_tokens) == n_before + 1  # its token was taken
    while eng.sched.n_pending:
        eng.step()
    for seq in seqs:
        assert seq.status is Status.FINISHED
        assert served_gap(built, seq) < 1e-4, seq.rid
    assert eng.cache.slots.n_used == 0


def test_preemptions_under_pool_pressure_keep_the_tokens(built):
    """A pool of three sequences' pages for four: rows are preempted as they
    grow, with tokens in flight, and every request still gets the
    reference's tokens."""
    model = model_of(built)
    eng = engine(model, num_blocks=9, validate_capacity=False)
    n0 = _count("serving.preemptions")
    reqs = [Request(f"p{i}", p, max_new_tokens=30) for i, p in
            enumerate(prompts(4, seed=9, lo=10, hi=20))]
    out = eng.serve(reqs)
    assert _count("serving.preemptions") > n0
    for r in reqs:
        assert out[r.rid].status is Status.FINISHED
        assert served_gap(built, out[r.rid]) < 1e-4, r.rid
    assert eng.cache.slots.n_used == 0 and eng.cache.allocator.n_used == 0


def test_a_model_without_state_layers_has_the_programs_it_had():
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny
    model = GPTForCausalLM(gpt_tiny())
    eng = ServingEngine(model, block_size=8, num_blocks=16, max_batch=2,
                        max_seq_len=64, prefill_buckets=[16],
                        decode_buckets=[2])
    assert eng.cache.states == () and eng.cache.slots is None
    assert eng.cache.arrays == eng.cache.pools
    assert eng._donated == (1, 2)
    steps = eng.trace_steps()
    # prefill (ids, K, V, block_ids, n_tokens); decode (tokens, K, V,
    # tables, ctx_lens, prev, src)
    assert len(steps["prefill"][0].jaxpr.invars) == 5
    assert len(steps["decode"][0].jaxpr.invars) == 7
    assert steps["prefill"][1] == steps["decode"][1] == (1, 2)
    assert len(steps["decode"][0].jaxpr.outvars) == 3


@pytest.mark.parametrize("tier", [dict(prefix_cache=True),
                                  dict(chunked_prefill=16),
                                  dict(speculative=2)])
def test_the_tiers_are_refused_for_a_model_with_state_layers(built, tier):
    with pytest.raises(ValueError, match="keep a state a sequence"):
        engine(model_of(built), **tier)
