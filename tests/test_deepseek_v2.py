"""DeepSeek-V2 through the serving seam, at a small size on the CPU, against
the benchmark's plain reference (``benchmark/families/deepseek_v2/
reference.py``: float32, unabsorbed attention, no cache, imports nothing of
the program), on seeded weights from the benchmark's generator.

(a) prefill then decode through the latent paged cache gives the reference's
full-forward logits; (b) absorbed decode attention equals the unabsorbed;
(c) the 8 shares' routed parts, the shared experts counted once, add up to
the uncut reference's expert layer; (d) group-limited routing picks the
reference's experts, and no pair is dropped at any load, in either form of
the expert layer (grouped products over sorted pairs; every held expert
over the whole batch), which agree. The latent decode kernel runs here in
interpret mode against the dense path.
"""

import importlib
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

import paddle_tpu as paddle  # noqa: E402
from benchmark.lib import weights as LW  # noqa: E402
from benchmark.lib.family import load_family  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe.dropless import (  # noqa: E402
    DENSE_MAX_TOKENS, EXPERT_FORMS, dropless_glu_experts, dropless_route,
    expert_form, group_limited_topk)
from paddle_tpu.observability import device_names, metrics  # noqa: E402
from paddle_tpu.ops._pallas.latent_paged_attention import (  # noqa: E402
    latent_paged_attention_pallas, supported_shapes)
from paddle_tpu.serving import Request, ServingEngine  # noqa: E402
from paddle_tpu.serving.paged_cache import NULL_BLOCK  # noqa: E402

FA = importlib.import_module("paddle_tpu.ops.flash_attention")
BS = 4                      # tokens a page in these tests


def small_cfg(**over):
    """The benchmark's configuration file with every size shrunk: 1 dense + 2
    expert layers, 32 routed experts in 4 groups of which 4 are held."""
    with open(os.path.join(
            ROOT, "benchmark/configs/deepseek-v2-ep16-l5.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, kv_lora_rank=32,
               q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, moe_intermediate_size=32,
               num_attention_heads=4, num_hidden_layers=3, vocab_size=512,
               router_width=32, n_routed_experts=4, experts_held_first=0,
               n_group=4, topk_group=2, num_experts_per_tok=3)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def built():
    """(family, cfg, float32 weights, the program's model in float32)."""
    cfg = small_cfg()
    fam = load_family(ROOT, cfg)
    w = LW.make_weights(fam.weights, cfg, 2**31 + 7, dtype=jnp.float32)
    model = fam.adapter.build_model(cfg, remat=False)
    fam.adapter.load_weights(model, cfg, w)
    model.eval()
    return fam, cfg, w, model


def ref_logits(fam, cfg, w, ids):
    """The reference's logits at every position of ``ids``."""
    ref = fam.reference.Reference(cfg)
    x = ref.forward(w, np.asarray(ids, np.int32))
    return np.asarray(ref._head(x, jnp.arange(len(ids)), w["lnf_g"],
                                w["head"]))


def _expert_forms():
    """The form each program kind's expert layers ran in, from the names of
    the programs the engine noted: grouped where an instruction under
    ``moe/experts`` sorts the pairs or is a grouped product, else dense."""
    forms = {}
    for p in device_names.table():
        grouped = any(family == "moe/experts" and device_names.opcode(t)
                      in ("sort", "ragged-dot")
                      for t, (_, family) in p.ops.items())
        forms.setdefault(p.kind, set()).add(
            "grouped" if grouped else "dense")
    return forms


def assert_greedy_by_the_reference(fam, cfg, w, reqs, res):
    """Each request's served tokens are the reference's best wherever its
    best logit leads by more than rounding."""
    for q in reqs:
        out = np.asarray(res[q.rid].output)
        assert len(out) == len(q.prompt_ids) + q.max_new_tokens
        lg = ref_logits(fam, cfg, w, out)[len(q.prompt_ids) - 1:-1]
        served = out[len(q.prompt_ids):]
        gap = lg.max(axis=-1) - lg[np.arange(len(served)), served]
        assert gap.max() < 1e-4, (q.rid, gap)


# -- (a) prefill, then decode through the latent paged cache ------------------

class _Probe:
    """Keeps the logits a raw (un-jitted) engine program computed."""

    def __init__(self, model):
        self.model, self.seen = model, []
        self._logits = model.logits
        model.logits = self

    def __call__(self, hidden):
        out = self._logits(hidden)
        self.seen.append(np.asarray(out, np.float32))
        return out

    def close(self):
        del self.model.logits


def test_prefill_then_decode_gives_the_references_logits(built):
    """Three prompts whose lengths cross block edges (5, 8 and 11 tokens at
    4 a page), each prefilled by the engine's own program, then decoded
    TOGETHER for six steps at unequal contexts through the latent pool, fed
    the same tokens as the reference's full forward: logits agree at every
    position of every row."""
    fam, cfg, w, model = built
    rng = np.random.default_rng(3)
    lens, steps = [5, 8, 11], 6
    seqs = [rng.integers(0, cfg["vocab_size"], n + steps) for n in lens]
    eng = ServingEngine(model, block_size=BS, num_blocks=33, max_batch=4,
                        max_seq_len=32, prefill_buckets=[16],
                        decode_buckets=[4])
    assert eng.cache.rows == ((128,),) and len(eng.cache.pools) == 1
    probe = _Probe(model)
    try:
        with jax.default_matmul_precision("highest"):
            pools = eng.cache.pools
            tables = np.full((4, eng.max_blocks_per_seq), NULL_BLOCK,
                             np.int32)
            got = [[] for _ in lens]
            for r, n in enumerate(lens):
                blocks = eng.cache.allocator.alloc(-(-(n + steps) // BS))
                tables[r, :len(blocks)] = blocks
                ids = np.zeros((1, 16), np.int32)
                ids[0, :n] = seqs[r][:n]
                _, *pools = eng._prefill_raw(
                    jnp.asarray(ids), *pools,
                    jnp.asarray(tables[r, :16 // BS]), jnp.asarray(n))
                got[r].append(probe.seen[-1][0, 0])
            for t in range(steps):
                tokens = np.zeros((4,), np.int32)
                ctx = np.zeros((4,), np.int32)
                for r, n in enumerate(lens):
                    tokens[r], ctx[r] = seqs[r][n + t], n + t
                _, *pools = eng._decode_raw(
                    jnp.asarray(tokens), *pools, jnp.asarray(tables),
                    jnp.asarray(ctx))
                for r in range(len(lens)):
                    got[r].append(probe.seen[-1][r, 0])
    finally:
        probe.close()
    for r, n in enumerate(lens):
        want = ref_logits(fam, cfg, w, seqs[r])[n - 1:]
        np.testing.assert_allclose(np.stack(got[r]), want, atol=2e-5,
                                   rtol=1e-4)


def test_engine_serves_the_references_greedy_tokens(built):
    """The whole engine (scheduler, allocator, refills: five requests through
    two rows) serves, token for token, the greedy continuation of the
    reference's logits, wherever the reference's best logit leads by more
    than rounding. Every expert layer runs in its dense form."""
    fam, cfg, w, model = built
    rng = np.random.default_rng(5)
    reqs = [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(3, 8)),
                    prompt_ids=rng.integers(0, cfg["vocab_size"],
                                            int(rng.integers(3, 14))))
            for i in range(5)]
    eng = ServingEngine(model, block_size=BS, num_blocks=33, max_batch=2,
                        max_seq_len=32)
    device_names.reset()
    with jax.default_matmul_precision("highest"):
        res = eng.serve(reqs)
    assert len(res) == 5
    # no program here has more tokens than the dense form takes
    assert _expert_forms() == {"prefill": {"dense"}, "decode": {"dense"}}
    assert_greedy_by_the_reference(fam, cfg, w, reqs, res)


# -- (b) absorbed equals unabsorbed ------------------------------------------------

def test_absorbed_decode_attention_equals_the_plain_form(built):
    """One attention layer: the last position's plain-form output (keys and
    values up-projected a head) equals the absorbed form over the latent
    rows (``W_UK`` folded into the query, ``W_UV`` applied after)."""
    _, cfg, _, model = built
    attn = model.model.layers[1].self_attn
    rng = np.random.default_rng(0)
    s = 13
    x = jnp.asarray(rng.standard_normal((1, s, cfg["hidden_size"])),
                    jnp.float32)
    pos = jnp.arange(s)[None, :]
    with jax.default_matmul_precision("highest"):
        q, row = attn.project(x, pos)
        plain = attn.attend_plain(q, row)[:, -1:]
        q_last = tuple(a[:, -1:] for a in q)
        o_lat = FA.latent_attention(
            attn.absorb(q_last), row, jnp.asarray([[s - 1]]),
            value_dim=cfg["kv_lora_rank"], scale=attn.cfg.softmax_scale)
        absorbed = attn.up_v(o_lat)
    assert row.shape == (1, s, 128)          # 32 + 8, padded to a lane tile
    assert not np.asarray(row[..., 40:]).any()
    np.testing.assert_allclose(absorbed, plain, atol=2e-6, rtol=1e-5)


def test_softmax_scale_and_yarn_frequencies_as_published():
    from paddle_tpu.text.models.deepseek_v2 import (DeepseekV2Config,
                                                    yarn_inv_freq)
    cfg = DeepseekV2Config()
    m = 0.1 * 0.707 * np.log(40) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert cfg.latent_width == 576 and cfg.latent_row == 640
    f = yarn_inv_freq(64, 10000.0, cfg.rope_scaling)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # fast pairs keep their frequency, slow ones are interpolated by 40
    np.testing.assert_allclose(f[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(f[-8:], plain[-8:] / 40, rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    fam = load_family(ROOT, small_cfg())
    full = dict(small_cfg(), qk_rope_head_dim=64)
    full["rope_scaling"]["original_max_position_embeddings"] = 4096
    np.testing.assert_allclose(fam.reference.yarn_inv_freq(full), f,
                               rtol=1e-6)


# -- (c) the share adds up -----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_expert_layer():
    """8 shares of 4 experts each over the router's 32: each share's routed
    part (its expert layer's output less the shared experts'), summed, plus
    the shared experts once, is the uncut reference's expert layer."""
    cfg = small_cfg()
    fam = load_family(ROOT, cfg)
    uncut = small_cfg(n_routed_experts=32)
    w = LW.make_weights(fam.weights, uncut, 11, dtype=jnp.float32)
    lp = w["layers"][1]
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.standard_normal((24, cfg["hidden_size"])),
                    jnp.float32)
    ref = fam.reference
    with jax.default_matmul_precision("highest"):
        idx, weight = ref.routing(y, lp["w_router"], uncut, "float32")
        shared = ref.swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                            "float32")
        whole = shared
        for e in range(32):
            w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)
            whole = whole + w_e[:, None] * ref.swiglu(
                y, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e],
                "float32")
        total, held = shared, 0
        for share in range(8):
            scfg = small_cfg(experts_held_first=4 * share)
            model = fam.adapter.build_model(scfg, remat=False)
            moe = model.model.layers[1].mlp
            assert (moe.first, moe.count) == (4 * share, 4)
            sl = slice(4 * share, 4 * share + 4)
            moe.router.weight = lp["w_router"]
            moe.shared_experts.gate_proj.weight = lp["ws_gate"]
            moe.shared_experts.up_proj.weight = lp["ws_up"]
            moe.shared_experts.down_proj.weight = lp["ws_down"]
            moe.w_gate, moe.w_up, moe.w_down = \
                lp["we_gate"][sl], lp["we_up"][sl], lp["we_down"][sl]
            out, load = moe(y[None])
            total = total + (out[0] - shared)
            held += int(load.sum())
    assert held == 24 * 3           # every pair fell to exactly one share
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=1e-4)


# -- (d) routing, and no pair dropped -----------------------------------------------

def test_group_limited_routing_picks_the_references_experts():
    cfg = small_cfg()
    fam = load_family(ROOT, cfg)
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.standard_normal((64, cfg["hidden_size"])),
                    jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((cfg["hidden_size"], 32)) * 0.3,
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_idx, want_w = fam.reference.routing(y, w_r, cfg, "float32")
        probs = jax.nn.softmax(y @ w_r, axis=-1)
    idx, score = group_limited_topk(probs, 3, n_group=4, topk_group=2)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    np.testing.assert_allclose(
        np.sort(score * cfg["routed_scaling_factor"], -1),
        np.sort(want_w, -1), rtol=1e-6)
    # the kept experts lie in at most two groups of eight
    assert all(len({int(e) // 8 for e in row}) <= 2 for row in idx)
    # and a group that holds the single best expert can still lose: by hand
    p = np.full((1, 8), 0.01, np.float32)
    p[0, [0, 2, 3, 4]] = [0.5, 0.3, 0.29, 0.1]
    got, _ = group_limited_topk(jnp.asarray(p), 2, n_group=4, topk_group=1)
    assert sorted(np.asarray(got)[0]) == [0, 1]     # group 0 only


@pytest.mark.parametrize("form", list(EXPERT_FORMS))
@pytest.mark.parametrize("load", ["one_expert", "uneven", "none_held"])
def test_no_pair_is_dropped_at_any_load(load, form):
    """Every (token, expert) pair routed to a held expert is computed: all
    tokens to one expert, a skewed load, and a load that misses the share."""
    rng = np.random.default_rng(1)
    t, d, f, e, first = 40, 16, 8, 4, 8
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((e, d, f)) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.3, jnp.float32)
    if load == "one_expert":
        idx = np.stack([np.full(t, first + 2), np.full(t, 3)], axis=1)
    elif load == "uneven":
        idx = np.stack([first + (np.arange(t) % 7 == 0) * 3,
                        np.full(t, first + 1)], axis=1)
    else:
        idx = np.stack([np.full(t, 1), np.full(t, 30)], axis=1)
    weight = jnp.asarray(rng.uniform(0.5, 2.0, (t, 2)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, got_load = EXPERT_FORMS[form](x, jnp.asarray(idx), weight, wg,
                                         wu, wd, first=first)
        want = np.zeros((t, d), np.float32)
        for tok in range(t):
            for j in range(2):
                k = idx[tok, j] - first
                if 0 <= k < e:
                    h = jax.nn.silu(x[tok] @ wg[k]) * (x[tok] @ wu[k])
                    want[tok] += float(weight[tok, j]) * np.asarray(h @ wd[k])
    held = (idx >= first) & (idx < first + e)
    assert int(got_load.sum()) == int(held.sum())
    np.testing.assert_array_equal(
        got_load, [(idx == first + k).sum() for k in range(e)])
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    route = dropless_route(jnp.asarray(idx), e, first)
    assert route.token.shape == (t * 2,)    # room for every pair, always


def _routing_case(case, t, rng):
    """``(idx [t, k], experts held, first)`` of a router over 16 experts."""
    e, first, k = 4, 8, 3
    if case == "even":
        first = 0
        idx = (np.arange(t)[:, None] + np.arange(k)[None, :]) % e
    elif case == "one_expert":
        idx = np.stack([np.full(t, first + 2), np.full(t, 1), np.full(t, 15)],
                       axis=1)
    elif case == "none_held":
        idx = np.stack([np.full(t, 1), np.full(t, 5), np.full(t, 14)], axis=1)
    elif case == "first_gt_0":
        idx = np.stack([rng.permutation(16)[:k] for _ in range(t)])
    elif case == "padded":
        idx = np.stack([rng.permutation(16)[:k] for _ in range(t)])
        idx[rng.random(t) < 0.4] = -1        # how padding is routed nowhere
        idx[-1] = -1
    else:                                    # more picks than experts held
        assert case == "k_gt_e"
        e, k = 2, 5
        idx = np.stack([rng.permutation(16)[:k] for _ in range(t)])
        idx[::3, 0], idx[::3, 1] = first, first + 1
    return idx.astype(np.int32), e, first


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tokens", [24, DENSE_MAX_TOKENS + 8])
@pytest.mark.parametrize("case", ["even", "one_expert", "none_held",
                                  "first_gt_0", "padded", "k_gt_e"])
def test_the_two_forms_of_the_expert_layer_agree(case, tokens, dtype):
    """The grouped and the dense form, called directly on the same inputs
    at token counts on both sides of the threshold: the same loads exactly,
    the same sums to float32 round-off (each form rounds a bfloat16 product
    once, in another order of summation); ``dropless_glu_experts`` is the
    one ``expert_form`` names for the count."""
    rng = np.random.default_rng(tokens)
    d, f = 16, 8
    idx, e, first = _routing_case(case, tokens, rng)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((tokens, d)), dt)
    wg, wu = (jnp.asarray(rng.standard_normal((e, d, f)) * 0.3, dt)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.3, dt)
    weight = jnp.asarray(rng.uniform(0.5, 2.0, idx.shape), jnp.float32)
    args = (x, jnp.asarray(idx), weight, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        got = {name: fn(*args, first=first) for name, fn in
               EXPERT_FORMS.items()}
        picked = dropless_glu_experts(*args, first=first)
    (yg, lg), (yd, ld) = got["grouped"], got["dense"]
    assert yg.dtype == yd.dtype == jnp.float32 and ld.dtype == jnp.int32
    np.testing.assert_array_equal(lg, ld)
    np.testing.assert_array_equal(
        ld, [(idx == first + j).sum() for j in range(e)])
    tol = 1e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(yd, yg, atol=tol, rtol=tol)
    untouched = ~((idx >= first) & (idx < first + e)).any(axis=1)
    assert not np.asarray(yd)[untouched].any()      # exactly zero, not small
    form = expert_form(tokens)
    assert form == ("dense" if tokens <= DENSE_MAX_TOKENS else "grouped")
    np.testing.assert_array_equal(picked[0], got[form][0])
    np.testing.assert_array_equal(picked[1], got[form][1])


def test_engine_serves_the_references_tokens_through_both_forms(built):
    """Prompts longer than the dense form's most tokens, so that each
    prefill program (bucket 1024) runs the grouped form and each decode
    program (bucket 2) the dense one: token for token the reference's greedy
    continuation, and the compiled programs' names show each form."""
    fam, cfg, w, model = built
    rng = np.random.default_rng(8)
    reqs = [Request(rid=f"r{i}", max_new_tokens=3 + i,
                    prompt_ids=rng.integers(0, cfg["vocab_size"],
                                            DENSE_MAX_TOKENS + 9 + 20 * i))
            for i in range(2)]
    eng = ServingEngine(model, block_size=16, num_blocks=129, max_batch=2,
                        max_seq_len=1024, prefill_buckets=[1024],
                        decode_buckets=[2])
    device_names.reset()
    with jax.default_matmul_precision("highest"):
        res = eng.serve(reqs)
    assert sum(1 for l in model.model.layers if l.is_moe) == 2
    assert _expert_forms() == {"prefill": {"grouped"}, "decode": {"dense"}}
    assert_greedy_by_the_reference(fam, cfg, w, reqs, res)


# -- the latent decode kernel, interpreted ---------------------------------------------

def _pool_case(seed, lengths, n_layers=2, bs=16, w=256, heads=8, m=6):
    rng = np.random.default_rng(seed)
    nb = 1 + len(lengths) * m
    pool = jnp.asarray(rng.standard_normal((n_layers, nb, bs, w)),
                       jnp.bfloat16)
    tables = np.zeros((len(lengths), m), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    for r, n in enumerate(lengths):
        used = -(-n // bs)
        tables[r, :used] = perm[r * m:r * m + used]
    q = jnp.asarray(rng.standard_normal((len(lengths), 1, heads, w)),
                    jnp.bfloat16)
    return q, pool, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("lengths,pages", [
    ([1, 16, 17, 96], 2),        # one key, a whole page, one over, full table
    ([0, 33, 0, 64], 2),         # rows without keys between rows with
    ([50, 3, 81], 16),           # a step wider than any row's pages
    ([0, 0], 2),                 # nothing to read at all
])
def test_latent_kernel_equals_the_dense_path(lengths, pages):
    q, pool, tables, lens = _pool_case(7, lengths)
    for layer in (0, 1):
        got = latent_paged_attention_pallas(
            q, pool, tables, lens, value_dim=128, scale=0.2, layer=layer,
            pages_per_step=pages, interpret=True)
        want = FA.latent_paged_attention(
            q, pool, tables, lens, block_size=16, value_dim=128, scale=0.2,
            layer=layer)
        assert got.shape == (len(lengths), 1, 8, 128)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)
        for r, n in enumerate(lengths):
            if n == 0:
                assert not np.asarray(got[r], np.float32).any()


def test_latent_kernel_takes_the_cells_shapes_and_refuses_others():
    pool = jax.ShapeDtypeStruct((5, 64, 16, 640), jnp.bfloat16)
    assert supported_shapes(jnp.bfloat16, pool, 512)
    assert not supported_shapes(jnp.float32, pool, 512)
    assert not supported_shapes(
        jnp.bfloat16, jax.ShapeDtypeStruct((5, 64, 16, 576), jnp.bfloat16),
        512)                     # a row that is not whole lane tiles
    assert not supported_shapes(
        jnp.bfloat16, jax.ShapeDtypeStruct((5, 64, 8, 640), jnp.bfloat16),
        512)
    # off the chip the dense path is the declared one, for either pool kind
    assert not FA.takes_paged_kernel(jnp.bfloat16, jnp.zeros((1, 2, 16, 640),
                                                             jnp.bfloat16),
                                     512)


def test_engine_tokens_equal_with_the_latent_kernel_and_counters_move(
        monkeypatch):
    """A whole ``ServingEngine`` run of the model (float32: in bfloat16 the
    two paths round their probabilities at different places, and a near-tie
    of random weights falls either way) with the latent entry point steered
    to the interpreted kernel serves the dense path's tokens; ``serving.kv_tokens`` counts pages read, and the expert counters
    count every real token's pairs once."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.text.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                                    deepseek_v2_tiny)
    LP = importlib.import_module(
        "paddle_tpu.ops._pallas.latent_paged_attention")
    engine_mod = importlib.import_module("paddle_tpu.serving.engine")
    paddle.seed(3)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny(
        kv_lora_rank=128, qk_rope_head_dim=16, experts_held=(4, 4)))
    rng = np.random.default_rng(9)
    reqs = [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(2, 6)),
                    prompt_ids=rng.integers(0, 512, int(rng.integers(3, 40))))
            for i in range(4)]

    def serve():
        kv = metrics.counter("serving.kv_tokens")
        pairs = metrics.counter("serving.moe_assignments")
        before = (kv.labels(kind="gathered").get(),
                  pairs.labels(kind="routed").get(),
                  pairs.labels(kind="held").get())
        eng = ServingEngine(model, block_size=16, num_blocks=33, max_batch=2,
                            max_seq_len=64)
        res = eng.serve(reqs)
        after = (kv.labels(kind="gathered").get(),
                 pairs.labels(kind="routed").get(),
                 pairs.labels(kind="held").get())
        return eng, res, [a - b for a, b in zip(after, before)]

    dense_eng, dense, dense_n = serve()
    assert not dense_eng._decode_paged
    calls = []
    kernel = LP.latent_paged_attention_pallas

    def interpreted(*a, **kw):
        calls.append(1)
        return kernel(*a, **dict(kw, interpret=True))

    monkeypatch.setattr(FA, "takes_paged_kernel", lambda *a: True)
    monkeypatch.setattr(engine_mod, "takes_paged_kernel", lambda *a: True)
    monkeypatch.setattr(LP, "latent_paged_attention_pallas", interpreted)
    paged_eng, paged, paged_n = serve()
    assert paged_eng._decode_paged and calls
    for rid in dense:
        np.testing.assert_array_equal(paged[rid].output, dense[rid].output)
    assert 0 < paged_n[0] < dense_n[0]
    tokens = sum(len(q.prompt_ids) + q.max_new_tokens - 1 for q in reqs)
    moe_layers = sum(1 for l in model.model.layers if l.is_moe)
    assert dense_n[1] == paged_n[1] == tokens * 3 * moe_layers
    assert 0 < dense_n[2] <= dense_n[1] and dense_n[2] == paged_n[2]
    loads = metrics.snapshot()["serving.moe_expert_load"]["series"]
    assert {s["labels"]["expert"] for s in loads} >= {"4", "5", "6", "7"}


def test_extend_program_over_latent_rows_serves_the_same_tokens(built):
    """The multi-token paged step (chunked prefill here) runs over latent
    rows too: the tokens equal the one-shot prefill's."""
    _, cfg, _, model = built
    rng = np.random.default_rng(6)
    reqs = [Request(rid=f"r{i}", max_new_tokens=4,
                    prompt_ids=rng.integers(0, cfg["vocab_size"], 9 + 6 * i))
            for i in range(3)]
    with jax.default_matmul_precision("highest"):
        plain = ServingEngine(model, block_size=BS, num_blocks=33,
                              max_batch=2, max_seq_len=32).serve(reqs)
        chunked = ServingEngine(model, block_size=BS, num_blocks=33,
                                max_batch=2, max_seq_len=32,
                                chunked_prefill=8).serve(reqs)
    for rid in plain:
        np.testing.assert_array_equal(chunked[rid].output, plain[rid].output)
