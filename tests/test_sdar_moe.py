"""SDAR-MoE (generation by diffusion over blocks) through the serving seam, at
a small size on the CPU, against the benchmark's plain reference
(``benchmark/families/sdar_moe/reference.py``: float32, no kernels, no cache,
imports nothing of the program), on seeded weights from the benchmark's
generator.

(a) the block-causal prefill gives the reference's logits; (b) every denoise
state of a block, run through the paged cache by the engine's own programs
after a prefill, gives the reference's full-forward logits, for prompts with
``P % 4`` of 0-3, and what a commit pass leaves in the pool is the clean
block's keys and values; (c) the engine's tokens are the reference's
free-running generation, on the schedule branch and, with a head scaled until
confidences pass 0.9, on the threshold branch (several positions in one
pass), answers ending inside a block; (d) the shares of the routed experts add
up to the uncut layer; (e) the replay of served tokens gives a zero gap for
the honest program.
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
from paddle_tpu.ops.paged_layout import (  # noqa: E402
    gather_pages, split_keys_values)
from paddle_tpu.serving import Request, ServingEngine  # noqa: E402
from paddle_tpu.serving.paged_cache import NULL_BLOCK  # noqa: E402

BS = 8          # tokens a page in these tests (two blocks of 4)
B = 4


def small_cfg(**over):
    """The benchmark's configuration file with every size shrunk but the
    head's (128, so that the pool takes the layout it has on the chip): 2
    layers, a router over 16 experts of which 4 are held."""
    with open(os.path.join(
            ROOT, "benchmark/configs/sdar-30b-a3b-ep8-l16.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, num_hidden_layers=2, vocab_size=512,
               router_width=16, num_experts=4, experts_held_first=4,
               num_experts_per_tok=4, max_position_embeddings=256,
               # the generator's 0.018 at these widths leaves every logit
               # within a hundredth of the others; scaled so that the
               # unmasking order is decided by more than round-off
               head_init_scale=8.0)
    cfg["generation"] = dict(cfg["generation"], mask_token_id=511)
    cfg["precision"] = dict(cfg["precision"], weights="float32",
                            compute="float32")
    cfg.update(over)
    return cfg


def build(cfg, seed=2**31 + 7):
    fam = load_family(ROOT, cfg)
    w = LW.make_weights(fam.weights, cfg, seed, dtype=jnp.float32)
    model = fam.adapter.build_model(cfg, remat=False)
    fam.adapter.load_weights(model, cfg, w)
    model.eval()
    return fam, w, model


@pytest.fixture(scope="module")
def built():
    cfg = small_cfg()
    return (cfg,) + build(cfg)


def engine(model, **kw):
    args = dict(block_size=BS, num_blocks=64, max_batch=4, max_seq_len=64,
                prefill_buckets=[16, 32], decode_buckets=[4])
    args.update(kw)
    return ServingEngine(model, **args)


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


# -- (a) the block-causal prefill --------------------------------------------

@pytest.mark.parametrize("n", [4, 9, 16, 23])
def test_block_causal_forward_gives_the_references_logits(built, n):
    cfg, fam, w, model = built
    ids = np.random.default_rng(n).integers(0, 500, n)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)[None])[0])
    want = np.asarray(fam.reference.Reference(cfg).logits(w, ids))
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)
    # and the mask is the block's: a later token of the same block moves an
    # earlier position's logits, a token of a later block does not
    if n >= 8:
        other = ids.copy()
        other[n - 1] = (other[n - 1] + 1) % 500
        with jax.default_matmul_precision("highest"):
            moved = np.asarray(model(jnp.asarray(other)[None])[0])
        first_of_last = (n - 1) // B * B
        assert np.abs(moved[:first_of_last] - got[:first_of_last]).max() == 0
        if first_of_last < n - 1:
            assert np.abs(moved[first_of_last] - got[first_of_last]).max() > 0


# -- (b) prefill, then every denoise state through the paged cache -----------

@pytest.mark.parametrize("n_prompt", [8, 9, 10, 11, 3])
def test_every_denoise_state_through_the_paged_cache(built, n_prompt):
    """A prompt with ``P % 4`` of 0-3 (and one shorter than a block, which has
    no prefill pass) is prefilled by the engine's own program; then two
    blocks go through the block-decode program state by state, fed the
    tokens of a fixed answer in a fixed order (second position first), and
    committed: the logits of every state are the reference's full forward of
    the clean sequence so far followed by the block as it stands, and the
    pool afterwards holds the clean blocks' keys and values."""
    cfg, fam, w, model = built
    ref = fam.reference.Reference(cfg)
    rng = np.random.default_rng(n_prompt)
    prompt = rng.integers(0, 500, n_prompt)
    answer = rng.integers(0, 500, 7)             # ends inside a block
    end = n_prompt + len(answer)
    eng = engine(model)
    pools = eng.cache.pools
    assert [p.shape for p in pools] == [(2, 64, 4, BS, 128)]  # one, fused
    blocks = eng.cache.allocator.alloc(-(-(end + B) // BS))
    table = np.full((4, eng.max_blocks_per_seq), NULL_BLOCK, np.int32)
    table[0, :len(blocks)] = blocks
    n_clean = n_prompt // B * B
    probe = _Probe(model)
    width = 4
    blank = jnp.zeros((eng._state_len(width) + eng._n_counts,),
                      jnp.int32)
    try:
        with jax.default_matmul_precision("highest"):
            if n_clean:
                ids = np.zeros((1, 16), np.int32)
                ids[0, :n_clean] = prompt[:n_clean]
                _, *pools = eng._prefill_raw(
                    jnp.asarray(ids), *pools,
                    jnp.asarray(table[0, :16 // BS]), jnp.asarray(n_clean))
            seq = [int(t) for t in prompt]
            for pos0 in range(n_clean, end, B):
                pos = pos0 + np.arange(B)
                tok = np.full((B,), 511, np.int32)
                tok[:max(0, n_prompt - pos0)] = prompt[pos0:]
                todo = [int(p) for p in pos if n_prompt <= p < end]
                order = todo[1:2] + todo[:1] + todo[2:]
                for step in range(len(order) + 1):
                    commit = step == len(order)
                    tokens = np.zeros((width, B), np.int32)
                    tokens[0] = tok
                    masked = np.zeros((width, B), np.int32)
                    masked[0] = [p in order[step:] for p in pos]
                    out, *pools = eng._decode_raw(
                        jnp.asarray(tokens), *pools, jnp.asarray(table),
                        jnp.asarray([pos0, 0, 0, 0], jnp.int32),
                        jnp.asarray(masked),
                        jnp.asarray([commit, 0, 0, 0], jnp.int32),
                        jnp.asarray([end, 0, 0, 0], jnp.int32), blank,
                        jnp.full((width,), -1, jnp.int32))
                    got = probe.seen[-1][0]
                    ids = np.concatenate([np.asarray(seq[:pos0], np.int32),
                                          tok])
                    want = np.asarray(ref.logits(w, ids))[pos0:]
                    np.testing.assert_allclose(got, want, atol=5e-4,
                                               rtol=1e-4)
                    stage = int(np.asarray(out)[2 * width * B])
                    # (the pass also unmasks by its own rule; this test
                    # feeds every state itself and reads only the logits)
                    assert (stage == 2) == commit
                    if not commit:
                        tok[order[step] - pos0] = answer[order[step]
                                                         - n_prompt]
                seq = seq[:pos0] + [int(t) for t in tok]
    finally:
        probe.close()
    # the pool holds the clean sequence's keys and values (the last block
    # with its masked tail, as the commit pass ran it)
    clean = np.asarray(seq, np.int32)
    _, kv = ref.forward(w, clean, keep_kv=True)
    tab = jnp.asarray(table[:1])
    for li, (k, v) in enumerate(kv):
        halves = split_keys_values(gather_pages(pools[0][li], tab, BS)[0])
        for got, want in zip(halves, (k, v)):
            np.testing.assert_allclose(np.asarray(got[:len(clean)]),
                                       np.asarray(want),
                                       atol=2e-5, rtol=1e-4)


# -- (c) the engine's tokens are the reference's free run --------------------

CASES = [(5, 6), (8, 9), (2, 3), (11, 1), (3, 13), (16, 8), (7, 4)]


def _serve(model, cases, seed=0, **kw):
    rng = np.random.default_rng(seed)
    reqs = [Request(f"r{i}", rng.integers(0, 500, size=p), max_new_tokens=n)
            for i, (p, n) in enumerate(cases)]
    eng = engine(model, **kw)
    with jax.default_matmul_precision("highest"):
        done = eng.serve(reqs)
    return reqs, done, eng


def test_engine_serves_the_references_tokens_on_the_schedule_branch(built):
    cfg, fam, w, model = built
    ref = fam.reference.Reference(cfg)
    metrics.reset_all()
    reqs, done, eng = _serve(model, CASES)
    for (p, n), r in zip(CASES, reqs):
        seq = done[r.rid]
        assert seq.status.value == "finished", seq.error
        assert len(seq.out_tokens) == n           # honoured to the token
        assert seq.out_tokens == ref.generate(w, r.prompt_ids, n), (p, n)
        # and the replay of those tokens has no gap anywhere
        gap = correct.served_gaps(np.asarray(ref.served_logits(
            w, r.prompt_ids, seq.out_tokens, 64, 16)), seq.out_tokens)
        assert gap.max() < 1e-4, (r.rid, gap)
    snap = metrics.snapshot()
    by = {tuple(sorted(s["labels"].items())): s["value"]
          for s in snap["serving.diffusion_unmasked"]["series"]}
    assert by[(("rule", "threshold"),)] == 0
    assert by[(("rule", "schedule"),)] == sum(n for _, n in CASES)
    eng.sched.assert_idle()
    assert eng.cache.allocator.n_used == 0


def test_engine_serves_the_references_tokens_on_the_threshold_branch():
    """A head scaled until confidences pass 0.9: several positions of a block
    are unmasked in one pass, and fewer passes than positions run."""
    cfg = small_cfg(head_init_scale=64.0)
    fam, w, model = build(cfg)
    ref = fam.reference.Reference(cfg)
    metrics.reset_all()
    reqs, done, _ = _serve(model, CASES, seed=1)
    for (p, n), r in zip(CASES, reqs):
        assert done[r.rid].out_tokens == ref.generate(w, r.prompt_ids, n)
    snap = metrics.snapshot()
    by = {s["labels"]["rule"]: s["value"]
          for s in snap["serving.diffusion_unmasked"]["series"]}
    passes = {s["labels"]["kind"]: s["value"]
              for s in snap["serving.diffusion_passes"]["series"]}
    assert by["threshold"] > 0 and by["schedule"] > 0
    assert by["threshold"] + by["schedule"] == sum(n for _, n in CASES)
    assert passes["denoise"] < sum(n for _, n in CASES)


def test_a_prompt_token_equal_to_the_mask_id_is_a_prompt(built):
    """Masked-ness is state, not equality with the mask id."""
    cfg, fam, w, model = built
    ref = fam.reference.Reference(cfg)
    prompt = np.asarray([3, 511, 511, 7, 511, 9], np.int32)
    eng = engine(model)
    with jax.default_matmul_precision("highest"):
        done = eng.serve([Request("m", prompt, max_new_tokens=6)])
    assert done["m"].out_tokens == ref.generate(w, prompt, 6)
    assert list(done["m"].output[:6]) == list(prompt)


# -- (d) the shares add up ---------------------------------------------------

def test_the_shares_add_up_to_the_uncut_expert_layer():
    """4 shares of 4 experts each over the router's 16: the shares' outputs,
    summed, are the uncut reference's expert layer, and every pair fell to
    exactly one share."""
    cfg = small_cfg()
    fam = load_family(ROOT, cfg)
    uncut = small_cfg(num_experts=16, experts_held_first=0)
    w = LW.make_weights(fam.weights, uncut, 11, dtype=jnp.float32)
    lp = w["layers"][1]
    y = jnp.asarray(np.random.default_rng(2).standard_normal(
        (24, cfg["hidden_size"])), jnp.float32)
    ref = fam.reference
    with jax.default_matmul_precision("highest"):
        idx, weight = ref.routing(y, lp["w_router"], uncut, "float32")
        np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0,
                                   rtol=1e-6)
        whole = 0.0
        for e in range(16):
            w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)
            whole = whole + w_e[:, None] * ref.swiglu(
                y, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e],
                "float32")
        total, held = 0.0, 0
        for share in range(4):
            scfg = small_cfg(experts_held_first=4 * share)
            moe = fam.adapter.build_model(scfg, remat=False) \
                .model.layers[1].mlp
            assert (moe.first, moe.count) == (4 * share, 4)
            sl = slice(4 * share, 4 * share + 4)
            moe.router.weight = lp["w_router"]
            moe.w_gate, moe.w_up, moe.w_down = \
                lp["we_gate"][sl], lp["we_up"][sl], lp["we_down"][sl]
            out, load = moe(y[None])
            total = total + out[0]
            held += int(load.sum())
    assert held == 24 * 4
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=1e-4)


def test_the_model_answers_the_engines_question():
    cfg = small_cfg()
    _, _, model = build(cfg)
    gen = model.serve_generation
    assert (gen.block_length, gen.steps, gen.threshold, gen.mask_id) == \
        (4, 4, 0.9, 511)
    assert model.serve_cache_rows() == ((4, 128),)       # keys | values
    assert model.serve_counts == 4 and model.serve_latent_value_dim is None
    with pytest.raises(ValueError, match="diffusion over blocks"):
        engine(model, prefix_cache=True)
    with pytest.raises(ValueError, match="block length"):
        engine(model, block_size=2, prefill_buckets=[16])
