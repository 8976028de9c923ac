"""Plain reference of one chip's share of the SDAR-MoE decoder (JetLM,
SDAR-30B-A3B-Chat; the release's ``generate.py: block_diffusion_generate``
for the procedure): ``jax.numpy``, float32, ``highest`` matmul precision, no
kernels, no cache. It imports nothing of the program and takes nothing the
program has made; weights come from the shared generator
(``benchmark/lib/weights.py``) over this family's leaves (``weights.py``).

**The model**, a layer on ``h [S, hidden]`` (eps ``rms_norm_eps``, no biases):

- ``x = RMSNorm(h)``; ``q = x W_q -> [S, heads, d]``, ``k = x W_k``, ``v = x
  W_v -> [S, kv_heads, d]``; ``q = RMSNorm_d(q) * g_q``, ``k = RMSNorm_d(k) *
  g_k`` (one gain vector of ``d`` for all heads, before the rotation); RoPE
  ``rope_theta`` over the whole head, halves layout (``x cos + rotate_half(x)
  sin``), positions absolute; query head ``i`` uses kv head ``i // (heads /
  kv_heads)``; scores ``q . k / sqrt(d)``; softmax over the keys the mask
  allows; ``h' = h + concat(P v) W_o``.
- ``y = RMSNorm(h')``; ``p = softmax_float32(y W_r)`` over all
  ``router_width`` experts; the ``num_experts_per_tok`` largest ``p`` are the
  token's experts, weights ``p_e / sum of them`` (``norm_topk_prob``); ``h''
  = h' + sum over the token's experts that are HELD of w_e * (silu(y W_g^e) *
  (y W_u^e)) W_d^e``.
- final RMSNorm, logits through the untied head. The logit at position ``i``
  predicts token ``i`` (no shift).

**The mask is block-causal**, block length ``B = generation.block_length``
counted from position 0: position ``i`` sees ``j`` iff ``j // B <= i // B``,
for prompt and answer alike.

**Generation** (greedy, remasking ``low_confidence_dynamic``): with prompt
length ``P`` the blocks ``0 .. P // B - 1`` are clean; every later block
starts as the prompt's tail (if ``P % B`` and it is the first) and mask
tokens elsewhere. A denoise pass runs the block over the clean earlier
blocks, every position seeing the whole block. At each still-masked position
``x0 = argmax(logits)``, ``conf = softmax_float32(logits)[x0]``; with ``k = B
/ denoising_steps`` the positions whose ``conf > confidence_threshold`` are
unmasked if they are at least ``k``, else the ``k`` most confident; an
unmasked position takes its ``x0`` and never changes. A whole block is clean
context for the later ones (the program's commit pass stores exactly that).
Two departures from the release, stated in the configuration: masked-ness is
state (position ``>= P``, not yet unmasked), not equality with the mask id;
and positions past ``max_new_tokens`` in a request's last block hold the mask
token through every pass and are never chosen.

Departures from the published model, each the configuration's: **the share**
(of the routed experts only ``num_experts`` from ``experts_held_first`` are
held; what the others would add is left out and the partial ``h''`` goes on,
as in the program) and **the depth**. Departures from "one big forward": the
memory schedule only (a layer at a time, attention in blocks of query rows,
every held expert over every token behind a mask).

``mode`` computes every matrix product in a lower precision by rounding both
operands (``bfloat16``; ``float8``: e4m3 with one scale a tensor) before an
exact product; the router's product, which the configuration states in
float32, is then rounded to bfloat16; the confidence stays a float32
softmax. ``float32`` is the reference; the others are the controls that the
comparison has to fail. No cell trains this family: ``train_step`` and
``delta_norms`` say so.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("float32", "bfloat16", "float8")
QUERY_BLOCK = 256       # query rows whose [heads, block, 2 S] scores are alive


def _round(x, mode: str):
    if mode == "float32":
        return x
    if mode == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"mode {mode!r}; one of {MODES}")


def _mm(spec: str, a, b, mode: str):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def rope(x, theta: float):
    """``x [S, heads, d]`` at positions ``0..S-1``, halves layout."""
    s, _, d = x.shape
    inv_freq = jnp.asarray(
        theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    half = d // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


def attention(x, p, cfg, mode, clean):
    """``x [S, hidden]`` (normed). Every position attends the EARLIER blocks
    through ``clean = (k, v)`` (``None``: its own, which makes the plain
    block-causal attention of a clean sequence) and its own block through
    the keys and values of ``x`` itself. Returns ``(o W_o, k, v)``."""
    s = x.shape[0]
    nh, kh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    g, eps, blk = nh // kh, cfg["rms_norm_eps"], \
        cfg["generation"]["block_length"]
    q = _mm("sh,hk->sk", x, p["w_q"], mode).reshape(s, nh, d)
    k = _mm("sh,hk->sk", x, p["w_k"], mode).reshape(s, kh, d)
    v = _mm("sh,hk->sk", x, p["w_v"], mode).reshape(s, kh, d)
    q = rope(rms_norm(q, p["q_norm_g"], eps), cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm_g"], eps), cfg["rope_theta"])
    kc, vc = (k, v) if clean is None else clean
    q = q.reshape(s, kh, g, d)
    qb = math.gcd(s, QUERY_BLOCK)
    key_block = jnp.arange(s) // blk

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        mine = ((i * qb + jnp.arange(qb)) // blk)[:, None]
        earlier = _mm("qkgd,skd->kgqs", qi, kc, mode) / math.sqrt(d)
        own = _mm("qkgd,skd->kgqs", qi, k, mode) / math.sqrt(d)
        sc = jnp.concatenate([
            jnp.where(key_block[None, :] < mine, earlier, -jnp.inf),
            jnp.where(key_block[None, :] == mine, own, -jnp.inf)], axis=-1)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm("kgqs,skd->qkgd", pr[..., :s], vc, mode) \
            + _mm("kgqs,skd->qkgd", pr[..., s:], v, mode)

    o = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, nh * d)
    return _mm("sk,kh->sh", o, p["w_o"], mode), k, v


def swiglu(y, w_gate, w_up, w_down, mode):
    return _mm("sf,fh->sh", jax.nn.silu(_mm("sh,hf->sf", y, w_gate, mode))
               * _mm("sh,hf->sf", y, w_up, mode), w_down, mode)


def routing(y, w_router, cfg, mode):
    """``(idx [S, k], weight [S, k])``: the token's experts among all
    ``router_width`` and their weights."""
    p = jax.nn.softmax(_mm("sh,he->se", y, w_router,
                           "float32" if mode == "float32" else "bfloat16"),
                       axis=-1)
    val, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        val = val / jnp.sum(val, axis=-1, keepdims=True)
    return idx, val


def layer(x, p, clean, cfg, mode):
    """One decoder layer on ``x [S, hidden]``; ``clean`` as
    :func:`attention` takes it. Returns ``(x, k, v)``."""
    eps = cfg["rms_norm_eps"]
    o, k, v = attention(rms_norm(x, p["ln1_g"], eps), p, cfg, mode, clean)
    x = x + o
    y = rms_norm(x, p["ln2_g"], eps)
    idx, weight = routing(y, p["w_router"], cfg, mode)
    first = cfg.get("experts_held_first", 0)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(
            y, p["we_gate"][e], p["we_up"][e], p["we_down"][e], mode)
    return x + out, k, v


class Reference:
    """The reference bound to one configuration's sizes."""

    def __init__(self, cfg: Dict, mode: str = "float32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r}; one of {MODES}")
        self.cfg, self.mode = cfg, mode
        gen = cfg["generation"]
        self.block, self.mask_id = gen["block_length"], gen["mask_token_id"]
        self.k_min = max(1, self.block // gen["denoising_steps"])
        self.threshold = gen["confidence_threshold"]
        #: natural-log margin inside which two confidences count as tied when
        #: the served tokens are replayed (:meth:`choose`)
        self.tie_margin = cfg.get("check", {}).get("order_tie_log_margin",
                                                   0.0)
        fn = functools.partial(layer, cfg=cfg, mode=mode)
        self._layer_clean = jax.jit(lambda x, p: fn(x, p, None))
        self._layer_noisy = jax.jit(lambda x, p, kc, vc: fn(x, p,
                                                            (kc, vc))[0])
        self._embed = jax.jit(lambda ids, table: table[ids])
        scale = W.init_scale(cfg, "head")
        self._head = jax.jit(lambda x, pos, g, head: _mm(
            "th,hv->tv", rms_norm(x[pos], g, cfg["rms_norm_eps"]),
            head * scale, mode))

        @jax.jit
        def stats(logits, served):
            top = jnp.max(logits, axis=-1)
            lse = jax.nn.logsumexp(logits, axis=-1)
            at = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
            return jnp.argmax(logits, axis=-1), top - lse, top - at

        self._stats = stats
        self._keep = jax.jit(lambda kept, new, rows: jnp.where(
            rows[:, None], new, kept))

    # -- training: no cell trains this family ------------------------------

    def init_state(self, weights):
        raise NotImplementedError(
            "the sdar_moe family has no training reference: its cells serve "
            "(the noise schedule of its training is not given)")

    def train_step(self, state, ids, labels, hp, rows=None):
        self.init_state(None)

    def delta_norms(self, state, weights0):
        self.init_state(None)

    # -- the forward -------------------------------------------------------

    def forward(self, p32, ids, keep_kv: bool = False):
        """Hidden states ``[S, hidden]`` before the final norm of a CLEAN
        sequence under the block-causal mask; with ``keep_kv`` also every
        layer's keys and values."""
        x = self._embed(jnp.asarray(ids), p32["embed"])
        kv = []
        for lp in p32["layers"]:
            x, k, v = self._layer_clean(x, lp)
            if keep_kv:
                kv.append((k, v))
        return (x, kv) if keep_kv else x

    def logits(self, p32, ids):
        """Logits ``[S, V]`` of a clean sequence at every position."""
        return self._head(self.forward(p32, ids), jnp.arange(len(ids)),
                          p32["lnf_g"], p32["head"])

    # -- the unmask rule -----------------------------------------------------

    def choose(self, masked: np.ndarray, log_conf: np.ndarray,
               gap: np.ndarray = None) -> np.ndarray:
        """Which of one block's ``masked`` positions a denoise pass unmasks,
        from the log confidences it read there: every one over the threshold
        if they are at least ``k``, else the ``k`` most confident. ``gap``
        (how far a position's served token lies below the best logit) is for
        a replay of served tokens, the one thing the reference cannot know:
        among the masked positions whose confidence lies within
        ``tie_margin`` of the best it takes the one with the smallest gap
        (the earlier on equal gaps). A free run gives none and takes the most
        confident, whatever the margin."""
        chosen = np.zeros_like(masked)
        high = masked & (log_conf > math.log(self.threshold))
        if high.sum() >= self.k_min:
            return high
        left = masked.copy()
        for _ in range(min(self.k_min, int(masked.sum()))):
            best = log_conf[left].max()
            if gap is None:
                pick = np.flatnonzero(left & (log_conf >= best))[0]
            else:
                close = np.flatnonzero(left & (log_conf >= best
                                               - self.tie_margin))
                pick = close[np.argmin(gap[close])]
            chosen[pick], left[pick] = True, False
        return chosen

    # -- free-running generation (tests) -----------------------------------

    def generate(self, p32, prompt, max_new_tokens: int) -> List[int]:
        """The reference's own greedy generation: no cache, every denoise
        pass one forward of the clean sequence so far followed by the block
        in its present state."""
        B = self.block
        seq = [int(t) for t in prompt]
        n_prompt, end = len(seq), len(seq) + max_new_tokens
        for pos0 in range(n_prompt // B * B, end, B):
            pos = pos0 + np.arange(B)
            blk = np.full((B,), self.mask_id, np.int32)
            blk[:max(0, n_prompt - pos0)] = seq[pos0:]
            masked = (pos >= n_prompt) & (pos < end)
            while masked.any():
                ids = np.concatenate([np.asarray(seq[:pos0], np.int32), blk])
                x0, log_conf, _ = (np.asarray(a) for a in self._stats(
                    self.logits(p32, ids)[pos0:],
                    jnp.zeros((B,), jnp.int32)))
                take = self.choose(masked, log_conf)
                blk[take] = x0[take]
                masked &= ~take
            seq = seq[:pos0] + [int(t) for t in blk[:min(B, end - pos0)]]
        return seq[n_prompt:]

    # -- serving -----------------------------------------------------------

    def served_logits(self, p32, prompt, out_tokens, pad_to: int,
                      max_out: int):
        """Logits ``[max_out, V]``: row ``t`` is the reference's logits at
        output position ``t`` in the state in which that position was
        unmasked, replaying the generation on the served tokens (rows past
        the served count are padding).

        One clean forward over prompt + served tokens gives every layer's
        clean keys and values; the earlier blocks being given, all blocks of
        the request are independent, and denoise pass ``s`` of EVERY block is
        one forward of the noisy sequence, each block seeing the clean keys
        before it and itself as it stands. Which positions a pass unmasks is
        the reference's own choice, from its own confidences
        (:meth:`choose`); what it puts there is the served token. One
        compiled shape: ids padded to ``pad_to`` with the mask token (the
        tail of the answer's last block holds it in the program too; later
        blocks are seen by nobody)."""
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out_tokens, np.int32)
        n_prompt, n = prompt.size, out.size
        B = self.block
        clean = np.full((pad_to,), self.mask_id, np.int32)
        clean[:n_prompt] = prompt
        clean[n_prompt:n_prompt + n] = out
        _, kv = self.forward(p32, clean, keep_kv=True)
        rows = np.zeros((max_out,), np.int32)        # output rows' positions
        rows[:n] = n_prompt + np.arange(n)
        rows_d = jnp.asarray(rows)
        served = np.zeros((max_out,), np.int32)
        served[:n] = out
        served_d = jnp.asarray(served)
        block_of = (n_prompt + np.arange(n)) // B
        noisy = clean.copy()
        noisy[n_prompt:n_prompt + n] = self.mask_id
        masked = np.ones((n,), bool)
        kept = None
        while masked.any():
            x = self._embed(jnp.asarray(noisy), p32["embed"])
            for lp, (kc, vc) in zip(p32["layers"], kv):
                x = self._layer_noisy(x, lp, kc, vc)
            logits = self._head(x, rows_d, p32["lnf_g"], p32["head"])
            _, log_conf, gap = (np.asarray(a)[:n] for a in self._stats(
                logits, served_d))
            take = np.zeros((n,), bool)
            for b in np.unique(block_of[masked]):
                at = np.flatnonzero(block_of == b)
                take[at] = self.choose(masked[at], log_conf[at], gap[at])
            sel = np.zeros((max_out,), bool)
            sel[:n] = take
            kept = logits if kept is None \
                else self._keep(kept, logits, jnp.asarray(sel))
            noisy[n_prompt + np.flatnonzero(take)] = out[take]
            masked &= ~take
        return kept
