"""Plain reference of one chip's share of the DeepSeek-V2 decoder
(DeepSeek-AI 2024, arXiv:2405.04434, and the model's published modeling
code): ``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no
cache, no absorbed form. It imports nothing of the program and takes nothing
the program has made; weights come from the shared generator
(``benchmark/lib/weights.py``) over this family's leaves (``weights.py``).

The model, a layer on ``h [S, hidden]`` (eps ``rms_norm_eps``, no biases):

- ``x = RMSNorm(h)``. MLA: ``c_q = RMSNorm(x W_DQ)``; ``q = c_q W_UQ`` ->
  heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_DKV``, ``c_kv =
  RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_UKV`` a head; RoPE (YaRN, pairs
  interleaved as published, de-interleaved to halves before the rotation) on
  ``q_rope`` and on ``k_r``, the one rotary key of all heads; score ``(q_nope
  . k_nope + q_rope . k_rope) * s`` with ``s = (nope + rope)^-0.5 * m^2``,
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax; ``h' = h +
  concat(softmax . v) W_O``. The UNABSORBED form, for prompt and served
  tokens alike.
- ``y = RMSNorm(h')``. Dense layers: ``h'' = h' + (silu(y W_g) * (y W_u))
  W_d``. Expert layers: ``p = softmax(y W_r)`` over all ``router_width``
  experts; a group (``n_group`` groups of consecutive experts) scores as its
  best expert, the ``topk_group`` best groups stay, the
  ``num_experts_per_tok`` largest ``p`` among their experts are the token's
  experts with weights ``routed_scaling_factor * p`` (not renormalised);
  ``h'' = h' + SwiGLU_shared(y) + sum over the token's experts that are HELD
  of weight * SwiGLU_e(y)``.
- final RMSNorm, logits through the untied head.

Departures from the published model, each the configuration's (its
``reduced`` and ``deployment``): **the share** (of the routed experts only
``n_routed_experts`` from ``experts_held_first`` are held; what the others
would add is left out and the partial ``h''`` goes on, as in the program);
**the vocabulary slice** (embedding and head over ``vocab_size`` rows,
logits and argmax over the slice); **the depth** (``num_hidden_layers``).
Departures from "one big forward": the memory schedule only. A layer at a
time, attention in blocks of query rows, every held expert over every token
behind a mask (plain, and 27 times the needed work: this is the reference,
not the implementation).

Two sizes are not the generator's (the configuration's ``assumed``):
``router_init_scale`` multiplies the router's matrix and
``routed_down_init_scale`` the routed experts' down-projection, here and in
the adapter alike (``weights.init_scale``); :func:`routing_margin` counts the
near-ties that they are there to make harmless.

``mode`` computes every matrix product in a lower precision by rounding both
operands (``bfloat16``; ``float8``: e4m3 with one scale a tensor) before an
exact product; the router's product, which the configuration states in
float32, is then rounded to bfloat16. ``float32`` is the reference; the
others are the controls that the comparison has to fail. No cell trains this
family: ``train_step`` and ``delta_norms`` say so.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("float32", "bfloat16", "float8")
QUERY_BLOCK = 256       # query rows whose [heads, block, S] scores are alive


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


def yarn_inv_freq(cfg) -> np.ndarray:
    """``inv_freq = f / factor * (1 - mask) + f * mask``, ``f_j = theta^(-2j /
    dim)``, ``mask = 1 - clip((j - low) / (high - low), 0, 1)`` with ``low``,
    ``high`` the correction range of ``beta_fast`` and ``beta_slow``
    rotations over the original context."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    j = np.arange(dim // 2, dtype=np.float64)
    f = base ** (-2.0 * j / dim)
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((j - low) / (high - low), 0.0, 1.0)
    return (f / rs["factor"] * (1.0 - mask) + f * mask).astype(np.float32)


def mscale(cfg, key: str) -> float:
    rs = cfg["rope_scaling"]
    return 0.1 * rs[key] * math.log(rs["factor"]) + 1.0 \
        if rs["factor"] > 1 else 1.0


def rope(x, inv_freq, scale: float):
    """``x [S, ..., dim]`` at positions ``0..S-1``: the pairs (2j, 2j+1) of
    the projection's output become (j, j + dim/2), then the half rotation."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1) * scale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1) * scale
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


def attention(x, p, cfg, inv_freq, mode):
    s = x.shape[0]
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rd, dv, kvr = cfg["qk_rope_head_dim"], cfg["v_head_dim"], \
        cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    cs = mscale(cfg, "mscale") / mscale(cfg, "mscale_all_dim")
    c_q = rms_norm(_mm("sh,hr->sr", x, p["w_dq"], mode), p["q_norm_g"], eps)
    q = _mm("sr,rk->sk", c_q, p["w_uq"], mode).reshape(s, nh, nope + rd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv_freq, cs)],
                        axis=-1)
    ckv = _mm("sh,hr->sr", x, p["w_dkv"], mode)
    c_kv = rms_norm(ckv[:, :kvr], p["kv_norm_g"], eps)
    k_rope = rope(ckv[:, kvr:], inv_freq, cs)
    kv = _mm("sr,rk->sk", c_kv, p["w_ukv"], mode).reshape(s, nh, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (s, nh, rd))],
        axis=-1)
    v = kv[..., nope:]
    scale = (nope + rd) ** -0.5 * mscale(cfg, "mscale_all_dim") ** 2
    qb = math.gcd(s, QUERY_BLOCK)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        sc = _mm("qnd,knd->nqk", qi, k, mode) * scale
        rows = i * qb + jnp.arange(qb)
        sc = jnp.where(jnp.arange(s)[None, None, :] <= rows[None, :, None],
                       sc, -jnp.inf)
        return _mm("nqk,knd->qnd", jax.nn.softmax(sc, axis=-1), v, mode)

    o = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, nh * dv)
    return _mm("sk,kh->sh", o, p["w_o"], mode)


def swiglu(y, w_gate, w_up, w_down, mode):
    return _mm("sf,fh->sh", jax.nn.silu(_mm("sh,hf->sf", y, w_gate, mode))
               * _mm("sh,hf->sf", y, w_up, mode), w_down, mode)


def _kept_probs(y, w_router, cfg, mode):
    """``p [S, router_width]`` with the experts outside the token's
    ``topk_group`` best groups (a group scores as its best expert) at 0."""
    n_group, per = cfg["n_group"], W.router_width(cfg) // cfg["n_group"]
    p = jax.nn.softmax(_mm("sh,he->se", y, w_router,
                           "float32" if mode == "float32" else "bfloat16"),
                       axis=-1)
    best = jnp.max(p.reshape(-1, n_group, per), axis=-1)
    _, groups = jax.lax.top_k(best, cfg["topk_group"])
    keep = jnp.zeros_like(best).at[
        jnp.arange(best.shape[0])[:, None], groups].set(1.0)
    return p * jnp.repeat(keep, per, axis=1)


def routing(y, w_router, cfg, mode):
    """``(idx [S, k], weight [S, k])``: the token's experts among all
    ``router_width`` and their weights."""
    val, idx = jax.lax.top_k(_kept_probs(y, w_router, cfg, mode),
                             cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob") and cfg["num_experts_per_tok"] > 1:
        val = val / (jnp.sum(val, axis=-1, keepdims=True) + 1e-20)
    return idx, val * cfg["routed_scaling_factor"]


def routing_margin(y, w_router, cfg):
    """For each token, how far the last expert it keeps leads the first it
    leaves out, as a share of the kept one's probability: a margin under
    the program's rounding is a near-tie that can fall either way."""
    k = cfg["num_experts_per_tok"]
    val, _ = jax.lax.top_k(_kept_probs(y, w_router, cfg, "float32"), k + 1)
    return (val[:, k - 1] - val[:, k]) / val[:, k - 1]


def layer(x, p, cfg, inv_freq, mode, margins=False):
    """One decoder layer on x [S, hidden]; dense or expert by its leaves.
    With ``margins`` also each token's :func:`routing_margin` (None for a
    dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["ln1_g"], eps), p, cfg, inv_freq, mode)
    y = rms_norm(x, p["ln2_g"], eps)
    if "w_router" not in p:
        out = x + swiglu(y, p["w_gate"], p["w_up"], p["w_down"], mode)
        return (out, None) if margins else out
    w_router = p["w_router"] * W.init_scale(cfg, "w_router")
    down = W.init_scale(cfg, "we_down")
    idx, weight = routing(y, w_router, cfg, mode)
    out = swiglu(y, p["ws_gate"], p["ws_up"], p["ws_down"], mode)
    first = cfg.get("experts_held_first", 0)
    for e in range(cfg["n_routed_experts"]):
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(
            y, p["we_gate"][e], p["we_up"][e], p["we_down"][e] * down, mode)
    if margins:
        return x + out, routing_margin(y, w_router, cfg)
    return x + out


class Reference:
    """The reference bound to one configuration's sizes."""

    def __init__(self, cfg: Dict, mode: str = "float32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r}; one of {MODES}")
        self.cfg, self.mode = cfg, mode
        inv_freq = yarn_inv_freq(cfg)
        self._layer = jax.jit(functools.partial(
            layer, cfg=cfg, inv_freq=inv_freq, mode=mode))
        self._layer_margins = jax.jit(functools.partial(
            layer, cfg=cfg, inv_freq=inv_freq, mode=mode, margins=True))
        self._embed = jax.jit(lambda ids, table: table[ids])
        self._head = jax.jit(lambda x, pos, g, head: _mm(
            "th,hv->tv", rms_norm(x[pos], g, cfg["rms_norm_eps"]), head,
            mode))

    # -- training: no cell trains this family ------------------------------

    def init_state(self, weights):
        raise NotImplementedError(
            "the deepseek_v2 family has no training reference: its cells "
            "serve (16 bytes a parameter do not fit one chip at the floors)")

    def train_step(self, state, ids, labels, hp, rows=None):
        self.init_state(None)

    def delta_norms(self, state, weights0):
        self.init_state(None)

    # -- serving -----------------------------------------------------------

    def forward(self, p32, ids):
        """Hidden states [S, hidden] before the final norm."""
        x = self._embed(jnp.asarray(ids), p32["embed"])
        for lp in p32["layers"]:
            x = self._layer(x, lp)
        return x

    def near_tie_share(self, p32, ids, margin: float) -> float:
        """Share of (token, expert layer) whose :func:`routing_margin` is
        under ``margin``, over the tokens ``ids``."""
        x = self._embed(jnp.asarray(ids), p32["embed"])
        close = total = 0
        for lp in p32["layers"]:
            x, m = self._layer_margins(x, lp)
            if m is not None:
                close += int(jnp.sum(m < margin))
                total += int(m.size)
        return close / max(total, 1)

    def served_logits(self, p32, prompt, out_tokens, pad_to: int,
                      max_out: int):
        """Logits [max_out, V] of the full forward over ``prompt`` followed by
        its served tokens, at the positions that predicted each served token
        (row i predicted ``out_tokens[i]``; rows past the served count are
        padding). One compiled shape: ids padded to ``pad_to`` at the end,
        which a causal model's earlier positions cannot see."""
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out_tokens, np.int32)
        ids = np.zeros((pad_to,), np.int32)
        n = prompt.size + out.size - 1
        ids[:n] = np.concatenate([prompt, out[:-1]])
        pos = np.full((max_out,), prompt.size - 1, np.int32)
        pos[:out.size] = prompt.size - 1 + np.arange(out.size)
        return self._head(self.forward(p32, ids), jnp.asarray(pos),
                          p32["lnf_g"], p32["head"])
