"""Plain reference of the Olmo-Hybrid decoder (AllenAI, Olmo-Hybrid-7B; the
gated delta rule as ``transformers``' ``Qwen3NextGatedDeltaNet`` and
``torch_recurrent_gated_delta_rule`` give it, the block as ``Olmo3DecoderLayer``
does): ``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no
cache. It imports nothing of the program and takes nothing the program has
made; weights come from the shared generator (``benchmark/lib/weights.py``)
over this family's leaves (``weights.py``).

**The block**, on ``x [S, hidden]`` (eps ``rms_norm_eps``, no biases, no
norm before the mixer): ``h = x + RMSNorm(mixer(x)) * g_mix``; ``out = h +
RMSNorm((silu(h W_gate) * (h W_up)) W_down) * g_ff``. Final RMSNorm, logits
through the untied head; the logit at position ``i`` predicts token ``i + 1``.

**A full layer's mixer**: ``q = RMSNorm(x W_q) * g_q``, ``k = RMSNorm(x W_k) *
g_k`` (over the whole projection), ``v = x W_v``, each ``[S, heads, d]``; no
rotation (NoPE); causal softmax of ``q . k / sqrt(d)``; ``concat(P v) W_o``.

**A linear layer's mixer**, token by token (the RECURRENT form, independent
of the program's chunked prefill): ``c = [x W_q; x W_k; x W_v]``; ``c'_t =
silu(sum_j w_j c_{t-K+1+j})`` (zeros before position 0); ``q, k, v`` split
from ``c'`` into heads; ``q, k`` L2-normalised (``x rsqrt(sum x^2 +
1e-6)``), ``q`` times ``1 / sqrt(d_k)``; ``beta = 2 sigmoid(x W_b)``; ``g =
-exp(a_log + offset) softplus(x W_a + dt_bias)``; a head's state ``S [d_k,
d_v]`` from 0: ``S = exp(g) S``; ``S = S + k (beta (v - S^T k))^T``; ``o =
S^T q``; ``y = (RMSNorm_dv(o) * g_o * silu(x W_z)) W_o``, the norm over each
head's ``d_v`` values.

Departures from one big forward: the memory schedule only (a layer at a
time, the full layer's attention in blocks of query rows). Departures from
the published model, each the configuration's: the depth (one period) and the
decay offset (``weights.a_log_offset``; the adapter adds the same).

``mode`` computes every matrix product (and the convolution's) in a lower
precision by rounding both operands (``bfloat16``; ``float8``: e4m3 with one
scale a tensor) before an exact product; the recurrence, which the
configuration states in float32, is then rounded to bfloat16. ``float32`` is
the reference; the others are the controls that the comparison has to fail.
No cell trains this family: ``train_step`` and ``delta_norms`` say so.
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


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def full_mixer(x, p, cfg, mode):
    s = x.shape[0]
    nh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["hidden_size"] // nh, cfg["rms_norm_eps"]
    q = rms_norm(_mm("sh,hk->sk", x, p["w_q"], mode), p["q_norm_g"], eps)
    k = rms_norm(_mm("sh,hk->sk", x, p["w_k"], mode), p["k_norm_g"], eps)
    v = _mm("sh,hk->sk", x, p["w_v"], mode).reshape(s, kh, d)
    q = q.reshape(s, kh, nh // kh, d)
    k = k.reshape(s, kh, d)
    qb = math.gcd(s, QUERY_BLOCK)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        sc = _mm("qkgd,skd->kgqs", qi, k, mode) / math.sqrt(d)
        mine = (i * qb + jnp.arange(qb))[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= mine, sc, -jnp.inf)
        return _mm("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v, mode)

    o = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, nh * d)
    return _mm("sk,kh->sh", o, p["w_o"], mode)


def linear_mixer(x, p, cfg, mode):
    s = x.shape[0]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    c = jnp.concatenate([_mm("sh,hk->sk", x, p[n], mode)
                         for n in ("w_q", "w_k", "w_v")], axis=-1)
    cp = jnp.pad(_round(c, mode), ((taps - 1, 0), (0, 0)))
    w = _round(p["conv_w"], mode)
    c = jax.nn.silu(sum(cp[j:j + s] * w[j] for j in range(taps)))
    q = l2norm(c[:, :nk * dk].reshape(s, nk, dk)) / math.sqrt(dk)
    k = l2norm(c[:, nk * dk:2 * nk * dk].reshape(s, nk, dk))
    v = c[:, 2 * nk * dk:].reshape(s, nv, dv)
    beta = jax.nn.sigmoid(_mm("sh,hk->sk", x, p["w_b"], mode))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    a_log = p["a_log"] + jnp.asarray(W.a_log_offset(cfg))
    g = -jnp.exp(a_log) * jax.nn.softplus(
        _mm("sh,hk->sk", x, p["w_a"], mode) + p["dt_bias"])
    sm = "float32" if mode == "float32" else "bfloat16"

    def token(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        st = st * jnp.exp(g_t)[:, None, None]
        kv = _mm("hde,hd->he", st, k_t, sm)
        st = st + _mm("hd,he->hde", k_t, b_t[:, None] * (v_t - kv), sm)
        return st, _mm("hde,hd->he", st, q_t, sm)

    _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    z = _mm("sh,hk->sk", x, p["w_z"], mode).reshape(s, nv, dv)
    y = rms_norm(o, p["o_norm_g"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return _mm("sk,kh->sh", y.reshape(s, nv * dv), p["w_o"], mode)


def layer(x, p, cfg, mode, kind):
    """One decoder layer of ``kind`` on ``x [S, hidden]``."""
    eps = cfg["rms_norm_eps"]
    mixer = full_mixer if kind == W.FULL else linear_mixer
    h = x + rms_norm(mixer(x, p, cfg, mode), p["post_mix_g"], eps)
    mlp = _mm("sf,fh->sh", jax.nn.silu(_mm("sh,hf->sf", h, p["w_gate"], mode))
              * _mm("sh,hf->sf", h, p["w_up"], mode), p["w_down"], mode)
    return h + rms_norm(mlp, p["post_ff_g"], eps)


class Reference:
    """The reference bound to one configuration's sizes."""

    def __init__(self, cfg: Dict, mode: str = "float32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r}; one of {MODES}")
        self.cfg, self.mode = cfg, mode
        self._layers = {
            kind: jax.jit(functools.partial(layer, cfg=cfg, mode=mode,
                                            kind=kind))
            for kind in (W.LINEAR, W.FULL)}
        self._embed = jax.jit(lambda ids, table: table[ids])
        self._head = jax.jit(lambda x, pos, g, head: _mm(
            "th,hv->tv", rms_norm(x[pos], g, cfg["rms_norm_eps"]), head,
            mode))

    # -- training: no cell trains this family ------------------------------

    def init_state(self, weights):
        raise NotImplementedError(
            "the olmo_hybrid family has no training reference: its cell "
            "serves (16 bytes a parameter of one period do not fit one chip)")

    def train_step(self, state, ids, labels, hp, rows=None):
        self.init_state(None)

    def delta_norms(self, state, weights0):
        self.init_state(None)

    # -- serving -----------------------------------------------------------

    def forward(self, p32, ids):
        """Hidden states [S, hidden] before the final norm."""
        x = self._embed(jnp.asarray(ids), p32["embed"])
        for kind, lp in zip(W.kinds(self.cfg), p32["layers"]):
            x = self._layers[kind](x, lp)
        return x

    def logits(self, p32, ids):
        """Logits ``[S, V]`` at every position of ``ids``."""
        return self._head(self.forward(p32, ids), jnp.arange(len(ids)),
                          p32["lnf_g"], p32["head"])

    def served_logits(self, p32, prompt, out_tokens, pad_to: int,
                      max_out: int):
        """Logits [max_out, V] of the full forward over ``prompt`` followed by
        its served tokens, at the positions that predicted each served token
        (row i predicted ``out_tokens[i]``; rows past the served count are
        padding). One compiled shape: ids padded to ``pad_to`` at the end,
        which no earlier position sees (causal attention, a recurrence)."""
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out_tokens, np.int32)
        ids = np.zeros((pad_to,), np.int32)
        n = prompt.size + out.size - 1
        ids[:n] = np.concatenate([prompt, out[:-1]])
        pos = np.full((max_out,), prompt.size - 1, np.int32)
        pos[:out.size] = prompt.size - 1 + np.arange(out.size)
        return self._head(self.forward(p32, ids), jnp.asarray(pos),
                          p32["lnf_g"], p32["head"])
