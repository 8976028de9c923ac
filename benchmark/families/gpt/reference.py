"""Plain reference of the GPT-3 decoder (Brown et al. 2020, after Radford et
al. 2019): ``jax.numpy``, float32, ``highest`` matmul precision, no kernels,
no cache, no remat. It imports nothing of the program and takes nothing the
program has made; weights come from the shared generator
(``benchmark/lib/weights.py``) over this family's leaves (``weights.py``).

The model: token plus learned position embedding; ``num_layers`` pre-LN
blocks ``x += attn(ln_1(x)); x += mlp(ln_2(x))`` with causal softmax
attention over ``num_heads`` heads of ``head_dim`` and a 4x GELU (tanh form)
MLP; final layer norm; logits through the transposed token embedding (tied
head); mean token cross-entropy against the given labels. Optimizer: AdamW
with decoupled decay on every leaf, as the configuration's ``optimizer``
block states.

Departures from "one big autodiff": only the memory schedule. Rows of the
batch go through a layer one at a time, the backward pass walks the layers
in reverse with ``jax.vjp`` of one layer, and each leaf is updated as soon as
its gradient is whole, so that a 0.7B-parameter float32 state with dense
[S, S] attention fits beside nothing else on a 16 GB chip. The arithmetic is
that of the textbook forward, backward and update.

``mode`` computes every matrix product in a lower precision by rounding both
operands (``bfloat16``; ``float8``: e4m3 with one scale a tensor) before an
exact product. ``float32`` is the reference; the others are the controls that
the comparison has to fail.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import f32_weights, get_leaf

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("float32", "bfloat16", "float8")


def _round(x, mode: str):
    """``x`` rounded to ``mode`` and back; the backward pass sees the
    identity (a float8 cotangent would underflow to nothing, which is a
    property of this emulation and of no fp8 training recipe)."""
    if mode == "float32":
        return x
    if mode == "bfloat16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        low = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    else:
        raise ValueError(f"mode {mode!r}; one of {MODES}")
    return x + jax.lax.stop_gradient(low - x)


def _mm(spec: str, a, b, mode: str):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=HIGHEST)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_heads: int, eps: float, mode: str):
    """One pre-LN block on x [S, h] (one row of the batch)."""
    s, h = x.shape
    d = h // n_heads
    y = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = _mm("sh,hk->sk", y, p["w_qkv"], mode) + p["b_qkv"]
    qkv = qkv.reshape(s, 3, n_heads, d)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = _mm("qnd,knd->nqk", q, k, mode) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("nqk,knd->qnd", probs, v, mode).reshape(s, h)
    x = x + _mm("sh,hk->sk", o, p["w_o"], mode) + p["b_o"]
    y = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    up = gelu_tanh(_mm("sh,hf->sf", y, p["w_up"], mode) + p["b_up"])
    return x + _mm("sf,fh->sh", up, p["w_down"], mode) + p["b_down"]


def head_logits(x, wte, g, b, eps: float, mode: str):
    """Final layer norm and the tied head on x [T, h] -> [T, V]."""
    return _mm("th,vh->tv", layer_norm(x, g, b, eps), wte, mode)


def _row_loss(x, labels, wte, g, b, eps, mode):
    """Sum of token cross-entropies of one row."""
    logits = head_logits(x, wte, g, b, eps, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


class Reference:
    """The reference bound to one configuration's sizes."""

    def __init__(self, cfg: Dict, mode: str = "float32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r}; one of {MODES}")
        self.cfg, self.mode = cfg, mode
        self.n_heads = cfg["num_heads"]
        self.eps = cfg["layer_norm_epsilon"]
        blk = functools.partial(block, n_heads=self.n_heads, eps=self.eps,
                                mode=mode)
        self._block = blk
        # rows of the batch go through a layer one after another (lax.map),
        # so only one row's [heads, S, S] scores are alive at a time
        self._layer_fwd = jax.jit(
            lambda x, p: jax.lax.map(lambda r: blk(r, p), x))
        self._layer_bwd = jax.jit(self._layer_bwd_impl)
        self._head_bwd = jax.jit(self._head_bwd_impl)
        self._embed = jax.jit(
            lambda ids, wte, wpe: wte[ids] + wpe[None, :ids.shape[1]])
        self._embed_bwd = jax.jit(self._embed_bwd_impl, donate_argnums=(2,))
        self._adamw = jax.jit(self._adamw_impl, static_argnums=(5,),
                              donate_argnums=(0, 2, 3))
        self._norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)))))
        self._diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))))
        self._block_jit = jax.jit(blk)
        self._embed_row = jax.jit(
            lambda ids, wte, wpe: wte[ids] + wpe[:ids.shape[0]])
        self._head = jax.jit(lambda x, pos, wte, g, b: head_logits(
            x[pos], wte, g, b, self.eps, mode))

    # -- training ----------------------------------------------------------

    def _layer_bwd_impl(self, x, p, dy):
        def one(acc, row):
            xr, dyr = row
            _, vjp = jax.vjp(self._block, xr, p)
            dx, dp = vjp(dyr)
            return jax.tree_util.tree_map(jnp.add, acc, dp), dx
        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        dp, dx = jax.lax.scan(one, zero, (x, dy))
        return dx, dp

    def _head_bwd_impl(self, x, labels, wte, g, b):
        n_tok = labels.size

        def one(acc, row):
            xr, lr = row
            loss, grads = jax.value_and_grad(
                lambda xr_, wte_, g_, b_: _row_loss(
                    xr_, lr, wte_, g_, b_, self.eps, self.mode) / n_tok,
                argnums=(0, 1, 2, 3))(xr, wte, g, b)
            dx, dw, dg, db = grads
            return (acc[0] + loss, acc[1] + dw, acc[2] + dg, acc[3] + db), dx
        zero = (jnp.zeros((), jnp.float32), jnp.zeros_like(wte),
                jnp.zeros_like(g), jnp.zeros_like(b))
        (loss, dw, dg, db), dx = jax.lax.scan(one, zero, (x, labels))
        return loss, dx, dw, dg, db

    @staticmethod
    def _embed_bwd_impl(ids, dx0, dwte):
        dwte = dwte.at[ids.reshape(-1)].add(dx0.reshape(-1, dx0.shape[-1]))
        return dwte, jnp.sum(dx0, axis=0)

    @staticmethod
    def _adamw_impl(p, g, m, v, step, hp):
        lr, b1, b2, eps, wd = hp
        p = p * (1.0 - lr * wd)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        t = step.astype(jnp.float32)
        m_hat = m / (1.0 - b1 ** t)
        v_hat = v / (1.0 - b2 ** t)
        return p - lr * m_hat / (jnp.sqrt(v_hat) + eps), m, v

    def init_state(self, weights):
        """float32 parameters (from the bf16 values both sides start from)
        and zero moments."""
        p = f32_weights(weights)
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        return {"p": p, "m": zeros(p), "v": zeros(p), "step": 0}

    def train_step(self, state, ids, labels, hp: Sequence[float],
                   rows: Optional[slice] = None):
        """One AdamW step in place; returns (loss, {leaf: ||grad||}).

        ``hp``: (lr, beta1, beta2, epsilon, weight_decay). ``rows`` plants the
        fault "part of the batch left out, the mean taken over the rest"."""
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        p = state["p"]
        state["step"] += 1
        step = jnp.asarray(state["step"], jnp.int32)
        hp = tuple(float(x) for x in hp)
        gnorm: Dict[str, float] = {}

        def update(name, layer, key, g):
            """AdamW on one leaf as soon as its gradient is whole."""
            for part, a in W.compared_parts(name, g).items():
                gnorm[part] = self._norm(a)
            hold = [state[k] if layer is None else state[k]["layers"][layer]
                    for k in ("p", "m", "v")]
            new = self._adamw(hold[0][key], g, hold[1][key], hold[2][key],
                              step, hp)
            for h_, n_ in zip(hold, new):
                h_[key] = n_

        xs = [self._embed(ids, p["wte"], p["wpe"])]
        for lp in p["layers"]:
            xs.append(self._layer_fwd(xs[-1], lp))
        loss, dx, dwte, dg, db = self._head_bwd(
            xs.pop(), labels, p["wte"], p["lnf_g"], p["lnf_b"])
        update("lnf_g", None, "lnf_g", dg)
        update("lnf_b", None, "lnf_b", db)
        for i in reversed(range(len(p["layers"]))):
            dx, dp = self._layer_bwd(xs.pop(), p["layers"][i], dx)
            for k in W.LAYER_LEAVES:
                update(f"layers.{i}.{k}", i, k, dp[k])
            del dp
        dwte, dwpe_used = self._embed_bwd(ids, dx, dwte)
        dwpe = jnp.zeros_like(p["wpe"]).at[:dwpe_used.shape[0]].set(dwpe_used)
        update("wte", None, "wte", dwte)
        update("wpe", None, "wpe", dwpe)
        return float(loss), {k: float(v) for k, v in gnorm.items()}

    def delta_norms(self, state, weights0) -> Dict[str, float]:
        """||p - p0|| of every leaf against the starting weights."""
        out = {}
        for name in W.leaf_names(self.cfg):
            now = W.compared_parts(name, get_leaf(state["p"], name))
            was = W.compared_parts(name, get_leaf(weights0, name))
            for part in now:
                out[part] = float(self._diff_norm(now[part], was[part]))
        return out

    # -- serving -----------------------------------------------------------

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
        x = self._embed_row(jnp.asarray(ids), p32["wte"], p32["wpe"])
        for lp in p32["layers"]:
            x = self._block_jit(x, lp)
        return self._head(x, jnp.asarray(pos), p32["wte"], p32["lnf_g"],
                          p32["lnf_b"])
