"""Plain reference of the grouped-query decoder: ``jax.numpy``, float32, one
autodiff over the whole batch (a fixture runs at sizes where that fits). It
imports nothing of the program."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import f32_weights, get_leaf

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("float32",)


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, cfg):
    """One pre-LN block on x [S, h]: query head i reads KV head
    ``i // (heads / kv_heads)``."""
    s, h = x.shape
    n, kv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    y = _norm(x, p["ln1_scale"], p["ln1_shift"], cfg["layer_norm_epsilon"])
    q = (_mm("sh,hk->sk", y, p["w_q"]) + p["b_q"]).reshape(s, kv, n // kv, d)
    k, v = jnp.moveaxis((_mm("sh,hk->sk", y, p["w_kv"]) + p["b_kv"])
                        .reshape(s, 2, kv, d), 1, 0)
    scores = _mm("qgrd,kgd->grqk", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = _mm("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _mm("sh,hk->sk", o.reshape(s, h), p["w_o"]) + p["b_o"]
    y = _norm(x, p["ln2_scale"], p["ln2_shift"], cfg["layer_norm_epsilon"])
    up = _gelu(_mm("sh,hf->sf", y, p["w_up"]) + p["b_up"])
    return x + _mm("sf,fh->sh", up, p["w_down"]) + p["b_down"]


def _logits(p, ids, cfg):
    """ids [S] -> [S, V]."""
    x = p["wte"][ids] + p["wpe"][:ids.shape[0]]
    for lp in p["layers"]:
        x = _block(x, lp, cfg)
    x = _norm(x, p["lnf_scale"], p["lnf_shift"], cfg["layer_norm_epsilon"])
    return _mm("th,vh->tv", x, p["wte"])


def _loss(p, ids, labels, cfg):
    logits = jax.vmap(lambda row: _logits(p, row, cfg))(ids)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def _parts_norms(cfg, leaf_of):
    out = {}
    for name in W.leaf_names(cfg):
        for part, a in W.compared_parts(name, leaf_of(name)).items():
            out[part] = float(jnp.sqrt(jnp.sum(jnp.square(a))))
    return out


class Reference:
    def __init__(self, cfg, mode: str = "float32"):
        if mode not in MODES:
            raise ValueError(f"mode {mode!r}; one of {MODES}")
        self.cfg = cfg
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, ids, labels: _loss(p, ids, labels, cfg)))
        self._fwd = jax.jit(lambda p, ids: _logits(p, ids, cfg))

    def init_state(self, weights):
        p = f32_weights(weights)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
        return {"p": p, "m": zeros, "v": zeros, "step": 0}

    def train_step(self, state, ids, labels, hp, rows=None):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        lr, b1, b2, eps, wd = (float(x) for x in hp)
        loss, g = self._grad(state["p"], ids, labels)
        state["step"] += 1
        t = state["step"]
        tm = jax.tree_util.tree_map
        state["m"] = tm(lambda m, g_: b1 * m + (1 - b1) * g_, state["m"], g)
        state["v"] = tm(lambda v, g_: b2 * v + (1 - b2) * g_ * g_,
                        state["v"], g)
        state["p"] = tm(
            lambda p, m, v: p * (1 - lr * wd) - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps),
            state["p"], state["m"], state["v"])
        return float(loss), _parts_norms(self.cfg, lambda n: get_leaf(g, n))

    def delta_norms(self, state, weights0):
        w0 = f32_weights(weights0)
        return _parts_norms(self.cfg, lambda n: get_leaf(state["p"], n)
                            - get_leaf(w0, n))

    def served_logits(self, p32, prompt, out_tokens, pad_to, max_out):
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out_tokens, np.int32)
        ids = np.zeros((pad_to,), np.int32)
        ids[:prompt.size + out.size - 1] = np.concatenate([prompt, out[:-1]])
        pos = np.full((max_out,), prompt.size - 1, np.int32)
        pos[:out.size] = prompt.size - 1 + np.arange(out.size)
        return self._fwd(p32, jnp.asarray(ids))[jnp.asarray(pos)]
