"""The gated delta rule: the recurrence of a linear-attention layer whose state
is a matrix a head (Yang et al., "Gated Delta Networks", arXiv:2412.06464; the
form of ``transformers``' ``Qwen3NextGatedDeltaNet``).

A head keeps ``S`` of ``[d_k, d_v]``. At each token, with ``q`` and ``k``
L2-normalised (``q`` then scaled by ``1 / sqrt(d_k)``), a decay ``g <= 0`` and
a write strength ``beta``::

    S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - exp(g_t) S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

Everything here is float32. Three forms of the one recurrence:

- :func:`gated_delta_step`, one token a row, the state in the serving pool's
  layout ``[d_k, H * d_v]`` (head ``h`` is the columns ``h * d_v ..``): a
  layout the chip stores without padding an axis (``[H, d_k, d_v]`` would pad
  ``d_v`` 192 to 256 lanes);
- :func:`chunk_gated_delta`, a whole prompt, in chunks of 64 tokens: within a
  chunk the recurrence is matrix products (the WY form, a unit lower
  triangular inverse by forward substitution), across chunks a scan of the
  state;
- :func:`gated_delta_decode`, the serving decode step over a pool of states
  read and written by slot: on a TPU the Pallas kernel
  ``_pallas/gated_delta_decode.py`` (each row's state fetched by DMA and
  written back in place), elsewhere :func:`gated_delta_step` between a gather
  and a scatter, which is also what the kernel is checked against.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import flags

__all__ = ["l2_normalize", "gated_delta_step", "chunk_gated_delta",
           "gated_delta_decode", "takes_state_kernel", "causal_conv",
           "conv_step", "CHUNK"]

HI = lax.Precision.HIGHEST
CHUNK = 64          # tokens a chunk of the prefill form


def l2_normalize(x, eps: float = 1e-6):
    """``x * rsqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """Causal depthwise convolution without bias: ``x [B, S, C]`` (zeros before
    position 0), ``w [K, C]`` (tap ``K - 1`` on the current token) -> ``silu``
    of it, float32 ``[B, S, C]``."""
    k = w.shape[0]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    out = sum(xf[:, j:j + s] * w[j].astype(jnp.float32) for j in range(k))
    return jax.nn.silu(out)


def conv_step(x, tail, w):
    """One token of :func:`causal_conv`: ``x [B, C]``, ``tail [B, K - 1, C]``
    (the inputs of the ``K - 1`` tokens before it) -> ``(silu(conv) [B, C]
    float32, the new tail [B, K - 1, C])``."""
    win = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    out = jnp.sum(win.astype(jnp.float32) * w.astype(jnp.float32), axis=1)
    return jax.nn.silu(out), win[:, 1:]


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row: ``q, k [B, H, d_k]``, ``v [B, H, d_v]``, ``g, beta
    [B, H]``, ``state [B, d_k, H * d_v]`` -> ``(o [B, H, d_v], state)``.
    Elementwise products and sums in float32 (no matrix unit), as the kernel
    computes them."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    s = state.astype(jnp.float32).reshape(b, dk, h, dv)
    kk = jnp.swapaxes(k, 1, 2)[..., None]               # [B, d_k, H, 1]
    qq = jnp.swapaxes(q, 1, 2)[..., None]
    s = s * jnp.exp(g)[:, None, :, None]
    kv = jnp.sum(s * kk, axis=1)                        # [B, H, d_v]
    delta = beta[..., None] * (v - kv)
    s = s + kk * delta[:, None]
    o = jnp.sum(s * qq, axis=1)
    return o, s.reshape(b, dk, h * dv)


def _unit_lower_inverse(low):
    """``(I + low)^-1`` for ``low [..., C, C]`` strictly lower triangular, by
    forward substitution: row ``i`` is ``e_i - low[i] @ rows before it``."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)

    def row(i, t):
        r = eye[i] - jnp.einsum("...j,...jk->...k", low[..., i, :], t,
                                precision=HI)
        return lax.dynamic_update_index_in_dim(t, r, i, axis=-2)
    return lax.fori_loop(0, c, row, jnp.zeros_like(low))


def chunk_gated_delta(q, k, v, g, beta, chunk: int = CHUNK):
    """A whole sequence from a zero state: ``q, k [B, S, H, d_k]``, ``v [B, S,
    H, d_v]``, ``g, beta [B, S, H]`` -> ``(o [B, S, H, d_v], final state [B,
    d_k, H * d_v])``. A position with ``g = beta = 0`` leaves the state as it
    is (padding past a prompt's end). Products at ``highest`` precision."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    if pad:
        def p(x):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = map(p, (q, k, v, g, beta))
    n = (s + pad) // chunk

    def split(x):                 # [B, S, H, d] -> [n, B, H, C, d]
        return x.reshape(b, n, chunk, h, -1).transpose(1, 0, 3, 2, 4)
    qc, kc, vc = split(q), split(k), split(v)
    gc = g.reshape(b, n, chunk, h).transpose(1, 0, 3, 2)       # [n, B, H, C]
    bc = beta.reshape(b, n, chunk, h).transpose(1, 0, 3, 2)
    gam = jnp.cumsum(gc, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = gam[..., :, None] - gam[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = kc * bc[..., None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    low = jnp.where(strict, jnp.einsum("...id,...jd->...ij", kb, kc,
                                       precision=HI) * decay, 0.0)
    t = _unit_lower_inverse(low)
    u = jnp.einsum("...ij,...je->...ie", t, vc * bc[..., None], precision=HI)
    w = jnp.einsum("...ij,...jd->...id", t, kb * jnp.exp(gam)[..., None],
                   precision=HI)
    att = jnp.einsum("...id,...jd->...ij", qc, kc, precision=HI) * decay
    qg = qc * jnp.exp(gam)[..., None]
    last = gam[..., -1:]
    kd = kc * jnp.exp(last - gam)[..., None]

    def body(st, xs):             # st [B, H, d_k, d_v]
        qg_i, kd_i, u_i, w_i, att_i, last_i = xs
        v_new = u_i - jnp.einsum("bhcd,bhde->bhce", w_i, st, precision=HI)
        o = jnp.einsum("bhcd,bhde->bhce", qg_i, st, precision=HI) \
            + jnp.einsum("bhij,bhje->bhie", att_i, v_new, precision=HI)
        st = st * jnp.exp(last_i)[..., None] + jnp.einsum(
            "bhcd,bhce->bhde", kd_i, v_new, precision=HI)
        return st, o

    st, o = lax.scan(body, jnp.zeros((b, h, dk, dv), jnp.float32),
                     (qg, kd, u, w, att, last))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)[:, :s]
    return o, st.transpose(0, 2, 1, 3).reshape(b, dk, h * dv)


def _platform_of(x) -> str:
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    return jax.default_backend()


def takes_state_kernel(pool, heads: Optional[int] = None) -> bool:
    """Does the decode step over ``pool`` (``[layers, slots, d_k, H * d_v]``)
    of ``heads`` heads (None: any the kernel takes) take the Pallas kernel?
    On a TPU with the flag on and a shape the kernel takes; an unsupported
    shape ON a TPU is announced once (P005). The serving engine asks too, to
    count what its decode program reads."""
    if not flags.flag("use_pallas_kernels") or _platform_of(pool) != "tpu":
        return False
    from ._pallas.gated_delta_decode import supported_shapes
    if supported_shapes(pool, heads):
        return True
    from ..analysis.pallas_check import report_fallback
    report_fallback(
        "gated_delta_decode", f"pool{tuple(pool.shape)} {pool.dtype}",
        "needs a float32 pool, d_k a multiple of 8, H * d_v a multiple of "
        "128 and at most 128 heads")
    return False


def gated_delta_decode(q, k, v, g, beta, pool, slots, *, layer=0
                       ) -> Tuple[jax.Array, jax.Array]:
    """The decode step of one layer over a pool of states: ``q, k [B, H,
    d_k]``, ``v [B, H, d_v]``, ``g, beta [B, H]`` (float32), ``pool [L,
    slots, d_k, H * d_v]`` float32, ``slots [B]`` (0: a pad row, whose state
    is neither read nor kept) -> ``(o [B, H, d_v], pool)`` with each row's
    state advanced by its token, in its own slot of ``layer``."""
    if takes_state_kernel(pool, k.shape[1]):
        from ._pallas.gated_delta_decode import gated_delta_decode_pallas
        return gated_delta_decode_pallas(q, k, v, g, beta, pool, slots,
                                         layer=layer)
    o, new = gated_delta_step(q, k, v, g, beta, pool[layer, slots])
    real = (slots > 0)[:, None, None]
    return (jnp.where(real, o, 0.0),
            pool.at[layer, slots].set(jnp.where(real, new, 0.0)))
