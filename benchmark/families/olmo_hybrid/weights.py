"""The leaves of the Olmo-Hybrid decoder as one chip holds them, under neutral
names that ``adapter.py`` maps to the program's. The values come from the
shared generator (``benchmark/lib/weights.py``).

Layout: ``{"embed", "head", "lnf_g", "layers": [...]}``, layer ``i`` of the
kind ``layer_types[i]``. Both kinds have ``post_mix_g`` (the norm after the
mixer), the SwiGLU ``w_gate, w_up [h, f]``, ``w_down [f, h]`` and
``post_ff_g``. A full layer has ``w_q, w_k, w_v [h, heads * d]``, ``q_norm_g,
k_norm_g [heads * d]`` (the norms over the whole projection) and ``w_o``. A
linear layer has ``w_q, w_k [h, H * d_k]``, ``w_v, w_z [h, H * d_v]``, ``w_a,
w_b [h, H]``, the convolution ``conv_w [K, 2 H d_k + H d_v]`` (tap ``K - 1``
on the current token; its channels are ``[q; k; v]``), ``a_log, dt_bias
[H]``, ``o_norm_g [d_v]`` (one gain for all heads) and ``w_o [H * d_v, h]``.
Linear weights are [in, out]; no biases.

**The decay offset.** With the generator's values (``a_log`` and ``dt_bias``
within 0.062 of 0) every head would decay as ``exp(-softplus(x w_a))``, about
a half a token: the state would forget in two tokens. The configuration's
``a_log_offset`` (``[first, last]``) is added to head ``h``'s ``a_log``:
evenly spaced from the first head's to the last's, each a multiple of 2**-5,
so that the sum is exact in float32 and the adapter and the reference, which
both add it, hold the same value.
"""

from __future__ import annotations

import numpy as np

LINEAR = "linear_attention"
FULL = "full_attention"


def kinds(cfg):
    """The kinds of the configuration's layers, in order."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def layer_shapes(cfg, kind: str):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    common = {"post_mix_g": (h,), "w_gate": (h, f), "w_up": (h, f),
              "w_down": (f, h), "post_ff_g": (h,)}
    if kind == FULL:
        hd = cfg["num_attention_heads"] * (h // cfg["num_attention_heads"])
        kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
        return {"w_q": (h, hd), "w_k": (h, kv), "w_v": (h, kv),
                "q_norm_g": (hd,), "k_norm_g": (kv,), "w_o": (hd, h),
                **common}
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    conv = 2 * nk * dk + nv * dv
    return {"w_q": (h, nk * dk), "w_k": (h, nk * dk), "w_v": (h, nv * dv),
            "w_z": (h, nv * dv), "w_a": (h, nv), "w_b": (h, nv),
            "conv_w": (cfg["linear_conv_kernel_dim"], conv),
            "a_log": (nv,), "dt_bias": (nv,), "o_norm_g": (dv,),
            "w_o": (nv * dv, h), **common}


def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, h), "head": (h, v), "lnf_g": (h,),
            "layers": [layer_shapes(cfg, k) for k in kinds(cfg)]}


def leaf_names(cfg):
    """Every leaf's name, e.g. ``layers.3.w_up``."""
    names = ["embed", "head", "lnf_g"]
    for i, kind in enumerate(kinds(cfg)):
        names += [f"layers.{i}.{k}" for k in layer_shapes(cfg, kind)]
    return names


def is_gain(name: str) -> bool:
    """The RMSNorm gains, which start near 1 and not near 0."""
    return name.endswith("_g")


def compared_parts(name: str, array):
    """No fused leaf is split: every leaf is compared whole."""
    return {name: array}


def a_log_offset(cfg) -> np.ndarray:
    """What is added to each head's ``a_log`` (float32 ``[H]``)."""
    first, last = cfg["a_log_offset"]
    n = cfg["linear_num_value_heads"]
    return np.round(np.linspace(first, last, n) * 32.0).astype(
        np.float32) / np.float32(32.0)
