"""The leaves of the DeepSeek-V2 decoder as one chip holds them, under
neutral names that ``adapter.py`` maps to the program's. The values come from
the shared generator (``benchmark/lib/weights.py``).

Layout: ``{"embed", "head", "lnf_g", "layers": [...]}``. Every layer has the
MLA leaves ``ln1_g, w_dq, q_norm_g, w_uq, w_dkv, kv_norm_g, w_ukv, w_o,
ln2_g``; the first ``first_k_dense_replace`` layers add the dense SwiGLU
``w_gate, w_up, w_down``, the others the router ``w_router`` (all
``router_width`` outputs), the shared experts as one SwiGLU ``ws_gate, ws_up,
ws_down`` and the experts HELD here stacked: ``we_gate, we_up [E, h, f]``,
``we_down [E, f, h]`` (``E = n_routed_experts``, expert ``e`` of the stack is
expert ``experts_held_first + e`` of the router). Linear weights are [in,
out]. ``w_uq`` columns are (head, [nope | rope]); ``w_dkv`` columns are
``[c_kv | k_rope]``; ``w_ukv`` columns are (head, [k_nope | v]). No biases.
"""

from __future__ import annotations

ATTN_LEAVES = ("ln1_g", "w_dq", "q_norm_g", "w_uq", "w_dkv", "kv_norm_g",
               "w_ukv", "w_o", "ln2_g")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("w_router", "ws_gate", "ws_up", "ws_down",
              "we_gate", "we_up", "we_down")


def router_width(cfg) -> int:
    """Experts the router scores: the published count, whatever is held."""
    return cfg.get("router_width", cfg["n_routed_experts"])


def is_moe_layer(cfg, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def layer_leaves(cfg, i: int):
    return ATTN_LEAVES + (MOE_LEAVES if is_moe_layer(cfg, i)
                          else DENSE_LEAVES)


def layer_shapes(cfg, i: int):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, kvr, qr = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    out = {"ln1_g": (h,), "w_dq": (h, qr), "q_norm_g": (qr,),
           "w_uq": (qr, nh * (nope + rope)), "w_dkv": (h, kvr + rope),
           "kv_norm_g": (kvr,), "w_ukv": (kvr, nh * (nope + dv)),
           "w_o": (nh * dv, h), "ln2_g": (h,)}
    if is_moe_layer(cfg, i):
        f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        fs = f * cfg["n_shared_experts"]
        out.update(w_router=(h, router_width(cfg)), ws_gate=(h, fs),
                   ws_up=(h, fs), ws_down=(fs, h), we_gate=(e, h, f),
                   we_up=(e, h, f), we_down=(e, f, h))
    else:
        f = cfg["intermediate_size"]
        out.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    return out


def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, h), "head": (h, v), "lnf_g": (h,),
            "layers": [layer_shapes(cfg, i)
                       for i in range(cfg["num_hidden_layers"])]}


def leaf_names(cfg):
    """Every leaf's name, e.g. ``layers.3.we_up``."""
    names = ["embed", "head", "lnf_g"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layers.{i}.{k}" for k in layer_leaves(cfg, i)]
    return names


_INIT_SCALE_KEYS = {"w_router": "router_init_scale",
                    "we_down": "routed_down_init_scale"}


def init_scale(cfg, name: str) -> float:
    """What the configuration's ``assumed`` multiplies the generator's draw
    of a leaf by (a power of two, so exact in bfloat16): the adapter and the
    reference both apply it, to the same values."""
    key = _INIT_SCALE_KEYS.get(name.rsplit(".", 1)[-1])
    return cfg.get(key, 1.0) if key else 1.0


def is_gain(name: str) -> bool:
    """The RMSNorm gains, which start near 1 and not near 0."""
    return name.endswith("_g")


def compared_parts(name: str, array):
    """No fused leaf is split: every leaf is compared whole."""
    return {name: array}
