"""The leaves of the SDAR-MoE decoder as one chip holds them, under neutral
names that ``adapter.py`` maps to the program's. The values come from the
shared generator (``benchmark/lib/weights.py``).

Layout: ``{"embed", "head", "lnf_g", "layers": [...]}``. Every layer has the
attention leaves ``ln1_g, w_q, w_k, w_v, q_norm_g, k_norm_g, w_o, ln2_g``,
the router ``w_router`` (all ``router_width`` outputs) and the experts HELD
here stacked: ``we_gate, we_up [E, h, f]``, ``we_down [E, f, h]`` (``E =
num_experts``, expert ``e`` of the stack is expert ``experts_held_first + e``
of the router). Linear weights are [in, out]; ``w_q`` columns are (head,
head_dim), ``w_k`` and ``w_v`` columns (kv head, head_dim). ``q_norm_g`` and
``k_norm_g`` are one gain vector of ``head_dim`` for all heads. No biases.
"""

from __future__ import annotations

LAYER_LEAVES = ("ln1_g", "w_q", "w_k", "w_v", "q_norm_g", "k_norm_g", "w_o",
                "ln2_g", "w_router", "we_gate", "we_up", "we_down")


def router_width(cfg) -> int:
    """Experts the router scores: the published count, whatever is held."""
    return cfg.get("router_width", cfg["num_experts"])


def layer_shapes(cfg):
    h, nh, kh = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    d, f, e = cfg["head_dim"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    return {"ln1_g": (h,), "w_q": (h, nh * d), "w_k": (h, kh * d),
            "w_v": (h, kh * d), "q_norm_g": (d,), "k_norm_g": (d,),
            "w_o": (nh * d, h), "ln2_g": (h,),
            "w_router": (h, router_width(cfg)), "we_gate": (e, h, f),
            "we_up": (e, h, f), "we_down": (e, f, h)}


def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, h), "head": (h, v), "lnf_g": (h,),
            "layers": [layer_shapes(cfg)
                       for _ in range(cfg["num_hidden_layers"])]}


def leaf_names(cfg):
    """Every leaf's name, e.g. ``layers.3.we_up``."""
    names = ["embed", "head", "lnf_g"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"layers.{i}.{k}" for k in LAYER_LEAVES]
    return names


def init_scale(cfg, name: str) -> float:
    """What a configuration multiplies the generator's draw of a leaf by: the
    head's ``head_init_scale`` (a power of two, so exact in bfloat16; 1 where
    the configuration gives none, as the benchmark's does: the CPU tests
    scale the head until confidences reach the threshold), 1 for every other
    leaf. The adapter and the reference both apply it, to the same values."""
    return cfg.get("head_init_scale", 1.0) if name == "head" else 1.0


def is_gain(name: str) -> bool:
    """The RMSNorm gains, which start near 1 and not near 0."""
    return name.endswith("_g")


def compared_parts(name: str, array):
    """No fused leaf is split: every leaf is compared whole."""
    return {name: array}
