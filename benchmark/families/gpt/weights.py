"""The leaves of the GPT-3 decoder, under neutral names that ``adapter.py``
maps to the program's. The values come from the shared generator
(``benchmark/lib/weights.py``).

Layout: ``{"wte", "wpe", "lnf_g", "lnf_b", "layers": [{"ln1_g", "ln1_b",
"w_qkv", "b_qkv", "w_o", "b_o", "ln2_g", "ln2_b", "w_up", "b_up", "w_down",
"b_down"}, ...]}``; linear weights are [in, out]; ``w_qkv`` columns are
ordered (q|k|v, head, head_dim).
"""

from __future__ import annotations

LAYER_LEAVES = ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_o", "b_o",
                "ln2_g", "ln2_b", "w_up", "b_up", "w_down", "b_down")


def leaf_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    layer = {"ln1_g": (h,), "ln1_b": (h,), "w_qkv": (h, 3 * h),
             "b_qkv": (3 * h,), "w_o": (h, h), "b_o": (h,),
             "ln2_g": (h,), "ln2_b": (h,), "w_up": (h, f), "b_up": (f,),
             "w_down": (f, h), "b_down": (h,)}
    return {"wte": (cfg["vocab_size"], h),
            "wpe": (cfg["max_position_embeddings"], h),
            "lnf_g": (h,), "lnf_b": (h,),
            "layers": [dict(layer) for _ in range(cfg["num_layers"])]}


def leaf_names(cfg):
    """Every leaf's name, e.g. ``layers.3.w_qkv``."""
    names = ["wte", "wpe", "lnf_g", "lnf_b"]
    for i in range(cfg["num_layers"]):
        names += [f"layers.{i}.{k}" for k in LAYER_LEAVES]
    return names


def is_gain(name: str) -> bool:
    """The layer norms' gains, which start near 1 and not near 0."""
    return name.endswith("_g")


def compared_parts(name: str, array):
    """The pieces of a leaf that the comparison treats as leaves of their
    own: a fused QKV weight or bias is three (its q, k and v columns), since a
    key's bias has no gradient under softmax while q's and v's have."""
    if not name.endswith("_qkv"):
        return {name: array}
    h = array.shape[-1] // 3
    return {f"{name}.{part}": array[..., i * h:(i + 1) * h]
            for i, part in enumerate("qkv")}
