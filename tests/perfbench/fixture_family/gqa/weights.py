"""Leaves of the fixture family: the GPT decoder with grouped-query attention
(``num_kv_heads`` key and value heads shared by groups of query heads), so Q
and KV are projections of their own and the norms' leaves have other names
than GPT's. A fixture of ``tests/perfbench/test_perfbench_family.py``."""

LAYER_LEAVES = ("ln1_scale", "ln1_shift", "w_q", "b_q", "w_kv", "b_kv",
                "w_o", "b_o", "ln2_scale", "ln2_shift", "w_up", "b_up",
                "w_down", "b_down")


def kv_width(cfg) -> int:
    return cfg["num_kv_heads"] * cfg["head_dim"]


def leaf_shapes(cfg):
    h, f, kv = cfg["hidden_size"], cfg["intermediate_size"], kv_width(cfg)
    layer = {"ln1_scale": (h,), "ln1_shift": (h,), "w_q": (h, h),
             "b_q": (h,), "w_kv": (h, 2 * kv), "b_kv": (2 * kv,),
             "w_o": (h, h), "b_o": (h,), "ln2_scale": (h,),
             "ln2_shift": (h,), "w_up": (h, f), "b_up": (f,),
             "w_down": (f, h), "b_down": (h,)}
    return {"wte": (cfg["vocab_size"], h),
            "wpe": (cfg["max_position_embeddings"], h),
            "lnf_scale": (h,), "lnf_shift": (h,),
            "layers": [dict(layer) for _ in range(cfg["num_layers"])]}


def leaf_names(cfg):
    names = ["wte", "wpe", "lnf_scale", "lnf_shift"]
    for i in range(cfg["num_layers"]):
        names += [f"layers.{i}.{k}" for k in LAYER_LEAVES]
    return names


def is_gain(name: str) -> bool:
    return name.endswith("_scale")


def compared_parts(name: str, array):
    """K and V columns of the fused KV projection apart: a key's bias has no
    gradient under softmax."""
    if not name.endswith("_kv"):
        return {name: array}
    w = array.shape[-1] // 2
    return {f"{name}.k": array[..., :w], f"{name}.v": array[..., w:]}
