"""Weights from the seed: one jitted call, on the device, in the type they are
served or trained in. The program's model and the plain reference are both
handed these values; neither takes anything the other has made.

Layout (neutral names; ``lib/system.py`` maps them to the program's):
``{"wte", "wpe", "lnf_g", "lnf_b", "layers": [{"ln1_g", "ln1_b", "w_qkv",
"b_qkv", "w_o", "b_o", "ln2_g", "ln2_b", "w_up", "b_up", "w_down",
"b_down"}, ...]}``; linear weights are [in, out]; ``w_qkv`` columns are
ordered (q|k|v, head, head_dim).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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


def seed_key(seed: int):
    """A PRNG key from any whole number a little over 2**31 (the driver's
    seeds do not fit 32 signed bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def weights_from_key(key, cfg, dtype):
    """Every matrix and bias about N(0, initializer_range); layer-norm gains
    1 + half of that; nothing is zero, so a path that drops a bias or a gain
    shows.

    Each value is a whole number times a power of two that bfloat16 holds
    exactly: the sum of four random 6-bit numbers less its mean (-126..126),
    times 2**-11 (standard deviation 0.018); a gain is 1 + (that sum // 32) *
    2**-7. No step rounds, so the same key gives the same bits inside any
    program, whatever the compiler fuses, reorders or keeps in excess
    precision (XLA on the TPU drops a float32 -> bfloat16 -> float32 round
    trip, which moved every gain by up to half a bfloat16 step when this was
    a rounded normal; my chip runs, PR 25). That is what lets the comparison
    make the starting weights again in place instead of keeping a second copy
    of the model resident."""
    shapes = leaf_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, flat)):
        bits = jax.random.bits(jax.random.fold_in(key, i), shape, jnp.uint32)
        total = sum(((bits >> (8 * b)) & 0x3F).astype(jnp.int32)
                    for b in range(4)) - 126
        if str(path[-1].key).endswith("_g"):
            # truncating division: -126..126 -> -3..3
            v = 1.0 + jax.lax.div(total, 32).astype(jnp.float32) * 2.0 ** -7
        else:
            v = total.astype(jnp.float32) * 2.0 ** -11
        out.append(v.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def make_weights(cfg, seed: int, dtype=jnp.bfloat16, out_shardings=None):
    """The weights of ``cfg`` from ``seed`` as ``dtype`` device arrays."""
    fn = jax.jit(lambda k: weights_from_key(k, cfg, dtype),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))


def leaf_names(cfg):
    """Flat ``name -> path`` of every leaf, e.g. ``layers.3.w_qkv``."""
    names = ["wte", "wpe", "lnf_g", "lnf_b"]
    for i in range(cfg["num_layers"]):
        names += [f"layers.{i}.{k}" for k in LAYER_LEAVES]
    return names


def get_leaf(tree, name: str):
    parts = name.split(".")
    if parts[0] == "layers":
        return tree["layers"][int(parts[1])][parts[2]]
    return tree[name]


def compared_parts(name: str, array):
    """The pieces of a leaf that the comparison treats as leaves of their
    own: a fused QKV weight or bias is three (its q, k and v columns), since a
    key's bias has no gradient under softmax while q's and v's have."""
    if not name.endswith("_qkv"):
        return {name: array}
    h = array.shape[-1] // 3
    return {f"{name}.{part}": array[..., i * h:(i + 1) * h]
            for i, part in enumerate("qkv")}
