"""Weights from the seed: one jitted call, on the device, in the type they are
served or trained in. The program's model and the plain reference are both
handed these values; neither takes anything the other has made.

The generator is every family's: it draws from the seed for whatever tree of
shapes the family's ``weights.py`` gives (``leaf_shapes(cfg)``: nested dicts
and lists of shape tuples, under neutral names that the family's adapter maps
to the program's), and asks the family only which leaves are gains. A leaf's
name is its path joined by dots, e.g. ``layers.3.w_up``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def seed_key(seed: int):
    """A PRNG key from any whole number a little over 2**31 (the driver's
    seeds do not fit 32 signed bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def path_name(path) -> str:
    return ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def weights_from_key(key, fam_weights, cfg, dtype):
    """Every matrix and bias about N(0, 0.018); a gain (a leaf for which the
    family's ``is_gain(name)`` holds) is 1 + half of that; nothing is zero,
    so a path that drops a bias or a gain shows.

    Each value is a whole number times a power of two that bfloat16 holds
    exactly: the sum of four random 6-bit numbers less its mean (-126..126),
    times 2**-11 (standard deviation 0.018); a gain is 1 + (that sum // 32) *
    2**-7. No step rounds, so the same key gives the same bits inside any
    program, whatever the compiler fuses, reorders or keeps in excess
    precision (XLA on the TPU drops a float32 -> bfloat16 -> float32 round
    trip, which moved every gain by up to half a bfloat16 step when this was
    a rounded normal; my chip runs, PR 25). That is what lets the comparison
    make the starting weights again in place instead of keeping a second copy
    of the model resident. Leaf ``i`` in the tree's flatten order draws from
    ``fold_in(key, i)``."""
    shapes = fam_weights.leaf_shapes(cfg)
    with_paths, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(with_paths):
        bits = jax.random.bits(jax.random.fold_in(key, i), shape, jnp.uint32)
        total = sum(((bits >> (8 * b)) & 0x3F).astype(jnp.int32)
                    for b in range(4)) - 126
        if fam_weights.is_gain(path_name(path)):
            # truncating division: -126..126 -> -3..3
            v = 1.0 + jax.lax.div(total, 32).astype(jnp.float32) * 2.0 ** -7
        else:
            v = total.astype(jnp.float32) * 2.0 ** -11
        out.append(v.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def make_weights(fam_weights, cfg, seed: int, dtype=jnp.bfloat16,
                 out_shardings=None):
    """The weights of ``cfg`` from ``seed`` as ``dtype`` device arrays."""
    fn = jax.jit(lambda k: weights_from_key(k, fam_weights, cfg, dtype),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))


def get_leaf(tree, name: str):
    for part in name.split("."):
        tree = tree[int(part)] if isinstance(tree, (list, tuple)) \
            else tree[part]
    return tree


def f32_weights(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def row_sharded(fam_weights, cfg, mesh):
    """Shardings that split every matrix by rows over all of ``mesh``'s chips
    and keep the vectors whole: a float32 state too large for one chip then
    fits, and no arithmetic changes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    flat = Mesh(mesh.devices.reshape(-1), ("x",))
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(flat, PartitionSpec(
            "x" if len(s) == 2 else None)),
        fam_weights.leaf_shapes(cfg), is_leaf=_is_shape)
