"""Context parallelism: ring attention + Ulysses (sequence all-to-all).

The reference snapshot has NO long-context CP (SURVEY §5: only the 'sep'
topology axis, batched p2p, and FlashAttention exist as building blocks);
the TPU build makes CP first-class:

- **Ring attention** (`ring_attention`): queries stay put, key/value blocks
  rotate around the ICI ring via ``lax.ppermute`` (one neighbor hop per
  step — the pattern bidirectional ICI is built for). Each step computes a
  blockwise attention against the resident kv block and merges with the
  flash-attention online-softmax rule, so memory is O(S/N) per chip and the
  permute overlaps with the block compute. Causal blocks strictly above the
  diagonal contribute zero work for XLA to schedule (their products are
  masked; the collective schedule stays uniform — the SPMD idiom).
- **Ulysses** (`ulysses_attention`): all-to-all converts sequence sharding
  to head sharding, runs dense/flash attention on full sequences for the
  local heads, and converts back (two a2a hops; better for small N and many
  heads, ref DeepSpeed-Ulysses).

Both run inside partial-manual ``jax.shard_map`` over the ``sep`` axis only,
so TP ('mp') and DP axes continue to be handled by GSPMD around them.
Layout: [batch, seq, heads, head_dim] (paddle flash_attn layout).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "SEP_AXIS"]

SEP_AXIS = "sep"
NEG_INF = -1e30


def _block_attn(q, k, v, scale, causal, q_off, k_off):
    """One q-block vs one kv-block, returning unnormalized flash partials.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]. Returns (acc [B,Sq,H,D] f32,
    m [B,Sq,H] f32 rowmax, l [B,Sq,H] f32 rowsum)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal is not None:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        allowed = (q_pos >= k_pos)[None, None]
        s = jnp.where(allowed, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None]) * allowed
    else:
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    # [B,H,Sq] -> [B,Sq,H]
    return acc, m.transpose(0, 2, 1), l.transpose(0, 2, 1)


def _merge_olse(o, lse, o_b, lse_b):
    """Merge two normalized flash partials over disjoint key sets:
    out = softmax-weighted combination, lse' = logaddexp(lse, lse_b).
    NEG_INF sentinels are finite, so fully-masked partials merge safely
    (weights underflow to 0 instead of producing NaN)."""
    m = jnp.maximum(lse, lse_b)
    a = jnp.exp(lse - m)
    bq = jnp.exp(lse_b - m)
    denom = a + bq
    o_new = (a[..., None] * o + bq[..., None] * o_b) / denom[..., None]
    return o_new, m + jnp.log(denom)


def _dense_block_olse(q, k, v, scale, causal, q_off, k_off):
    """(o, lse) form of _block_attn for the jnp fallback path."""
    acc, m, l = _block_attn(q, k, v, scale, causal, q_off, k_off)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o, lse


def _ring_use_flash(s_local: int, d: int, dtype) -> bool:
    """Static decision: run the Pallas flash kernel inside the ring step?
    (TPU backend + kernel-supported local block shapes; off the chip the
    dense jnp hop is the declared path — the CPU-mesh test path. An
    unsupported block ON a TPU is announced once, P005.)"""
    from ..core import flags
    if not flags.flag("use_pallas_kernels"):
        return False
    if jax.default_backend() != "tpu":
        return False
    if s_local % 128 == 0 and d in (64, 128, 256):
        return True
    from ..analysis.pallas_check import report_fallback
    report_fallback(
        "ring_attention_hop", f"s_local={s_local} d={d}",
        "needs the per-rank sequence block % 128 == 0 and head_dim in "
        "(64, 128, 256)")
    return False


def _inner_mesh(mesh):
    """Mesh to hand a nested shard_map: when already inside a shard_map /
    use_mesh scope (e.g. the pipeline runtime's manual pp axis), jax
    requires the AMBIENT abstract mesh, not the concrete one."""
    am = jax.sharding.get_abstract_mesh()
    if am is not None and len(am.axis_names):
        return am
    return mesh


def _nested_ring_enabled() -> bool:
    """``FLAGS_cp_nested_ring``: run the manual ring inside an enclosing
    manual shard_map instead of the GSPMD fallback."""
    from ..core import flags
    try:
        return bool(flags.flag("cp_nested_ring"))
    except KeyError:
        return False


def _ambient_manual_axes():
    """Axis names already bound manual by an enclosing shard_map (e.g. the
    pipeline runtime's pp axis)."""
    am = jax.sharding.get_abstract_mesh()
    if am is None:
        return ()
    return tuple(n for n, t in zip(am.axis_names, am.axis_types)
                 if "Manual" in str(t))


def _auto_mode_attention(query, key, value, axis, causal, scale):
    """CP inside a partial-manual region (nested in the pipeline's pp
    shard_map): `axis` is an AUTO axis there, so the manual ppermute ring
    cannot be nested (sdy rejects re-binding/mixed-vma operands). Instead
    constrain the seq dim over `axis` and let GSPMD schedule the gathers —
    same math, compiler-chosen communication."""
    from ..ops.flash_attention import flash_attention
    spec = P(P.UNCONSTRAINED, axis, P.UNCONSTRAINED, P.UNCONSTRAINED)
    try:
        query = jax.lax.with_sharding_constraint(query, spec)
        key = jax.lax.with_sharding_constraint(key, spec)
        value = jax.lax.with_sharding_constraint(value, spec)
    except Exception:
        pass  # constraint is an optimization hint; the math is identical
    out = flash_attention(query, key, value, causal=causal, scale=scale)
    try:
        out = jax.lax.with_sharding_constraint(out, spec)
    except Exception:
        pass
    return out


def ring_attention(query, key, value, mesh=None, axis: str = SEP_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   remat: bool = True):
    """[B, S, H, D] attention with S sharded over `axis` (ICI ring CP).

    Inputs/outputs are GLOBAL arrays; the seq dim is sharded over the sep
    axis inside. Equivalent to full (flash) attention over the global
    sequence. On TPU the per-step block compute is the Pallas flash kernel
    (SURVEY §7: "ring attention ... over a Pallas flash-attention kernel")
    via its (o, lse) entry — O(block) memory at any global length; the jnp
    path remains as the CPU/odd-shape fallback."""
    if mesh is None:
        from .topology import get_hybrid_mesh
        mesh = get_hybrid_mesh()
    n = mesh.shape[axis]
    b, s_global, h, d = query.shape
    scale_ = scale if scale is not None else 1.0 / math.sqrt(d)
    if n == 1:
        from ..ops.flash_attention import flash_attention
        return flash_attention(query, key, value, causal=causal, scale=scale)
    if _ambient_manual_axes() and not _nested_ring_enabled():
        # FLAGS_cp_nested_ring=0: GSPMD-scheduled fallback when nested in
        # an enclosing manual region (the pipeline runtime's pp axis).
        # With the flag on, the manual ppermute ring itself nests: the
        # vma plumbing below (pcast'd carries/ranks, abstract inner mesh)
        # exists exactly for that composition, and the multichip dryrun's
        # 4-axis scenario asserts its loss parity against the fallback.
        return _auto_mode_attention(query, key, value, axis, causal, scale)
    s_local = s_global // n
    perm = [(i, (i + 1) % n) for i in range(n)]
    use_flash = _ring_use_flash(s_local, d, query.dtype)
    if use_flash:
        from ..ops._pallas.flash_attention import flash_attention_with_lse

    def fn(q, k, v, ranks):
        # rank from a sep-sharded arange, NOT lax.axis_index: axis_index
        # fails MLIR verification when this shard_map is nested inside
        # another manual axis (the pipeline runtime's pp shard_map)
        rank = ranks[0]
        q_off = rank * s_local

        def block_olse(q, k_blk, v_blk, src):
            """(o [B,s,H,D] f32, lse [B,s,H] f32) for the resident block."""
            if not use_flash:
                return _dense_block_olse(
                    q, k_blk, v_blk, scale_, causal if causal else None,
                    q_off, src * s_local)
            if not causal:
                o, lse = flash_attention_with_lse(q, k_blk, v_blk,
                                                  causal=False, scale=scale_)
                return o.astype(jnp.float32), lse
            # Causal: the block is diagonal (src == rank, kernel causal),
            # fully visible (src < rank), or fully masked (src > rank —
            # no kernel launch, zero partial).
            def diag(q, kb, vb):
                o, lse = flash_attention_with_lse(q, kb, vb, causal=True,
                                                  scale=scale_)
                return o.astype(jnp.float32), lse

            def full(q, kb, vb):
                o, lse = flash_attention_with_lse(q, kb, vb, causal=False,
                                                  scale=scale_)
                return o.astype(jnp.float32), lse

            def masked(q, kb, vb):
                return (jnp.zeros(q.shape, jnp.float32),
                        jnp.full((q.shape[0], q.shape[1], q.shape[2]),
                                 NEG_INF, jnp.float32))

            case = jnp.where(src == rank, 0, jnp.where(src < rank, 1, 2))
            return lax.switch(case, [diag, full, masked], q, k_blk, v_blk)

        def step_fn(carry, i):
            k_blk, v_blk, o, lse = carry
            src = (rank - i) % n  # which global kv block is resident now
            blk = block_olse
            if remat:
                blk = jax.checkpoint(blk)
            o_b, lse_b = blk(q, k_blk, v_blk, src)
            o, lse = _merge_olse(o, lse, o_b, lse_b)
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            return (k_blk, v_blk, o, lse), None

        lse0 = jnp.full((b, s_local, h), NEG_INF, jnp.float32)
        o0 = jnp.zeros((b, s_local, h, d), jnp.float32)
        # the scan carry must be varying over every manual axis the inputs
        # vary over (just `axis` standalone; axis + pp when nested inside
        # the pipeline runtime's manual shard_map)
        target_vma = (set(jax.typeof(q).vma) | set(jax.typeof(k).vma)
                      | {axis})

        def _match_vma(x):
            missing = tuple(a for a in target_vma
                            if a not in jax.typeof(x).vma)
            return lax.pcast(x, missing, to="varying") if missing else x

        lse0, o0 = _match_vma(lse0), _match_vma(o0)
        (_, _, o, lse), _ = lax.scan(
            step_fn, (k, v, o0, lse0), jnp.arange(n))
        return o.astype(query.dtype)

    spec = P(None, axis, None, None)
    ranks = jnp.arange(n, dtype=jnp.int32)
    outer_vma = tuple(getattr(jax.typeof(query), "vma", ()))
    if outer_vma:
        # match the enclosing manual axes (nested-in-pipeline case): all
        # operands of one shard_map must agree on their varying axes
        ranks = lax.pcast(ranks, outer_vma, to="varying")
    return jax.shard_map(fn, mesh=_inner_mesh(mesh),
                         in_specs=(spec, spec, spec, P(axis)),
                         out_specs=spec, axis_names={axis},
                         check_vma=True)(query, key, value, ranks)


def ulysses_attention(query, key, value, mesh=None, axis: str = SEP_AXIS,
                      causal: bool = False, scale: Optional[float] = None):
    """[B, S, H, D] attention, S sharded over `axis`: all-to-all to head
    sharding, full-sequence attention on local heads, all-to-all back
    (DeepSpeed-Ulysses; needs heads % axis_size == 0)."""
    if mesh is None:
        from .topology import get_hybrid_mesh
        mesh = get_hybrid_mesh()
    n = mesh.shape[axis]
    from ..ops.flash_attention import flash_attention
    if n == 1:
        return flash_attention(query, key, value, causal=causal, scale=scale)
    if _ambient_manual_axes():
        return _auto_mode_attention(query, key, value, axis, causal, scale)
    if query.shape[2] % n:
        raise ValueError(f"heads {query.shape[2]} not divisible by "
                         f"{axis}={n}")
    hk = key.shape[2]
    if value.shape[2] != hk:
        raise ValueError(f"key has {hk} heads but value has "
                         f"{value.shape[2]}")
    if query.shape[2] % hk:
        raise ValueError(f"query heads {query.shape[2]} must be a multiple "
                         f"of kv heads {hk} (grouped-query)")
    if hk % n:
        # Grouped-query kv: repeat kv heads just enough that the head
        # all-to-all splits evenly (flash_attention broadcasts the rest
        # locally after the a2a, so a minimal repeat saves ICI bandwidth).
        rep = n // math.gcd(hk, n)
        if (query.shape[2] // hk) % rep:
            rep = query.shape[2] // hk  # full broadcast fallback
        key = jnp.repeat(key, rep, axis=2)
        value = jnp.repeat(value, rep, axis=2)

    def fn(q, k, v):
        # local [B, S/N, H, D] -> [B, S, H/N, D]
        def to_heads(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def to_seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        q, k, v = to_heads(q), to_heads(k), to_heads(v)
        out = flash_attention(q, k, v, causal=causal, scale=scale)
        return to_seq(out)

    spec = P(None, axis, None, None)
    return jax.shard_map(fn, mesh=_inner_mesh(mesh),
                         in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis},
                         check_vma=True)(query, key, value)
