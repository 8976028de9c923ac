"""Dropless expert routing: every (token, expert) pair the gate picks is
computed, whatever the load; nothing is a capacity bucket.

The pairs that fall to the experts *held here* (``count`` consecutive
experts from ``first``; all of them when the layer is not expert-parallel)
are sorted by expert, the tokens' activations gathered in that order, and
the experts run as grouped matrix products (``jax.lax.ragged_dot``: row
block ``e`` of the sorted activations times expert ``e``'s matrix). The
sorted buffer holds ``tokens * min(top_k, count)`` rows, which is every pair
that can be held here (a token's experts are distinct), so no load, however
uneven, cuts a pair off; rows past the pairs really held belong to no group
and are never read back. Pairs routed to experts held elsewhere add nothing
here: on one chip the layer runs without its exchange, and nothing stands in
for the absent chips.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["Route", "dropless_route", "group_limited_topk",
           "dropless_glu_experts"]


class Route(NamedTuple):
    """Where each pair goes. ``rows`` is the static row count of the sorted
    buffer."""
    token: jax.Array        # [rows] token of each sorted row
    expert: jax.Array       # [rows] local expert of each sorted row (clamped)
    group_sizes: jax.Array  # [count] pairs held by each local expert
    slot: jax.Array         # [T, k] sorted row of each pair (clamped)
    held: jax.Array         # [T, k] the pair's expert is held here

    def gather(self, x):
        """``x [T, d]`` -> the sorted buffer ``[rows, d]``."""
        return x[self.token]

    def combine(self, out, weight):
        """Un-route: ``out [rows, d]`` (expert outputs in sorted order) and
        the pairs' ``weight [T, k]`` -> ``[T, d]`` float32, each token the
        weighted sum of its held experts' outputs."""
        picked = out[self.slot].astype(jnp.float32)            # [T, k, d]
        # rows past the pairs held belong to no group: never read them back
        picked = jnp.where(self.held[..., None], picked, 0.0)
        return jnp.sum(picked * weight.astype(jnp.float32)[..., None],
                       axis=1)


def dropless_route(idx, count: int, first: int = 0) -> Route:
    """Route ``idx [T, k]`` (global expert ids, distinct within a token) to
    the ``count`` experts held here, ``first .. first + count - 1``."""
    t, k = idx.shape
    local = idx - first
    held = jnp.logical_and(local >= 0, local < count)
    key = jnp.where(held, local, count).reshape(-1)             # [T*k]
    rows = t * min(k, count)
    order = jnp.argsort(key, stable=True)
    rank = jnp.argsort(order)            # sorted position of each pair
    order = order[:rows]
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    return Route(token=order // k,
                 expert=jnp.minimum(key[order], count - 1),
                 group_sizes=group_sizes,
                 slot=jnp.minimum(rank, rows - 1).reshape(t, k),
                 held=held)


def group_limited_topk(scores, top_k: int, n_group: int = 1,
                       topk_group: int = 1):
    """Group-limited greedy selection: ``scores [T, E]`` are split into
    ``n_group`` groups of consecutive experts, a group scores as its best
    expert, the ``topk_group`` best groups stay, and the ``top_k`` best
    experts among them are the token's. Returns ``(idx, score)`` each
    ``[T, k]``. ``n_group == 1`` is plain top-k."""
    t, e = scores.shape
    if n_group > 1:
        per = e // n_group
        group_best = jnp.max(scores.reshape(t, n_group, per), axis=-1)
        _, gidx = jax.lax.top_k(group_best, topk_group)          # [T, g]
        keep = jnp.any(gidx[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)                                   # [T, G]
        scores = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
    val, idx = jax.lax.top_k(scores, top_k)
    return idx, val


def dropless_glu_experts(x, idx, weight, w_gate, w_up, w_down, *,
                         first: int = 0, activation=jax.nn.silu):
    """Gated-linear-unit experts over the pairs held here.

    ``x [T, d]``; ``idx``/``weight [T, k]`` the gate's experts and their
    weights; ``w_gate``/``w_up [E, d, f]``, ``w_down [E, f, d]`` the experts
    held (expert ``e`` of the stack is expert ``first + e`` of the gate).
    Returns ``(y [T, d] float32, load [E] int32)``: each token's weighted sum
    over its held experts, and how many pairs each held expert got."""
    route = dropless_route(idx, w_gate.shape[0], first)
    xs = route.gather(x)
    gs = route.group_sizes
    mid = activation(jax.lax.ragged_dot(xs, w_gate, gs)) \
        * jax.lax.ragged_dot(xs, w_up, gs)
    out = jax.lax.ragged_dot(mid.astype(x.dtype), w_down, gs)
    return route.combine(out, weight), gs
