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

That is the *grouped* form, made for prefill, where thousands of tokens meet
the held experts. A grouped product costs at least one row tile for every
expert that has a pair, so when the whole call has no more tokens than one
such tile (a decode step) each expert's tile may as well be the batch
itself: the *dense* form runs every held expert over every token and gives
a pair that was not routed the weight zero. No sort, no gather; the same
pairs, the same rounding points, the same loads. ``expert_form`` picks from
the token count alone.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["Route", "dropless_route", "group_limited_topk",
           "renormalised_topk", "record_held_pairs", "dropless_glu_experts", "expert_form", "EXPERT_FORMS",
           "grouped_glu_experts", "dense_glu_experts", "DENSE_MAX_TOKENS"]

#: The most tokens a call may have and still take the dense form: the row
#: tile XLA:TPU's ``ragged_dot`` gives every expert that has a pair
#: (``ragged_dot_tiling="512,512,512"`` in the compiled program). Each form
#: alone on a TPU v5e at E 10, d 5120, f 1536, top-6 of 160 (9.6 pairs an
#: expert at T 256), bfloat16, in ms a call (median of 5 x 100 calls by the
#: host's clock; chip run of PR 31):
#:
#:     T         128    256    384    512    768   1024   2048
#:     grouped  1.04   1.66   1.15   1.86   2.02   2.35   4.17
#:     dense    0.65   0.75   1.09   1.48   2.14   3.01   6.54
#:
#: They cross between 512 and 768 (near 700 by the lines through those
#: points). One gate product at T 256: 0.53 ms grouped, 0.25 dense, beside
#: 0.19 ms of weight read and 0.20 of products at the chip's peaks.
#:
#: The same at E 16, d 2048, f 768, top-8 of 128 renormalised (one expected
#: pair a token; 32 pairs an expert at T 512, which is a whole pass of the
#: block-diffusion serving cell; chip run of PR 34):
#:
#:     T         128    256    512    768   1024
#:     grouped  0.62   0.69   0.73   0.84   0.89
#:     dense    0.23   0.31   0.47   0.74   1.00
#:
#: They cross between 768 and 1024 (near 850). Both forms do 16 times the
#: needed products there; at T 512 the dense form takes 2.5 times what
#: reading the 151 MB of held experts takes. The constant stays where the
#: grouped form's tile puts it: one choice for both cells.
DENSE_MAX_TOKENS = 512


def expert_form(tokens: int) -> str:
    """The form ``dropless_glu_experts`` takes for a call of ``tokens``
    tokens, ``"dense"`` or ``"grouped"``. Both cost a multiple of the held
    experts, so their number does not enter; neither does the load, which
    is not known when the program is traced."""
    return "dense" if tokens <= DENSE_MAX_TOKENS else "grouped"


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


def renormalised_topk(probs, top_k: int, renormalise: bool = True):
    """Plain top-k of ``probs [T, E]`` (a float32 softmax over every expert):
    ``(idx, weight)`` each ``[T, k]``, the weights divided by their sum where
    ``renormalise`` (``norm_topk_prob`` of the Qwen3-MoE family), so that a
    token's experts weigh 1 together whatever the router left to the
    others."""
    val, idx = jax.lax.top_k(probs, top_k)
    if renormalise:
        val = val / jnp.sum(val, axis=-1, keepdims=True)
    return idx, val


def record_held_pairs(load, n_tokens: int, *, top_k: int, n_layers: int,
                      first: int) -> None:
    """The serving counters behind the counts a routed-expert model's
    programs return (``model.serve_record_counts``): ``n_tokens`` real tokens
    went through ``n_layers`` expert layers, ``load[e]`` of their pairs fell
    to held expert ``first + e``."""
    from .....observability import metrics
    pairs = metrics.counter(
        "serving.moe_assignments",
        "(token, expert) pairs the router made (kind=routed: tokens x "
        "top-k x expert layers) and those that fell to experts held "
        "here (kind=held)")
    pairs.labels(kind="routed").inc(int(n_tokens) * top_k * n_layers)
    pairs.labels(kind="held").inc(int(load.sum()))
    by_expert = metrics.counter(
        "serving.moe_expert_load",
        "(token, expert) pairs that fell to each held expert")
    for i, n in enumerate(load):
        by_expert.labels(expert=first + i).inc(int(n))


def grouped_glu_experts(x, idx, weight, w_gate, w_up, w_down, *,
                        first: int = 0, activation=jax.nn.silu):
    """``dropless_glu_experts`` (below) by sorting the held pairs by expert
    and grouped products over the sorted rows."""
    route = dropless_route(idx, w_gate.shape[0], first)
    xs = route.gather(x)
    gs = route.group_sizes
    mid = activation(jax.lax.ragged_dot(xs, w_gate, gs)) \
        * jax.lax.ragged_dot(xs, w_up, gs)
    out = jax.lax.ragged_dot(mid.astype(x.dtype), w_down, gs)
    return route.combine(out, weight), gs


def dense_glu_experts(x, idx, weight, w_gate, w_up, w_down, *,
                      first: int = 0, activation=jax.nn.silu):
    """``dropless_glu_experts`` by running every held expert over every
    token: ``c [T, E]`` holds each pair's weight, and zero where the token
    did not pick the expert (an ``idx`` of -1 and an expert held elsewhere
    match nothing), so such a product adds exactly nothing."""
    count = w_gate.shape[0]
    hit = (idx - first)[..., None] == jnp.arange(count, dtype=idx.dtype)
    c = jnp.sum(jnp.where(hit, weight.astype(jnp.float32)[..., None], 0.0),
                axis=1)                                          # [T, E]
    load = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    mid = activation(jnp.einsum("td,edf->etf", x, w_gate)) \
        * jnp.einsum("td,edf->etf", x, w_up)
    out = jnp.einsum("etf,efd->etd", mid.astype(x.dtype), w_down)
    # elementwise, not a product: float32 on the MXU would round c to bf16
    y = jnp.sum(c.T[..., None] * out.astype(jnp.float32), axis=0)
    return y, load


#: What ``expert_form`` names.
EXPERT_FORMS = {"grouped": grouped_glu_experts, "dense": dense_glu_experts}


def dropless_glu_experts(x, idx, weight, w_gate, w_up, w_down, *,
                         first: int = 0, activation=jax.nn.silu):
    """Gated-linear-unit experts over the pairs held here.

    ``x [T, d]``; ``idx``/``weight [T, k]`` the gate's experts and their
    weights; ``w_gate``/``w_up [E, d, f]``, ``w_down [E, f, d]`` the experts
    held (expert ``e`` of the stack is expert ``first + e`` of the gate).
    Returns ``(y [T, d] float32, load [E] int32)``: each token's weighted sum
    over its held experts, and how many pairs each held expert got. Each
    product accumulates in float32 and is stored in ``x.dtype``, in either
    form (``expert_form`` of ``T``)."""
    return EXPERT_FORMS[expert_form(x.shape[0])](
        x, idx, weight, w_gate, w_up, w_down, first=first,
        activation=activation)
