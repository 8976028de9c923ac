"""MoE layer, dropless, told which experts it holds.

Reference design: ``incubate/distributed/models/moe/moe_layer.py:263`` —
tokens sparse-routed via ``global_scatter``/``global_gather`` (alltoall ops,
``distributed/utils/moe_utils.py:20/146``) to experts living on different
ranks of the EP group.

Here: the gate scores every token over ALL ``num_experts`` and picks its
top-k; the layer holds ``experts_held = (first, count)`` of them (all, by
default) and computes, for every (token, expert) pair that falls to an expert
it holds, that expert's FFN, as grouped matrix products over the pairs sorted
by expert (``dropless.py``). No pair is dropped at any load and no capacity
bucket exists. What the experts held elsewhere would add is left out: with
every expert held the result is the whole layer, with a share it is this
rank's part of it, and the parts of all ranks add up to the whole. The
exchange that would carry tokens between ranks is not part of this layer.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..... import nn
from .....nn import functional as F
from .....nn.layer import ParamAttr
from .dropless import dropless_route
from .gate import NaiveGate, GShardGate, SwitchGate

__all__ = ["MoELayer"]

EP_AXIS = "mp"  # expert axis rides the model-parallel axis unless a
                # dedicated 'ep' axis exists in the mesh


class _ExpertFFN(nn.Layer):
    """The held experts' FFN weights stacked: [E, d, ffn] / [E, ffn, d],
    expert dim sharded over the EP axis."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation: Callable = F.gelu):
        super().__init__()
        self.activation = activation
        self.w1 = self.create_parameter(
            (num_experts, d_model, d_hidden),
            attr=ParamAttr(partition_spec=P(EP_AXIS, None, None)))
        self.b1 = self.create_parameter(
            (num_experts, 1, d_hidden), is_bias=True,
            attr=ParamAttr(partition_spec=P(EP_AXIS, None, None)))
        self.w2 = self.create_parameter(
            (num_experts, d_hidden, d_model),
            attr=ParamAttr(partition_spec=P(EP_AXIS, None, None)))
        self.b2 = self.create_parameter(
            (num_experts, 1, d_model), is_bias=True,
            attr=ParamAttr(partition_spec=P(EP_AXIS, None, None)))

    def forward(self, xs, route):
        """xs [rows, d] sorted by expert -> [rows, d]."""
        gs = route.group_sizes
        h = jax.lax.ragged_dot(xs, self.w1, gs) + self.b1[route.expert, 0]
        h = self.activation(h)
        return jax.lax.ragged_dot(h, self.w2, gs) + self.b2[route.expert, 0]


class MoELayer(nn.Layer):
    """ref moe_layer.py:263 MoELayer(gate=..., experts=...).

    forward: x [B, S, d] -> y [B, S, d]; records the aux loss in
    ``self.l_aux`` (reference attribute name) and the pairs each held expert
    got in ``self.expert_load``. ``experts_held=(first, count)`` makes the
    layer one rank's share of expert parallelism."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: str = "gshard", capacity_factor: float = 1.25,
                 activation=F.gelu, gate_cls=None, moe_group=None,
                 recompute_interval: int = 0,
                 experts_held: Optional[Tuple[int, int]] = None):
        super().__init__()
        self.num_experts = num_experts
        self.first, self.count = experts_held or (0, num_experts)
        if not 0 <= self.first <= self.first + self.count <= num_experts \
                or self.count < 1:
            raise ValueError(f"experts_held {experts_held} outside the "
                             f"gate's {num_experts} experts")
        gates = {"naive": NaiveGate, "gshard": GShardGate, "switch": SwitchGate}
        cls = gate_cls or gates[gate]
        self.gate = cls(d_model, num_experts, capacity_factor)
        self.experts = _ExpertFFN(self.count, d_model, d_hidden, activation)
        self.l_aux = jnp.zeros(())
        self.expert_load = None

    def forward(self, x):
        b, s, d = x.shape
        flat = x.reshape(b * s, d)
        idx, weight, aux = self.gate(flat)
        self.l_aux = aux
        route = dropless_route(idx, self.count, self.first)
        self.expert_load = route.group_sizes
        out = self.experts(route.gather(flat), route)
        return route.combine(out, weight).astype(x.dtype).reshape(b, s, d)
