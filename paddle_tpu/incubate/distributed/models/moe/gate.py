"""MoE gates.

ref: ``python/paddle/incubate/distributed/models/moe/gate/`` —
{naive,gshard,switch}_gate.py. Each gate scores tokens over experts and
returns ``(idx [T, k], weight [T, k], aux_loss)``: the experts each token
goes to and the weight of each. There is no capacity: the layer is dropless
(``dropless.py``), so a gate never cuts a token off; ``capacity_factor`` is
accepted for the reference's signature and unused."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..... import nn
from .....core.random import next_key

__all__ = ["NaiveGate", "GShardGate", "SwitchGate"]


def _balance_loss(probs, top1):
    """GShard's load-balance loss: the share of tokens whose first choice an
    expert is, times its mean probability, summed and scaled by E^2."""
    e = probs.shape[-1]
    density = jnp.mean(jax.nn.one_hot(top1, e), axis=0)
    return jnp.sum(density * jnp.mean(probs, axis=0)) * e


class _GateBase(nn.Layer):
    top_k = 1

    def __init__(self, d_model: int, num_experts: int,
                 capacity_factor: float = 1.25):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.weight = self.create_parameter((d_model, num_experts))

    def _probs(self, x):
        logits = jnp.matmul(x, self.weight)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    def forward(self, x):
        """x [T, d] -> (idx [T, k], weight [T, k], aux)."""
        probs = self._probs(x)
        weight, idx = jax.lax.top_k(probs, self.top_k)
        return idx, weight, _balance_loss(probs, idx[:, 0])


class NaiveGate(_GateBase):
    """ref naive_gate.py: plain top-1 weighted by its probability."""


class SwitchGate(_GateBase):
    """ref switch_gate.py: top-1 with jitter noise during training."""

    def __init__(self, d_model, num_experts, capacity_factor=1.25,
                 jitter: float = 0.01):
        super().__init__(d_model, num_experts, capacity_factor)
        self.jitter = jitter

    def forward(self, x):
        if self.training and self.jitter > 0:
            noise = jax.random.uniform(next_key(), x.shape,
                                       minval=1 - self.jitter,
                                       maxval=1 + self.jitter)
            x = x * noise.astype(x.dtype)
        return super().forward(x)


class GShardGate(_GateBase):
    """ref gshard_gate.py: top-2, the two weights normalised to sum 1."""
    top_k = 2

    def forward(self, x):
        idx, weight, aux = super().forward(x)
        weight = weight / jnp.clip(jnp.sum(weight, axis=-1, keepdims=True),
                                   1e-9, None)
        return idx, weight, aux
