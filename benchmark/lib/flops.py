"""Operations and bytes at the level of one kernel call and of the chip: what
a flash-attention call needs from its shapes, and the roofline of a count.
What a whole step of a model needs is its family's to say
(``benchmark/families/<model>/needs.py``).

Every count is of *needed* work: recomputed activations, padding up to a
bucket and pages gathered beyond a row's real context do not count, so a
share built on these cannot be raised by doing more work than needed.
"""

from __future__ import annotations

from typing import Tuple

BF16 = 2  # bytes


def flash_call_needs(kind: str, batch_heads: int, seq: int,
                     head_dim: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one causal flash-attention kernel call as executed.

    ``kind``: ``fwd`` (QK^T, PV), ``bwd_dq`` (QK^T, dP=dO V^T, dQ=dS K) or
    ``bwd_dkv`` (QK^T, dP, dV=P^T dO, dK=dS^T Q); each product is
    2*seq^2*head_dim FLOPs a head, halved for the causal mask. Bytes: each
    [batch*heads, seq, head_dim] bf16 operand read or written once."""
    products = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind]
    operands = {"fwd": 4, "bwd_dq": 6, "bwd_dkv": 7}[kind]
    flops = products * 2 * seq * seq * head_dim * batch_heads / 2
    nbytes = operands * batch_heads * seq * head_dim * BF16
    return float(flops), float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks) -> Tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_f, t_b = flops / peaks.flops_bf16, nbytes / peaks.hbm_bytes_s
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
