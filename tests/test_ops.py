"""Systematic op-parity harness.

The TPU-native analog of the reference's OpTest
(``test/legacy_test/eager_op_test.py:381``): every spec declares an op, its
inputs, and a numpy reference; the harness checks

- **eager forward** against the numpy reference,
- **jit forward** against eager (the XLA path — what actually runs on TPU),
- **reverse-mode gradients** against central finite differences in float64
  (``jax.test_util.check_grads``), the analog of ``check_grad_with_place``.

Specs live in one table (OPS) and are parametrized by name, replacing the
reference's 1,335 per-op test files with one declarative sweep.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.special as sps

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.tensor as T


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

@dataclass
class Op:
    name: str
    fn: Callable
    args: tuple                      # numpy arrays / python scalars
    ref: Optional[Callable] = None   # numpy reference over the same args
    kwargs: dict = field(default_factory=dict)
    grad: bool = True                # check rev-mode grads vs finite diffs
    grad_argnums: Optional[tuple] = None  # default: all float array args
    rtol: float = 1e-5
    atol: float = 1e-5
    jit: bool = True   # False for data-dependent output shapes (nonzero…)
    # Ops whose output is integer/bool or non-differentiable by nature set
    # grad=False; ops with no numpy reference (RNG, identity) set ref=None
    # and only get eager-vs-jit + shape/dtype checks.


def _rng(seed=0):
    return np.random.default_rng(seed)


def _is_traced(a) -> bool:
    """Arrays (and lists of arrays) are traced under jit; ints/shapes/axis
    lists/strings stay static — mirroring how attrs vs inputs split in the
    reference's OpTest."""
    if isinstance(a, np.ndarray):
        return True
    if isinstance(a, (list, tuple)) and a and \
            all(isinstance(x, np.ndarray) for x in a):
        return True
    return False


def _f32(*shape, seed=0, lo=-2.0, hi=2.0):
    return _rng(seed).uniform(lo, hi, shape).astype(np.float32)


def _pos(*shape, seed=0, lo=0.1, hi=3.0):
    return _rng(seed).uniform(lo, hi, shape).astype(np.float32)


def _i32(*shape, seed=0, lo=0, hi=10):
    return _rng(seed).integers(lo, hi, shape).astype(np.int32)


def _bool(*shape, seed=0):
    return _rng(seed).integers(0, 2, shape).astype(bool)


def _is_float_array(a) -> bool:
    return isinstance(a, np.ndarray) and np.issubdtype(a.dtype, np.floating)


def _to_jax(a):
    if isinstance(a, np.ndarray):
        return jnp.asarray(a)
    if isinstance(a, (list, tuple)) and a and \
            all(isinstance(x, np.ndarray) for x in a):
        return type(a)(jnp.asarray(x) for x in a)
    return a


def _check_forward(spec: Op):
    jargs = tuple(_to_jax(a) for a in spec.args)
    f = lambda *xs: spec.fn(*xs, **spec.kwargs)
    out_eager = f(*jargs)
    if spec.jit:
        traced_idx = [i for i, a in enumerate(spec.args) if _is_traced(a)]

        def f_traced(*traced):
            full = list(jargs)
            for i, t in zip(traced_idx, traced):
                full[i] = t
            return spec.fn(*full, **spec.kwargs)

        out_jit = jax.jit(f_traced)(*[jargs[i] for i in traced_idx])
    else:
        out_jit = out_eager
    e_flat = jax.tree_util.tree_leaves(out_eager)
    j_flat = jax.tree_util.tree_leaves(out_jit)
    assert len(e_flat) == len(j_flat)
    for a, b in zip(e_flat, j_flat):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=spec.rtol, atol=spec.atol,
                                   err_msg=f"{spec.name}: eager vs jit")
    if spec.ref is not None:
        expect = spec.ref(*spec.args)
        expect_flat = expect if isinstance(expect, (tuple, list)) \
            else [expect]
        assert len(e_flat) == len(expect_flat), \
            f"{spec.name}: arity {len(e_flat)} vs ref {len(expect_flat)}"
        for a, b in zip(e_flat, expect_flat):
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.asarray(b).dtype), b,
                rtol=spec.rtol, atol=spec.atol,
                err_msg=f"{spec.name}: eager vs numpy ref")


def _check_grad(spec: Op):
    from jax.test_util import check_grads
    argnums = spec.grad_argnums
    if argnums is None:
        argnums = tuple(i for i, a in enumerate(spec.args)
                        if _is_float_array(a))
    if not argnums:
        return
    with jax.enable_x64(True):
        fixed = list(spec.args)
        var = []
        for i in argnums:
            var.append(jnp.asarray(np.asarray(spec.args[i], np.float64)))

        def g(*xs):
            full = list(fixed)
            for i, x in zip(argnums, xs):
                full[i] = x
            out = spec.fn(*full, **spec.kwargs)
            leaves = [l for l in jax.tree_util.tree_leaves(out)
                      if jnp.issubdtype(l.dtype, jnp.floating)]
            return sum(jnp.sum(l * jnp.cos(0.1 * l)) for l in leaves)

        check_grads(g, tuple(var), order=1, modes=("rev",),
                    rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Spec table
# ---------------------------------------------------------------------------

A = _f32(3, 4, seed=1)
B = _f32(3, 4, seed=2)
POSA = _pos(3, 4, seed=3)
SQ = _f32(4, 4, seed=4)
V3 = _f32(5, seed=5)
M34 = _f32(3, 4, seed=6)
M45 = _f32(4, 5, seed=7)
SMALL = _f32(2, 3, seed=8, lo=-0.9, hi=0.9)
IDX = np.array([2, 0, 1], np.int32)
SPD = (SQ @ SQ.T + 4 * np.eye(4)).astype(np.float32)

OPS = [
    # ---- unary elementwise (math.py) ----
    Op("abs", T.abs, (A,), np.abs, grad=False),
    Op("acos", T.acos, (SMALL,), np.arccos),
    Op("asin", T.asin, (SMALL,), np.arcsin),
    Op("atan", T.atan, (A,), np.arctan),
    Op("ceil", T.ceil, (A,), np.ceil, grad=False),
    Op("cos", T.cos, (A,), np.cos),
    Op("cosh", T.cosh, (A,), np.cosh),
    Op("deg2rad", T.deg2rad, (A,), np.deg2rad),
    Op("digamma", T.digamma, (POSA,), sps.digamma, rtol=1e-4, atol=1e-4),
    Op("erf", T.erf, (A,), sps.erf),
    Op("erfinv", T.erfinv, (SMALL,), sps.erfinv, rtol=1e-4, atol=1e-4),
    Op("exp", T.exp, (A,), np.exp),
    Op("expm1", T.expm1, (A,), np.expm1),
    Op("floor", T.floor, (A,), np.floor, grad=False),
    Op("frac", T.frac, (A,), lambda x: x - np.trunc(x), grad=False),
    Op("lgamma", T.lgamma, (POSA,), sps.gammaln, rtol=1e-4, atol=1e-4),
    Op("log", T.log, (POSA,), np.log),
    Op("log10", T.log10, (POSA,), np.log10),
    Op("log1p", T.log1p, (POSA,), np.log1p),
    Op("log2", T.log2, (POSA,), np.log2),
    Op("logit", T.logit, (_pos(3, 4, lo=0.1, hi=0.9),),
       lambda x: np.log(x / (1 - x)), rtol=1e-4, atol=1e-4),
    Op("neg", T.neg, (A,), np.negative),
    Op("rad2deg", T.rad2deg, (A,), np.rad2deg, rtol=1e-4, atol=1e-3),
    Op("reciprocal", T.reciprocal, (POSA,), np.reciprocal),
    Op("round", T.round, (A,), np.round, grad=False),
    Op("rsqrt", T.rsqrt, (POSA,), lambda x: 1 / np.sqrt(x)),
    Op("sign", T.sign, (A,), np.sign, grad=False),
    Op("sin", T.sin, (A,), np.sin),
    Op("sinh", T.sinh, (A,), np.sinh),
    Op("sqrt", T.sqrt, (POSA,), np.sqrt),
    Op("square", T.square, (A,), np.square),
    Op("stanh", T.stanh, (A,), lambda x: 1.7159 * np.tanh(2 / 3 * x),
       kwargs=dict(scale_a=2 / 3, scale_b=1.7159)),
    Op("tan", T.tan, (SMALL,), np.tan),
    Op("tanh", T.tanh, (A,), np.tanh),
    Op("trunc", T.trunc, (A,), np.trunc, grad=False),
    Op("angle", T.angle, (A,), np.angle, grad=False),
    # ---- binary elementwise ----
    Op("add", T.add, (A, B), np.add),
    Op("atan2", T.atan2, (A, POSA), np.arctan2),
    Op("divide", T.divide, (A, POSA), np.divide),
    Op("floor_divide", T.floor_divide, (_i32(3, 4, lo=1, hi=20),
                                        _i32(3, 4, seed=2, lo=1, hi=5)),
       np.floor_divide, grad=False),
    Op("fmax", T.fmax, (A, B), np.fmax, grad=False),
    Op("fmin", T.fmin, (A, B), np.fmin, grad=False),
    Op("heaviside", T.heaviside, (A, B), np.heaviside, grad=False),
    Op("lerp", T.lerp, (A, B, 0.3), lambda a, b, w: a + w * (b - a)),
    Op("maximum", T.maximum, (A, B), np.maximum, grad=False),
    Op("minimum", T.minimum, (A, B), np.minimum, grad=False),
    Op("mod", T.mod, (A, POSA), np.mod, grad=False),
    Op("multiply", T.multiply, (A, B), np.multiply),
    Op("pow", T.pow, (POSA, 2.5), np.power),
    Op("subtract", T.subtract, (A, B), np.subtract),
    Op("gcd", T.gcd, (_i32(4, lo=1, hi=40), _i32(4, seed=3, lo=1, hi=40)),
       np.gcd, grad=False),
    Op("lcm", T.lcm, (_i32(4, lo=1, hi=12), _i32(4, seed=3, lo=1, hi=12)),
       np.lcm, grad=False),
    Op("scale", T.scale, (A,), lambda x: 2.0 * x + 1.0,
       kwargs=dict(scale=2.0, bias=1.0)),
    Op("nan_to_num", T.nan_to_num,
       (np.array([1.0, np.nan, np.inf, -np.inf], np.float32),),
       np.nan_to_num, grad=False),
    # ---- reductions / stats ----
    Op("all", T.all, (_bool(3, 4),), np.all, grad=False),
    Op("any", T.any, (_bool(3, 4),), np.any, grad=False),
    Op("amax", T.amax, (A,), np.max, kwargs=dict(), grad=False),
    Op("amin", T.amin, (A,), np.min, grad=False),
    Op("max", T.max, (A,), np.max, grad=False),
    Op("min", T.min, (A,), np.min, grad=False),
    Op("mean", T.mean, (A,), np.mean),
    Op("mean_axis", T.mean, (A,), lambda x: np.mean(x, 1),
       kwargs=dict(axis=1)),
    Op("median", T.median, (V3,), np.median, grad=False),
    Op("nanmean", T.nanmean,
       (np.array([[1.0, np.nan], [2.0, 3.0]], np.float32),),
       np.nanmean, grad=False),
    Op("nansum", T.nansum,
       (np.array([[1.0, np.nan], [2.0, 3.0]], np.float32),),
       np.nansum, grad=False),
    Op("nanmedian", T.nanmedian,
       (np.array([[1.0, np.nan], [2.0, 3.0]], np.float32),),
       np.nanmedian, grad=False),
    Op("prod", T.prod, (POSA,), np.prod),
    Op("std", T.std, (A,), lambda x: np.std(x, ddof=1), rtol=1e-4,
       atol=1e-4),
    Op("sum", T.sum, (A,), np.sum),
    Op("sum_axis", T.sum, (A,), lambda x: np.sum(x, 0), kwargs=dict(axis=0)),
    Op("var", T.var, (A,), lambda x: np.var(x, ddof=1), rtol=1e-4,
       atol=1e-4),
    Op("logsumexp", T.logsumexp, (A,), sps.logsumexp, rtol=1e-4, atol=1e-4),
    Op("quantile", T.quantile, (V3, 0.5),
       lambda x, q: np.quantile(x, q), grad=False),
    Op("numel", T.numel, (A,), lambda x: np.asarray(x.size), grad=False),
    Op("dist", T.dist, (A, B), lambda a, b: np.linalg.norm(a - b),
       rtol=1e-4, atol=1e-4),
    Op("norm_fro", T.norm, (A,), np.linalg.norm, rtol=1e-4, atol=1e-4),
    Op("logcumsumexp", T.logcumsumexp, (V3,),
       lambda x: np.log(np.cumsum(np.exp(x))), kwargs=dict(axis=0),
       rtol=1e-4, atol=1e-4),
    # ---- cumulative ----
    Op("cumsum", T.cumsum, (A,), lambda x: np.cumsum(x, 1),
       kwargs=dict(axis=1)),
    Op("cumprod", T.cumprod, (POSA,), lambda x: np.cumprod(x, 1),
       kwargs=dict(dim=1)),
    # ---- logic / comparison ----
    Op("allclose", T.allclose, (A, A), np.allclose, grad=False),
    Op("equal", T.equal, (IDX, IDX), np.equal, grad=False),
    Op("equal_all", T.equal_all, (A, A), np.array_equal, grad=False),
    Op("greater_equal", T.greater_equal, (A, B), np.greater_equal,
       grad=False),
    Op("greater_than", T.greater_than, (A, B), np.greater, grad=False),
    Op("isclose", T.isclose, (A, B), np.isclose, grad=False),
    Op("isfinite", T.isfinite, (A,), np.isfinite, grad=False),
    Op("isinf", T.isinf, (A,), np.isinf, grad=False),
    Op("isnan", T.isnan, (A,), np.isnan, grad=False),
    Op("less_equal", T.less_equal, (A, B), np.less_equal, grad=False),
    Op("less_than", T.less_than, (A, B), np.less, grad=False),
    Op("logical_and", T.logical_and, (_bool(3), _bool(3, seed=2)),
       np.logical_and, grad=False),
    Op("logical_not", T.logical_not, (_bool(3),), np.logical_not,
       grad=False),
    Op("logical_or", T.logical_or, (_bool(3), _bool(3, seed=2)),
       np.logical_or, grad=False),
    Op("logical_xor", T.logical_xor, (_bool(3), _bool(3, seed=2)),
       np.logical_xor, grad=False),
    Op("not_equal", T.not_equal, (IDX, np.array([2, 1, 1], np.int32)),
       np.not_equal, grad=False),
    Op("bitwise_and", T.bitwise_and, (_i32(4), _i32(4, seed=2)),
       np.bitwise_and, grad=False),
    Op("bitwise_not", T.bitwise_not, (_i32(4),), np.bitwise_not,
       grad=False),
    Op("bitwise_or", T.bitwise_or, (_i32(4), _i32(4, seed=2)),
       np.bitwise_or, grad=False),
    Op("bitwise_xor", T.bitwise_xor, (_i32(4), _i32(4, seed=2)),
       np.bitwise_xor, grad=False),
    # ---- linalg ----
    Op("matmul", T.matmul, (M34, M45), np.matmul, rtol=1e-4, atol=1e-4),
    Op("mm", T.mm, (M34, M45), np.matmul, rtol=1e-4, atol=1e-4),
    Op("bmm", T.bmm, (_f32(2, 3, 4), _f32(2, 4, 5, seed=2)), np.matmul,
       rtol=1e-4, atol=1e-4),
    Op("dot", T.dot, (V3, _f32(5, seed=6)), np.dot, rtol=1e-4, atol=1e-4),
    Op("mv", T.mv, (M34, _f32(4, seed=9)), np.matmul, rtol=1e-4,
       atol=1e-4),
    Op("inner", T.inner, (V3, _f32(5, seed=6)), np.inner, rtol=1e-4,
       atol=1e-4),
    Op("outer", T.outer, (V3, _f32(5, seed=6)), np.outer, rtol=1e-4,
       atol=1e-4),
    Op("addmm", T.addmm, (_f32(3, 5, seed=3), M34, M45),
       lambda i, a, b: i + a @ b, rtol=1e-4, atol=1e-4),
    Op("cholesky", T.cholesky, (SPD,), np.linalg.cholesky, rtol=1e-4,
       atol=1e-4, grad=False),
    Op("cross", T.cross, (_f32(3, 3), _f32(3, 3, seed=2)),
       lambda a, b: np.cross(a, b), rtol=1e-4, atol=1e-4),
    Op("det", T.det, (SQ,), np.linalg.det, rtol=1e-4, atol=1e-4),
    Op("slogdet", T.slogdet, (SQ,),
       lambda x: tuple(np.linalg.slogdet(x)), rtol=1e-4, atol=1e-4,
       grad=False),
    Op("inv", T.inv, (SPD,), np.linalg.inv, rtol=1e-3, atol=1e-3,
       grad=False),
    Op("kron", T.kron, (_f32(2, 2), _f32(2, 2, seed=2)), np.kron),
    Op("matrix_power", T.matrix_power, (SQ, 3),
       lambda x, n: np.linalg.matrix_power(x, n), rtol=1e-3, atol=1e-3,
       grad=False),
    Op("matrix_rank", T.matrix_rank, (SPD,),
       lambda x: np.linalg.matrix_rank(x), grad=False),
    Op("multi_dot", T.multi_dot, ([M34, M45, _f32(5, 2, seed=3)],),
       lambda ms: np.linalg.multi_dot(ms), rtol=1e-4, atol=1e-4,
       grad=False),
    Op("t", T.t, (M34,), np.transpose),
    Op("trace", T.trace, (SQ,), np.trace),
    Op("solve", T.solve, (SPD, _f32(4, 2, seed=5)), np.linalg.solve,
       rtol=1e-3, atol=1e-3, grad=False),
    Op("triangular_solve", T.triangular_solve,
       (np.tril(SPD).astype(np.float32), _f32(4, 2, seed=5)),
       lambda a, b: np.linalg.solve(a, b), kwargs=dict(upper=False),
       rtol=1e-3, atol=1e-3, grad=False),
    Op("pinv", T.pinv, (M34,), np.linalg.pinv, rtol=1e-3, atol=1e-3,
       grad=False),
    # ---- creation ----
    Op("arange", T.arange, (0, 10, 2), lambda a, b, s: np.arange(a, b, s),
       grad=False),
    Op("eye", T.eye, (3,), lambda n: np.eye(n, dtype=np.float32),
       grad=False),
    Op("full", T.full, ([2, 3], 7.0),
       lambda s, v: np.full(s, v, np.float32), grad=False),
    Op("full_like", T.full_like, (A, 3.0),
       lambda x, v: np.full_like(x, v), grad=False),
    Op("linspace", T.linspace, (0.0, 1.0, 5),
       lambda a, b, n: np.linspace(a, b, n, dtype=np.float32), grad=False),
    Op("ones", T.ones, ([2, 3],),
       lambda s: np.ones(s, np.float32), grad=False),
    Op("ones_like", T.ones_like, (A,), np.ones_like, grad=False),
    Op("zeros", T.zeros, ([2, 3],),
       lambda s: np.zeros(s, np.float32), grad=False),
    Op("zeros_like", T.zeros_like, (A,), np.zeros_like, grad=False),
    Op("diag", T.diag, (V3,), np.diag, grad=False),
    Op("diagflat", T.diagflat, (M34,), np.diagflat, grad=False),
    Op("tril", T.tril, (SQ,), np.tril),
    Op("triu", T.triu, (SQ,), np.triu),
    Op("meshgrid", lambda a, b: T.meshgrid(a, b), (V3, _f32(3, seed=2)),
       lambda a, b: tuple(np.meshgrid(a, b, indexing="ij")), grad=False),
    Op("assign", T.assign, (A,), np.array, grad=False),
    Op("clone", T.clone, (A,), np.array, grad=False),
    Op("to_tensor", T.to_tensor, (A,), np.array, grad=False),
    # ---- manipulation ----
    Op("broadcast_to", T.broadcast_to, (V3, [2, 5]),
       lambda x, s: np.broadcast_to(x, s), grad=False),
    Op("cast", T.cast, (A, "int32"),
       lambda x, d: x.astype(np.int32), grad=False),
    Op("chunk", T.chunk, (_f32(4, 3), 2),
       lambda x, n: tuple(np.split(x, n, 0)), kwargs=dict(axis=0),
       grad=False),
    Op("concat", lambda xs: T.concat(xs, axis=0), ([A, B],),
       lambda xs: np.concatenate(xs, 0), grad=False),
    Op("expand", T.expand, (V3, [2, 5]),
       lambda x, s: np.broadcast_to(x, s), grad=False),
    Op("expand_as", T.expand_as, (V3, _f32(2, 5)),
       lambda x, y: np.broadcast_to(x, y.shape), grad=False),
    Op("flatten", T.flatten, (_f32(2, 3, 4),),
       lambda x: x.reshape(2, 12), kwargs=dict(start_axis=1, stop_axis=2),
       grad=False),
    Op("flip", T.flip, (M34,), lambda x: np.flip(x, 1),
       kwargs=dict(axis=1), grad=False),
    Op("gather", T.gather, (M34, IDX), lambda x, i: x[i], grad=False),
    Op("gather_nd", T.gather_nd, (M34, np.array([[0, 1], [2, 3]], np.int32)),
       lambda x, i: x[tuple(i.T)], grad=False),
    Op("index_select", T.index_select, (M34, IDX),
       lambda x, i: x[i], grad=False),
    Op("index_sample", T.index_sample,
       (M34, np.array([[0, 1], [2, 3], [1, 0]], np.int32)),
       lambda x, i: np.take_along_axis(x, i, 1), grad=False),
    Op("masked_fill", T.masked_fill, (A, _bool(3, 4), 0.0),
       lambda x, m, v: np.where(m, v, x), grad=False),
    Op("masked_select", T.masked_select, (A, A > 0),
       lambda x, m: x[m], grad=False, jit=False),
    Op("moveaxis", T.moveaxis, (_f32(2, 3, 4), 0, 2),
       lambda x, s, d: np.moveaxis(x, s, d), grad=False),
    Op("repeat_interleave", T.repeat_interleave, (V3, 2),
       lambda x, r: np.repeat(x, r), grad=False),
    Op("reshape", T.reshape, (M34, [4, 3]),
       lambda x, s: x.reshape(s), grad=False),
    Op("roll", T.roll, (M34, 1), lambda x, s: np.roll(x, s), grad=False),
    Op("rot90", T.rot90, (M34,), lambda x: np.rot90(x), grad=False),
    Op("slice", T.slice, (M34, [0, 1], [0, 1], [2, 3]),
       lambda x, ax, st, en: x[0:2, 1:3], grad=False),
    Op("split", lambda x: T.split(x, 2, axis=0), (_f32(4, 3),),
       lambda x: tuple(np.split(x, 2, 0)), grad=False),
    Op("squeeze", T.squeeze, (_f32(1, 3, 1),),
       lambda x: np.squeeze(x), grad=False),
    Op("stack", lambda xs: T.stack(xs, axis=0), ([A, B],),
       lambda xs: np.stack(xs, 0), grad=False),
    Op("strided_slice", T.strided_slice, (M34, [1], [0], [4], [2]),
       lambda x, ax, st, en, sd: x[:, 0:4:2], grad=False),
    Op("swapaxes", T.swapaxes, (_f32(2, 3, 4), 0, 1),
       lambda x, a, b: np.swapaxes(x, a, b), grad=False),
    Op("take_along_axis", T.take_along_axis,
       (M34, np.array([[0], [1], [2]], np.int32), 1),
       lambda x, i, a: np.take_along_axis(x, i, a), grad=False),
    Op("tile", T.tile, (M34, [2, 1]), lambda x, r: np.tile(x, r),
       grad=False),
    Op("transpose", T.transpose, (_f32(2, 3, 4), [2, 0, 1]),
       lambda x, p: np.transpose(x, p), grad=False),
    Op("unbind", T.unbind, (_f32(3, 2),),
       lambda x: tuple(x[i] for i in range(3)), grad=False),
    Op("unsqueeze", T.unsqueeze, (V3, 0),
       lambda x, a: np.expand_dims(x, a), grad=False),
    Op("unstack", T.unstack, (_f32(3, 2),),
       lambda x: tuple(x[i] for i in range(3)), grad=False),
    Op("atleast_1d", T.atleast_1d, (np.float32(3.0),),
       np.atleast_1d, grad=False),
    Op("atleast_2d", T.atleast_2d, (V3,), np.atleast_2d, grad=False),
    Op("atleast_3d", T.atleast_3d, (M34,), np.atleast_3d, grad=False),
    Op("as_complex", T.as_complex, (_f32(3, 2),),
       lambda x: x[..., 0] + 1j * x[..., 1], grad=False),
    Op("as_real", T.as_real,
       ((_f32(3) + 1j * _f32(3, seed=2)).astype(np.complex64),),
       lambda x: np.stack([x.real, x.imag], -1), grad=False),
    Op("diff", T.diff, (V3,), np.diff, grad=False),
    Op("clip", T.clip, (A, -1.0, 1.0),
       lambda x, lo, hi: np.clip(x, lo, hi), grad=False),
    # ---- search / sort ----
    Op("argmax", T.argmax, (M34,), np.argmax, grad=False),
    Op("argmin", T.argmin, (M34,), np.argmin, grad=False),
    Op("argsort", T.argsort, (V3,), np.argsort, grad=False),
    Op("sort", T.sort, (V3,), np.sort, grad=False),
    Op("nonzero", T.nonzero, (np.array([0, 1, 0, 2], np.float32),),
       lambda x: np.argwhere(x), grad=False, jit=False),
    Op("searchsorted", T.searchsorted,
       (np.array([1.0, 3.0, 5.0], np.float32), np.array([2.0], np.float32)),
       lambda a, v: np.searchsorted(a, v), grad=False),
    Op("bucketize", T.bucketize,
       (np.array([2.0], np.float32), np.array([1.0, 3.0, 5.0], np.float32)),
       lambda v, edges: np.searchsorted(edges, v), grad=False),
    Op("topk", T.topk, (V3, 2),
       lambda x, k: (np.sort(x)[::-1][:k].copy(),
                     np.argsort(-x)[:k].copy()), grad=False),
    Op("kthvalue", T.kthvalue, (V3, 2),
       lambda x, k: (np.partition(x, k - 1)[k - 1],
                     np.argsort(x)[k - 1]), grad=False),
    Op("mode", T.mode, (np.array([[1.0, 2.0, 2.0]], np.float32),),
       lambda x: (np.array([2.0], np.float32), np.array([2])),
       grad=False),
    Op("where", T.where, (A > 0, A, B), np.where, grad=False),
    Op("bincount", T.bincount, (_i32(10, hi=5),),
       lambda x: np.bincount(x, minlength=0), grad=False, jit=False),
    Op("histogram", T.histogram, (V3,),
       lambda x: np.histogram(x, bins=100, range=(x.min(), x.max()))[0],
       grad=False),
    Op("unique", T.unique, (np.array([3, 1, 2, 1, 3], np.int32),),
       lambda x: np.unique(x), grad=False, jit=False),
    Op("index_put", T.index_put,
       (A, (np.array([0, 1]),), _f32(2, 4, seed=21)),
       lambda x, i, v: _np_index_put(x, i, v), grad=False),
    Op("put_along_axis", T.put_along_axis,
       (M34, np.array([[0], [1], [2]], np.int32),
        np.array([[9.0], [8.0], [7.0]], np.float32), 1),
       lambda x, i, v, a: _np_put_along(x, i, v, a), grad=False),
    Op("scatter", T.scatter,
       (M34, np.array([2, 0], np.int32), _f32(2, 4, seed=9)),
       lambda x, i, u: _np_scatter(x, i, u), grad=False),
    Op("scatter_nd_add", T.scatter_nd_add,
       (M34, np.array([[0], [2], [0]], np.int32), _f32(3, 4, seed=9)),
       lambda x, i, u: _np_scatter_nd_add(x, i, u), grad=False),
    Op("multiplex", T.multiplex,
       ([M34, B], np.array([0, 1, 0], np.int32)),
       lambda xs, i: np.stack([xs[i[r]][r] for r in range(len(i))]),
       grad=False),
    # ---- nn.functional ----
    Op("relu", F.relu, (A,), lambda x: np.maximum(x, 0), grad=False),
    Op("relu6", F.relu6, (A,), lambda x: np.clip(x, 0, 6), grad=False),
    Op("elu", F.elu, (A,),
       lambda x: np.where(x > 0, x, np.expm1(x)), rtol=1e-4, atol=1e-4),
    Op("selu", F.selu, (A,),
       lambda x: 1.0507009873554805 * np.where(
           x > 0, x, 1.6732632423543772 * np.expm1(x)),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("gelu", F.gelu, (A,),
       lambda x: x * 0.5 * (1 + sps.erf(x / np.sqrt(2))), rtol=1e-4,
       atol=1e-4),
    Op("sigmoid", F.sigmoid, (A,), sps.expit),
    Op("silu", F.silu, (A,), lambda x: x * sps.expit(x)),
    Op("swish", F.swish, (A,), lambda x: x * sps.expit(x)),
    Op("mish", F.mish, (A,),
       lambda x: x * np.tanh(np.log1p(np.exp(x))), rtol=1e-4, atol=1e-4),
    Op("softplus", F.softplus, (A,), lambda x: np.log1p(np.exp(x)),
       rtol=1e-4, atol=1e-4),
    Op("hardsigmoid", F.hardsigmoid, (A,),
       lambda x: np.clip(x / 6 + 0.5, 0, 1), grad=False),
    Op("hardswish", F.hardswish, (A,),
       lambda x: x * np.clip(x + 3, 0, 6) / 6, grad=False),
    Op("leaky_relu", F.leaky_relu, (A,),
       lambda x: np.where(x > 0, x, 0.01 * x), grad=False),
    Op("log_softmax", F.log_softmax, (A,),
       lambda x: x - sps.logsumexp(x, 1, keepdims=True),
       kwargs=dict(axis=-1), rtol=1e-4, atol=1e-4),
    Op("softmax", F.softmax, (A,), lambda x: sps.softmax(x, 1),
       kwargs=dict(axis=-1), rtol=1e-4, atol=1e-4),
    Op("glu", F.glu, (_f32(3, 6),),
       lambda x: x[:, :3] * sps.expit(x[:, 3:]), rtol=1e-4, atol=1e-4),
    Op("one_hot", F.one_hot, (IDX, 4),
       lambda x, n: np.eye(n, dtype=np.float32)[x], grad=False),
    Op("normalize", F.normalize, (A,),
       lambda x: x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True),
                                1e-12),
       rtol=1e-4, atol=1e-4),
    Op("cosine_similarity", F.cosine_similarity, (A, B),
       lambda a, b: np.sum(a * b, 1) / np.maximum(
           np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1), 1e-8),
       rtol=1e-4, atol=1e-4),
    Op("linear", F.linear, (M34, M45, _f32(5, seed=3)),
       lambda x, w, b: x @ w + b, rtol=1e-4, atol=1e-4),
    Op("embedding_f", F.embedding, (IDX, _f32(6, 4)),
       lambda i, w: w[i], grad_argnums=(1,)),
    Op("mse_loss", F.mse_loss, (A, B), lambda a, b: np.mean((a - b) ** 2)),
    Op("l1_loss", F.l1_loss, (A, B),
       lambda a, b: np.mean(np.abs(a - b)), grad=False),
    Op("smooth_l1_loss", F.smooth_l1_loss, (A, B),
       lambda a, b: np.mean(np.where(np.abs(a - b) < 1.0,
                                     0.5 * (a - b) ** 2,
                                     np.abs(a - b) - 0.5)),
       grad=False),
    Op("kl_div", F.kl_div,
       (np.log(sps.softmax(_f32(3, 4, seed=11), 1)),
        sps.softmax(_f32(3, 4, seed=12), 1)),
       lambda lp, t: np.mean(t * (np.log(np.clip(t, 1e-12, None)) - lp)),
       kwargs=dict(reduction="mean"), rtol=1e-4, atol=1e-4,
       grad_argnums=(0,)),
    Op("nll_loss", F.nll_loss,
       (np.log(sps.softmax(_f32(3, 4, seed=11), 1)), IDX),
       lambda lp, t: -np.mean(lp[np.arange(3), t]), rtol=1e-4, atol=1e-4,
       grad_argnums=(0,)),
    Op("binary_cross_entropy_with_logits",
       F.binary_cross_entropy_with_logits, (A, (_bool(3, 4)).astype(np.float32)),
       lambda x, t: np.mean(
           np.maximum(x, 0) - x * t + np.log1p(np.exp(-np.abs(x)))),
       rtol=1e-4, atol=1e-4, grad_argnums=(0,)),
    Op("cross_entropy", F.cross_entropy, (_f32(3, 5, seed=13), _i32(3, hi=5)),
       lambda x, t: -np.mean(
           (x - sps.logsumexp(x, 1, keepdims=True))[np.arange(3), t]),
       rtol=1e-4, atol=1e-4, grad_argnums=(0,)),
    Op("label_smooth", F.label_smooth,
       (np.eye(4, dtype=np.float32)[IDX],),
       lambda l: 0.9 * l + 0.1 / 4, kwargs=dict(epsilon=0.1)),
    Op("pad", F.pad, (M34, [1, 1, 0, 2]),
       lambda x, p: np.pad(x, ((0, 2), (1, 1))), grad=False),
    Op("dropout_eval", F.dropout, (A, 0.5),
       lambda x, p: x, kwargs=dict(training=False), grad=False),
    Op("layer_norm", F.layer_norm,
       (A, 4, _pos(4, seed=14), _f32(4, seed=15)),
       lambda x, n, w, b: ((x - x.mean(-1, keepdims=True)) /
                           np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b),
       rtol=1e-3, atol=1e-3, grad_argnums=(0, 2, 3)),
    Op("rms_norm", F.rms_norm, (A, _pos(4, seed=14)),
       lambda x, w: x / np.sqrt(np.mean(x ** 2, -1, keepdims=True) +
                                1e-6) * w,
       rtol=1e-3, atol=1e-3),
    Op("softmax_with_cross_entropy", F.softmax_with_cross_entropy,
       (_f32(3, 5, seed=13), _i32(3, 1, hi=5)),
       lambda x, t: -np.take_along_axis(
           x - sps.logsumexp(x, 1, keepdims=True), t, 1),
       rtol=1e-4, atol=1e-4, grad_argnums=(0,)),
    # ---- wave 2: math ----
    Op("acosh", T.acosh, (_pos(3, 4, lo=1.1, hi=4.0),), np.arccosh),
    Op("asinh", T.asinh, (A,), np.arcsinh),
    Op("atanh", T.atanh, (SMALL,), np.arctanh),
    Op("nextafter", T.nextafter, (A, _f32(3, 4, seed=21)), np.nextafter,
       grad=False),
    Op("remainder", T.remainder, (A, POSA), np.mod, grad=False),
    Op("copysign", T.copysign, (A, _f32(3, 4, seed=22)), np.copysign,
       grad=False),
    Op("hypot", T.hypot, (A, _f32(3, 4, seed=23)), np.hypot),
    Op("ldexp", T.ldexp, (A, _i32(3, 4, lo=-3, hi=3)), np.ldexp,
       grad=False),
    Op("i0", T.i0, (SMALL,), sps.i0, rtol=1e-4, atol=1e-4),
    Op("i0e", T.i0e, (SMALL,), sps.i0e, rtol=1e-4, atol=1e-4),
    Op("i1", T.i1, (SMALL,), sps.i1, rtol=1e-4, atol=1e-4),
    Op("i1e", T.i1e, (SMALL,), sps.i1e, rtol=1e-4, atol=1e-4),
    Op("polygamma", T.polygamma, (POSA,),
       lambda x: sps.polygamma(1, x), kwargs={"n": 1},
       rtol=1e-3, atol=1e-3, grad=False),
    Op("cummax", T.cummax, (A,),
       lambda x: (np.maximum.accumulate(x.reshape(-1)),
                  np.array([int(np.argmax(x.reshape(-1)[:i + 1]))
                            for i in range(x.size)])),
       grad=False),
    Op("cummin", T.cummin, (A,),
       lambda x: (np.minimum.accumulate(x.reshape(-1)),
                  np.array([int(np.argmin(x.reshape(-1)[:i + 1]))
                            for i in range(x.size)])),
       grad=False),
    Op("renorm", T.renorm, (_f32(3, 4, seed=24),),
       kwargs={"p": 2.0, "axis": 0, "max_norm": 1.0},
       ref=lambda x: x * np.minimum(
           1.0, 1.0 / (np.sqrt((x ** 2).sum(1, keepdims=True)) + 1e-7)),
       rtol=1e-4, atol=1e-4),
    Op("add_n", T.add_n, ([A, POSA, _f32(3, 4, seed=50)],),
       lambda xs: xs[0] + xs[1] + xs[2]),
    Op("complex", T.complex, (A, _f32(3, 4, seed=25)),
       lambda re, im: re + 1j * im, grad=False),
    Op("real", T.real, (A,), lambda x: np.real(x), grad=False),
    Op("imag_of_complex",
       lambda re, im: T.imag(T.complex(re, im)), (A, _f32(3, 4, seed=26)),
       lambda re, im: im, grad=False),
    Op("conj", T.conj, (A,), np.conj, grad=False),
    # ---- wave 2: manipulation / creation ----
    Op("diagonal", T.diagonal, (_f32(4, 4, seed=27),),
       lambda x: np.diagonal(x), grad=False),
    Op("diag_embed", T.diag_embed, (_f32(2, 3, seed=28),),
       lambda x: np.stack([np.diag(r) for r in x]), grad=False),
    Op("fill_diagonal", T.fill_diagonal, (_f32(4, 4, seed=29), 7.0),
       lambda x, v: (lambda y: (np.fill_diagonal(y, v), y)[1])(x.copy()),
       grad=False),
    Op("index_add", T.index_add,
       (_f32(5, 3, seed=30), np.array([0, 2, 0]), 0, _f32(3, 3, seed=31)),
       lambda x, i, ax, v: (lambda y: (np.add.at(y, i, v), y)[1])(x.copy()),
       grad=False),
    Op("index_fill", T.index_fill,
       (_f32(5, 3, seed=32), np.array([1, 3]), 0, 9.0),
       lambda x, i, ax, v: (lambda y: (y.__setitem__(i, v), y)[1])(x.copy()),
       grad=False),
    Op("reverse", T.reverse, (A,), lambda x: x[::-1], kwargs={"axis": 0},
       grad=False),
    Op("crop", T.crop, (_f32(4, 5, seed=33),),
       kwargs={"shape": [2, 3], "offsets": [1, 1]},
       ref=lambda x: x[1:3, 1:4], grad=False),
    Op("logspace", T.logspace, (0.0, 3.0, 7),
       lambda a, b, n: np.logspace(a, b, n), rtol=1e-4, grad=False),
    Op("vander", T.vander, (_pos(4, seed=34),),
       lambda x: np.vander(x), rtol=1e-4, grad=False),
    Op("tril_indices", T.tril_indices, (4,),
       lambda n: np.stack(np.tril_indices(n)), grad=False),
    Op("triu_indices", T.triu_indices, (4,),
       lambda n: np.stack(np.triu_indices(n)), grad=False),
    Op("unique_consecutive", T.unique_consecutive,
       (np.array([1, 1, 2, 2, 2, 3, 1, 1]),),
       lambda x: np.array([1, 2, 3, 1]), jit=False, grad=False),
    # ---- wave 2: linalg ----
    Op("eigvalsh", paddle.linalg.eigvalsh,
       ((lambda a: a @ a.T + 3 * np.eye(4, dtype=np.float32))(
           _f32(4, 4, seed=35)),),
       lambda a: np.linalg.eigvalsh(a), rtol=1e-3, atol=1e-3, grad=False),
    Op("cholesky_solve", paddle.linalg.cholesky_solve,
       (_f32(4, 2, seed=36),
        np.linalg.cholesky(
            (lambda a: a @ a.T + 3 * np.eye(4))(
                _rng(37).normal(size=(4, 4))).astype(np.float32)).astype(
                    np.float32)),
       lambda b, L: np.linalg.solve(L @ L.T, b),
       rtol=1e-3, atol=1e-3, grad=False),
    # ---- wave 2: fft ----
    Op("fft_roundtrip", lambda x: paddle.fft.ifft(paddle.fft.fft(x)),
       (_f32(8, seed=38),), lambda x: x.astype(np.complex64),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("rfft", paddle.fft.rfft, (_f32(8, seed=39),),
       lambda x: np.fft.rfft(x).astype(np.complex64),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("fft2", paddle.fft.fft2, (_f32(4, 4, seed=40),),
       lambda x: np.fft.fft2(x).astype(np.complex64),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("fftshift", paddle.fft.fftshift, (_f32(5, seed=41),),
       np.fft.fftshift, grad=False),
    # ---- wave 2: activations ----
    Op("celu", F.celu, (A,),
       lambda x: np.maximum(x, 0) + np.minimum(0, np.expm1(x))),
    Op("hardshrink", F.hardshrink, (A,),
       lambda x: np.where(np.abs(x) > 0.5, x, 0.0), grad=False),
    Op("hardtanh", F.hardtanh, (A,), lambda x: np.clip(x, -1, 1),
       grad=False),
    Op("softshrink", F.softshrink, (A,),
       lambda x: np.where(x > 0.5, x - 0.5,
                          np.where(x < -0.5, x + 0.5, 0.0)), grad=False),
    Op("softsign", F.softsign, (A,), lambda x: x / (1 + np.abs(x))),
    Op("tanhshrink", F.tanhshrink, (A,), lambda x: x - np.tanh(x)),
    Op("thresholded_relu", F.thresholded_relu, (A,),
       lambda x: np.where(x > 1.0, x, 0.0), grad=False),
    Op("log_sigmoid", F.log_sigmoid, (A,),
       lambda x: -np.log1p(np.exp(-x))),
    Op("maxout", F.maxout, (_f32(2, 6, 3, seed=42),),
       kwargs={"groups": 2},
       ref=lambda x: x.reshape(2, 3, 2, 3).max(2), grad=False),
    Op("prelu", F.prelu, (A, np.float32(0.2)),
       lambda x, w: np.where(x >= 0, x, w * x), grad_argnums=(0,)),
    # ---- wave 2: losses ----
    Op("binary_cross_entropy", F.binary_cross_entropy,
       (_pos(6, lo=0.05, hi=0.95, seed=43),
        _i32(6, hi=2).astype(np.float32)),
       lambda p, y: np.mean(-(y * np.log(p + 1e-12)
                              + (1 - y) * np.log(1 - p + 1e-12))),
       rtol=1e-4, atol=1e-4, grad_argnums=(0,)),
    Op("square_error_cost", F.square_error_cost, (A, POSA),
       lambda a, b: (a - b) ** 2),
    Op("log_loss", F.log_loss,
       (_pos(6, lo=0.05, hi=0.95, seed=44),
        _i32(6, hi=2).astype(np.float32)),
       lambda p, y: -(y * np.log(p + 1e-4)
                      + (1 - y) * np.log(1 - p + 1e-4)),
       rtol=1e-4, atol=1e-4, grad_argnums=(0,)),
    # ---- wave 2: geometry ----
    Op("pixel_shuffle", F.pixel_shuffle, (_f32(1, 4, 2, 2, seed=45),),
       kwargs={"upscale_factor": 2},
       ref=lambda x: x.reshape(1, 1, 2, 2, 2, 2).transpose(
           0, 1, 4, 2, 5, 3).reshape(1, 1, 4, 4), grad=False),
    Op("channel_shuffle", F.channel_shuffle, (_f32(1, 6, 2, 2, seed=46),),
       kwargs={"groups": 2},
       ref=lambda x: x.reshape(1, 2, 3, 2, 2).transpose(
           0, 2, 1, 3, 4).reshape(1, 6, 2, 2), grad=False),
    # ---- round-3 tail (VERDICT r2 missing-op probe) ----
    Op("cov", T.cov, (_f32(3, 8),),
       lambda x: np.cov(x), rtol=1e-4, atol=1e-4),
    Op("cov_colvar", T.cov, (_f32(6, 3),), lambda x: np.cov(x, rowvar=False),
       kwargs={"rowvar": False}, rtol=1e-4, atol=1e-4),
    Op("corrcoef", T.corrcoef, (_f32(3, 10),),
       lambda x: np.corrcoef(x), rtol=1e-4, atol=1e-4, grad=False),
    Op("matrix_exp", T.matrix_exp, (_f32(4, 4, lo=-0.5, hi=0.5),),
       lambda x: __import__("scipy.linalg", fromlist=["expm"]).expm(x),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("pdist", T.pdist, (_f32(5, 3),),
       lambda x: __import__("scipy.spatial.distance",
                            fromlist=["pdist"]).pdist(x),
       rtol=1e-4, atol=1e-4),
    Op("pdist_p1", T.pdist, (_f32(5, 3),), kwargs={"p": 1.0},
       ref=lambda x: __import__("scipy.spatial.distance",
                                fromlist=["pdist"]).pdist(x, "minkowski",
                                                          p=1.0),
       rtol=1e-4, atol=1e-4),
    Op("masked_scatter", T.masked_scatter,
       (_f32(3, 4), _rng(1).integers(0, 2, (3, 4)).astype(bool),
        _f32(12, seed=2)),
       lambda x, m, v: np.where(
           m, np.where(m.reshape(-1),
                       v.reshape(-1)[np.clip(
                           np.cumsum(m.reshape(-1)) - 1, 0, 11)],
                       x.reshape(-1)).reshape(x.shape), x),
       grad=False),
    Op("igamma", T.igamma, (_pos(8), _pos(8, seed=3)),
       lambda a, x: __import__("scipy.special",
                               fromlist=["gammaincc"]).gammaincc(a, x),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("igammac", T.igammac, (_pos(8), _pos(8, seed=3)),
       lambda a, x: __import__("scipy.special",
                               fromlist=["gammainc"]).gammainc(a, x),
       rtol=1e-4, atol=1e-4, grad=False),
    Op("multigammaln", T.multigammaln, (_pos(6, lo=2.0, hi=6.0),),
       lambda x: __import__("scipy.special",
                            fromlist=["multigammaln"]).multigammaln(x, 3),
       kwargs={"p": 3}, rtol=1e-4, atol=1e-4),
]


def _np_index_put(x, idx, v):
    y = x.copy()
    y[idx] = v
    return y


def _np_put_along(x, i, v, a):
    y = x.copy()
    np.put_along_axis(y, i, v, a)
    return y


def _np_scatter(x, i, u):
    y = x.copy()
    y[i] = u
    return y


def _np_scatter_nd_add(x, i, u):
    y = x.copy()
    for r in range(i.shape[0]):
        y[tuple(i[r])] += u[r]
    return y


_BY_NAME = {s.name: s for s in OPS}
assert len(_BY_NAME) == len(OPS), "duplicate op spec names"


@pytest.mark.parametrize("name", sorted(_BY_NAME))
def test_op_forward(name):
    _check_forward(_BY_NAME[name])


GRAD_OPS = sorted(s.name for s in OPS if s.grad)


@pytest.mark.parametrize("name", GRAD_OPS)
def test_op_grad(name):
    _check_grad(_BY_NAME[name])


def test_coverage_count():
    """The sweep must keep covering a broad slice of the op surface."""
    assert len(OPS) >= 150, f"only {len(OPS)} op specs"


def test_householder_product_reconstructs_q():
    import scipy.linalg as sl
    rng = np.random.default_rng(0)
    a = rng.standard_normal((6, 4)).astype(np.float64)
    (qr_raw, tau), _r = sl.qr(a, mode="raw")
    q_ref = sl.qr(a, mode="economic")[0]
    got = np.asarray(T.householder_product(
        jnp.asarray(qr_raw, jnp.float32), jnp.asarray(tau, jnp.float32)))
    # Q columns are sign-fixed by the factorization — direct compare works
    np.testing.assert_allclose(got, q_ref, rtol=1e-4, atol=1e-4)
    # orthonormal columns
    np.testing.assert_allclose(got.T @ got, np.eye(4), atol=1e-4)


def test_householder_product_batched():
    import scipy.linalg as sl
    rng = np.random.default_rng(1)
    a = rng.standard_normal((3, 5, 3)).astype(np.float64)
    qrs, taus, refs = [], [], []
    for i in range(3):
        (qr_raw, tau), _r = sl.qr(a[i], mode="raw")
        qrs.append(qr_raw); taus.append(tau)
        refs.append(sl.qr(a[i], mode="economic")[0])
    got = np.asarray(T.householder_product(
        jnp.asarray(np.stack(qrs), jnp.float32),
        jnp.asarray(np.stack(taus), jnp.float32)))
    np.testing.assert_allclose(got, np.stack(refs), rtol=1e-4, atol=1e-4)
