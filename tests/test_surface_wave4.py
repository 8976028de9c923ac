"""Round-3 surface-completion wave: nn.functional wave 4, distributed
compat tail, linalg cond/pca_lowrank, Adamax/Adadelta/LBFGS."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn import functional as F


class TestFunctionalWave4:
    def test_pairwise_distance(self):
        x = jnp.asarray([[1.0, 2.0]]); y = jnp.asarray([[4.0, 6.0]])
        np.testing.assert_allclose(np.asarray(F.pairwise_distance(x, y)),
                                   [5.0], rtol=1e-4)

    def test_diag_embed(self):
        out = F.diag_embed(jnp.asarray([[1.0, 2.0], [3.0, 4.0]]))
        assert out.shape == (2, 2, 2)
        np.testing.assert_allclose(np.asarray(out[0]), np.diag([1.0, 2.0]))

    def test_dropout2d_drops_whole_channels(self):
        paddle.seed(0)
        x = jnp.ones((4, 8, 5, 5))
        out = np.asarray(F.dropout2d(x, 0.5, training=True))
        per_channel = out.reshape(4, 8, -1)
        for nc in per_channel.reshape(-1, 25):
            assert (nc == 0).all() or (nc != 0).all()

    def test_alpha_dropout_preserves_moments(self):
        paddle.seed(3)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(20000),
                        jnp.float32)
        out = np.asarray(F.alpha_dropout(x, 0.3, training=True))
        assert abs(out.mean()) < 0.1
        assert abs(out.std() - 1.0) < 0.1

    def test_bilinear_matches_layer_math(self):
        rng = np.random.default_rng(0)
        x1 = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
        x2 = jnp.asarray(rng.standard_normal((3, 5)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((2, 4, 5)), jnp.float32)
        out = F.bilinear(x1, x2, w)
        ref = np.einsum("bi,oij,bj->bo", x1, w, x2)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4)

    def test_max_unpool1d_roundtrip(self):
        x = jnp.asarray([[[1.0, 3.0, 2.0, 8.0]]])
        pooled, idx = F.max_pool2d_with_index(
            x[:, :, None, :], kernel_size=(1, 2), stride=(1, 2)) \
            if hasattr(F, "max_pool2d_with_index") else (None, None)
        # direct: use known indices
        up = F.max_unpool1d(jnp.asarray([[[3.0, 8.0]]]),
                            jnp.asarray([[[1, 3]]]), kernel_size=2)
        np.testing.assert_allclose(np.asarray(up),
                                   [[[0.0, 3.0, 0.0, 8.0]]])

    def test_adaptive_max_pools(self):
        x = jnp.asarray(np.arange(16, dtype=np.float32).reshape(1, 1, 16))
        out = F.adaptive_max_pool1d(x, 4)
        np.testing.assert_allclose(np.asarray(out[0, 0]),
                                   [3.0, 7.0, 11.0, 15.0])
        x2 = jnp.asarray(np.arange(36, dtype=np.float32).reshape(1, 1, 6, 6))
        out2, mask = F.adaptive_max_pool2d(x2, 2, return_mask=True)
        np.testing.assert_allclose(np.asarray(out2[0, 0]),
                                   [[14.0, 17.0], [32.0, 35.0]])
        assert int(mask[0, 0, 1, 1]) == 35

    def test_sigmoid_focal_loss_reduces_easy_examples(self):
        logit = jnp.asarray([4.0, -4.0])
        label = jnp.asarray([1.0, 0.0])
        easy = float(F.sigmoid_focal_loss(logit, label))
        hard = float(F.sigmoid_focal_loss(-logit, label))
        assert easy < hard

    def test_multi_margin_and_gaussian_nll(self):
        x = jnp.asarray([[0.1, 0.9, 0.2]])
        lbl = jnp.asarray([1])
        assert float(F.multi_margin_loss(x, lbl)) >= 0
        g = F.gaussian_nll_loss(jnp.asarray([1.0]), jnp.asarray([1.0]),
                                jnp.asarray([1.0]))
        np.testing.assert_allclose(float(g), 0.0, atol=1e-6)

    def test_sparse_attention_matches_dense_on_full_pattern(self):
        rng = np.random.default_rng(0)
        B, H, S, D = 1, 1, 4, 8
        q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
        # full pattern: every row attends all columns
        offset = jnp.asarray(np.arange(0, (S + 1) * S, S).reshape(1, 1, -1))
        cols = jnp.asarray(np.tile(np.arange(S), S).reshape(1, 1, -1))
        out = F.sparse_attention(q, k, v, offset, cols)
        ref = jax.nn.softmax((q @ jnp.swapaxes(k, -1, -2)) /
                             np.sqrt(D)) @ v
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_inplace_aliases_exist(self):
        for n in ("relu_", "tanh_", "softmax_", "elu_"):
            assert callable(getattr(F, n))


class TestDistributedCompat:
    def test_parallel_mode_and_backend(self):
        from paddle_tpu import distributed as dist
        assert dist.ParallelMode.DATA_PARALLEL == 0
        assert dist.is_available()
        assert "XLA" in dist.get_backend()

    def test_entries(self):
        from paddle_tpu import distributed as dist
        assert dist.CountFilterEntry(5)._to_attr() == "count_filter_entry:5"
        with pytest.raises(ValueError):
            dist.ProbabilityEntry(0.0)
        e = dist.ShowClickEntry("show", "click")
        assert "show" in e._to_attr()

    def test_io_roundtrip(self, tmp_path):
        from paddle_tpu import distributed as dist
        from paddle_tpu import nn
        paddle.seed(0)
        net = nn.Linear(3, 2)
        dist.io.save_persistables(net, str(tmp_path))
        sd = dist.io.load_persistables(None, str(tmp_path))
        assert "weight" in sd

    def test_split_linear_column(self):
        from paddle_tpu import distributed as dist
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
        out = dist.split(x, (6, 8), operation="linear", axis=1,
                         num_partitions=1, weight=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5)

    def test_gather_and_wait(self):
        from paddle_tpu import distributed as dist
        x = jnp.ones((2, 3))
        out = dist.wait(x)
        assert out.shape == (2, 3)


class TestLinalgTail:
    def test_cond_matches_numpy(self):
        rng = np.random.default_rng(0)
        a = rng.standard_normal((5, 5)).astype(np.float32)
        for p in (None, "fro", 1, np.inf):
            got = float(paddle.linalg.cond(jnp.asarray(a), p=p))
            want = float(np.linalg.cond(a, p=2 if p is None else p))
            np.testing.assert_allclose(got, want, rtol=1e-3)

    def test_pca_lowrank_reconstructs(self):
        rng = np.random.default_rng(1)
        base = rng.standard_normal((30, 3)) @ rng.standard_normal((3, 10))
        x = jnp.asarray(base, jnp.float32)
        u, s, v = paddle.linalg.pca_lowrank(x, q=3, center=False)
        recon = np.asarray(u) * np.asarray(s) @ np.asarray(v).T
        np.testing.assert_allclose(recon, base, atol=1e-3)


class TestNewOptimizers:
    def _descend(self, opt_cls, lr, steps=60, **kw):
        from paddle_tpu import nn
        from paddle_tpu.framework.functional import (functional_call,
                                                     get_params)
        paddle.seed(0)
        net = nn.Linear(8, 1)
        params = get_params(net)
        rng = np.random.default_rng(0)
        xb = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
        yb = xb @ jnp.arange(1.0, 9.0)[:, None] / 8.0
        opt = opt_cls(learning_rate=lr, **kw)
        st = opt.init(params)

        def loss_fn(p):
            return jnp.mean((functional_call(net, p, xb) - yb) ** 2)

        l0 = float(loss_fn(params))
        for _ in range(steps):
            _, grads = jax.value_and_grad(loss_fn)(params)
            params, st = opt.apply_gradients(params, grads, st, lr)
        return l0, float(loss_fn(params))

    def test_adamax_descends(self):
        l0, l1 = self._descend(paddle.optimizer.Adamax, 0.05)
        assert l1 < 0.5 * l0

    def test_adadelta_descends(self):
        l0, l1 = self._descend(paddle.optimizer.Adadelta, 1.0)
        assert l1 < 0.8 * l0

    def test_lbfgs_converges_on_quadratic(self):
        l0, l1 = self._descend(paddle.optimizer.LBFGS, 0.5,
                               history_size=6, steps=40)
        assert l1 < 1e-6 * l0


class TestReviewFixesWave3:
    def test_orthogonal_via_param_attr(self):
        from paddle_tpu.nn import initializer as I
        paddle.seed(0)
        from paddle_tpu import nn as _nn
        lin = _nn.Linear(4, 4,
                         weight_attr=paddle.ParamAttr(
                             initializer=I.Orthogonal()))
        w = np.asarray(lin.weight)
        np.testing.assert_allclose(w @ w.T, np.eye(4), atol=1e-5)

    def test_weight_norm_registers_trainable_params(self):
        from paddle_tpu import nn as _nn
        paddle.seed(0)
        lin = _nn.Linear(4, 3)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 4)),
                        jnp.float32)
        before = np.asarray(lin(x))
        _nn.utils.weight_norm(lin)
        assert set(lin._parameters) == {"bias", "weight_g", "weight_v"}
        np.testing.assert_allclose(np.asarray(lin(x)), before, atol=1e-5)
        sd = lin.state_dict()
        assert "weight_g" in sd and "weight_v" in sd
        _nn.utils.remove_weight_norm(lin)
        assert "weight" in lin._parameters
        np.testing.assert_allclose(np.asarray(lin(x)), before, atol=1e-5)

    def test_set_global_initializer_honored_and_reset(self):
        from paddle_tpu import nn as _nn
        from paddle_tpu.nn import initializer as I
        I.set_global_initializer(I.Constant(3.5))
        try:
            lin = _nn.Linear(2, 2)
            assert float(np.asarray(lin.weight)[0, 0]) == 3.5
        finally:
            I.set_global_initializer(None)
        paddle.seed(0)
        lin2 = _nn.Linear(2, 2)
        assert float(np.asarray(lin2.weight)[0, 0]) != 3.5


class TestCompatCollectives:
    """Eager stacked-ranks conventions of the compat wrappers."""

    def test_alltoall_list_form(self):
        from paddle_tpu import distributed as dist
        g = dist.world_group()
        n = g.nranks
        # rank s's payload: chunk d carries value 10*s + d
        ins = [jnp.asarray([[10.0 * s + d] for d in range(n)])
               for s in range(n)]
        outs = dist.alltoall(ins)
        assert len(outs) == n
        # rank r receives chunk r of every source: value 10*s + r
        for r, o in enumerate(outs):
            np.testing.assert_allclose(
                np.asarray(o).reshape(-1),
                [10.0 * s + r for s in range(n)])

    def test_gather_fills_list(self):
        from paddle_tpu import distributed as dist
        g = dist.world_group()
        x = jnp.ones((g.nranks, 3))
        bucket = []
        dist.gather(x, gather_list=bucket)
        assert len(bucket) == g.nranks

    def test_alltoall_single_equal_splits_only(self):
        import pytest
        from paddle_tpu import distributed as dist
        with pytest.raises(NotImplementedError):
            dist.alltoall_single(jnp.ones((4, 2)), in_split_sizes=[1, 3])
