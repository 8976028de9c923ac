"""Tests for 3rd-wave nn.functional extension ops and distributed.utils.

Reference anchors: python/paddle/nn/functional/extension.py (sequence_mask
:154, temporal_shift :343), loss.py (dice_loss :35, npair_loss :311,
margin_cross_entropy :2082), common.py (class_center_sample),
distributed/utils/moe_utils.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


class TestSequenceMask:
    def test_basic(self):
        m = F.sequence_mask(jnp.asarray([1, 3]), maxlen=4)
        np.testing.assert_array_equal(
            np.asarray(m), [[1, 0, 0, 0], [1, 1, 1, 0]])
        assert m.dtype == jnp.int64 or m.dtype == jnp.int32

    def test_default_maxlen_and_dtype(self):
        m = F.sequence_mask(jnp.asarray([2, 4]), dtype="float32")
        assert m.shape == (2, 4)
        assert m.dtype == jnp.float32

    def test_batched(self):
        m = F.sequence_mask(jnp.asarray([[1], [2]]), maxlen=3)
        assert m.shape == (2, 1, 3)


class TestTemporalShift:
    def test_shift_semantics(self):
        # 2 segments, 4 channels, shift_ratio 0.25 -> c1=1 backward,
        # c2-c1=1 forward, rest static.
        nt, c, h, w = 2, 4, 1, 1
        x = jnp.arange(nt * c, dtype=jnp.float32).reshape(nt, c, h, w)
        out = F.temporal_shift(x, seg_num=2, shift_ratio=0.25)
        out = np.asarray(out).reshape(nt, c)
        # t=0 channel 0 reads t=-1 -> 0; t=1 channel 0 reads t=0 -> x[0,0]
        assert out[0, 0] == 0.0
        assert out[1, 0] == 0.0  # x[0, 0] = 0
        # channel 1 reads from t+1: t=0 gets x[1,1]=5, t=1 gets 0 (pad)
        assert out[0, 1] == 5.0
        assert out[1, 1] == 0.0
        # static channels unchanged
        np.testing.assert_array_equal(out[:, 2:],
                                      np.asarray(x).reshape(2, 4)[:, 2:])

    def test_nhwc(self):
        x = jnp.ones((4, 2, 2, 8))
        out = F.temporal_shift(x, seg_num=2, data_format="NHWC")
        assert out.shape == x.shape


class TestPixelUnshuffle:
    def test_roundtrip_with_pixel_shuffle(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 3, 8, 8)), jnp.float32)
        down = F.pixel_unshuffle(x, 2)
        assert down.shape == (2, 12, 4, 4)
        back = F.pixel_shuffle(down, 2)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x))

    def test_upsample_alias(self):
        x = jnp.ones((1, 1, 4, 4))
        out = F.upsample(x, scale_factor=2)
        assert out.shape == (1, 1, 8, 8)


class TestLosses3:
    def test_dice_perfect_prediction(self):
        label = jnp.asarray([[0, 1], [1, 0]])
        probs = jax.nn.one_hot(label, 2, dtype=jnp.float32)
        loss = F.dice_loss(probs, label)
        assert float(loss) < 1e-4

    def test_dice_worst(self):
        label = jnp.asarray([[0, 0]])
        probs = jax.nn.one_hot(jnp.asarray([[1, 1]]), 2, dtype=jnp.float32)
        assert float(F.dice_loss(probs, label)) > 0.99

    def test_npair_separable(self):
        """Matching pairs aligned, mismatched orthogonal -> lower loss than
        the reverse arrangement."""
        e = jnp.eye(4, 8)
        labels = jnp.arange(4)
        good = F.npair_loss(e, e, labels, l2_reg=0.0)
        bad = F.npair_loss(e, jnp.roll(e, 1, axis=0), labels, l2_reg=0.0)
        assert float(good) < float(bad)

    def test_margin_ce_margins_increase_loss(self):
        rng = np.random.default_rng(0)
        cos = jnp.clip(jnp.asarray(rng.standard_normal((8, 16)),
                                   jnp.float32), -0.9, 0.9)
        label = jnp.asarray(rng.integers(0, 16, (8,)))
        plain = F.margin_cross_entropy(cos, label, margin1=1.0, margin2=0.0,
                                       margin3=0.0, scale=16.0)
        arc = F.margin_cross_entropy(cos, label, margin1=1.0, margin2=0.5,
                                     margin3=0.0, scale=16.0)
        assert float(arc) > float(plain)

    def test_margin_ce_return_softmax_and_label_col(self):
        cos = jnp.zeros((2, 4))
        loss, sm = F.margin_cross_entropy(cos, jnp.asarray([[1], [2]]),
                                          return_softmax=True)
        assert sm.shape == (2, 4)
        assert bool(jnp.isfinite(loss))


class TestClassCenterSample:
    def test_positives_always_kept(self):
        label = jnp.asarray([5, 17, 5, 99])
        remapped, sampled = F.class_center_sample(label, 100, 10, seed=3)
        sampled = np.asarray(sampled)
        assert {5, 17, 99}.issubset(set(sampled.tolist()))
        assert len(sampled) == 10
        # remapped labels index into sampled
        for orig, rm in zip(np.asarray(label), np.asarray(remapped)):
            assert sampled[rm] == orig

    def test_more_positives_than_samples(self):
        label = jnp.arange(20)
        remapped, sampled = F.class_center_sample(label, 50, 10)
        assert len(np.asarray(sampled)) == 20  # all positives kept


class TestDistributedUtils:
    def test_global_scatter_gather_eager(self):
        x = jnp.arange(12.0).reshape(4, 3)
        out = paddle.distributed.utils.global_scatter(
            x, jnp.asarray([4]), jnp.asarray([4]))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
        back = paddle.distributed.utils.global_gather(
            out, jnp.asarray([4]), jnp.asarray([4]))
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_count_mismatch_raises(self):
        with pytest.raises(ValueError):
            paddle.distributed.utils.global_scatter(
                jnp.ones((4, 3)), jnp.asarray([2]), jnp.asarray([2]))

    def test_counts_in_trace_rejected(self):
        """Ragged count routing cannot be expressed as an equal-split a2a;
        the traced path must refuse rather than misroute."""
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))

        def f(xs):
            return paddle.distributed.utils.global_scatter(
                xs, jnp.asarray([1, 3]), jnp.asarray([2, 2]),
                axis_name="ep")

        with pytest.raises(NotImplementedError, match="capacity"):
            jax.shard_map(f, mesh=mesh, in_specs=P("ep"),
                          out_specs=P("ep"))(jnp.ones((4, 2)))

    def test_global_scatter_in_shard_map(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
        x = jnp.arange(8.0).reshape(4, 2)

        def f(xs):
            return paddle.distributed.utils.global_scatter(
                xs, None, None, axis_name="ep")

        out = jax.shard_map(f, mesh=mesh, in_specs=P("ep"),
                            out_specs=P("ep"))(x)
        # all_to_all over 2 ranks with tiled split: row blocks exchanged
        assert out.shape == x.shape


class TestFusedRmsNorm:
    def test_matches_rms_norm(self):
        from paddle_tpu.incubate.nn.functional import fused_rms_norm
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
        got = fused_rms_norm(x, w, jnp.ones((8,)))
        ref = F.rms_norm(x, w, 1e-6) + 1.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6)

    def test_begin_norm_axis_joint(self):
        """begin_norm_axis=1 on [2,3,4] normalizes over all 12 trailing
        elements jointly (reference semantics), not per-axis."""
        from paddle_tpu.incubate.nn.functional import fused_rms_norm
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 3, 4)), jnp.float32)
        got = fused_rms_norm(x, begin_norm_axis=1)
        flat = np.asarray(x).reshape(2, 12)
        rms = np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(got).reshape(2, 12),
                                   flat / rms, atol=1e-5)


class TestSampleFreshness:
    def test_class_center_sample_varies_without_seed(self):
        label = jnp.asarray([0])
        draws = {tuple(np.asarray(F.class_center_sample(
            label, 1000, 5)[1]).tolist()) for _ in range(6)}
        assert len(draws) > 1  # fresh negatives each call

    def test_class_center_sample_seed_reproducible(self):
        label = jnp.asarray([0])
        a = np.asarray(F.class_center_sample(label, 1000, 5, seed=7)[1])
        b = np.asarray(F.class_center_sample(label, 1000, 5, seed=7)[1])
        np.testing.assert_array_equal(a, b)
