"""ERNIE model tests (BASELINE config 5 model family)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.text.models.ernie import (ErnieForPretraining, ernie_tiny,
                                          ernie_pipeline_descs)


def test_ernie_pretraining_loss_sane():
    paddle.seed(0)
    cfg = ernie_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model = ErnieForPretraining(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
    sop = jnp.asarray(rng.integers(0, 2, (2,)), jnp.int32)
    loss = model(ids, masked_lm_labels=labels, sop_labels=sop)
    # MLM ~ ln(vocab) + SOP ~ ln(2) at init
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < \
        2.0 * (np.log(cfg.vocab_size) + np.log(2))
    # task-type embedding table exists (the ERNIE-specific piece)
    names = [n for n, _ in model.named_parameters()]
    assert any("task_type_embeddings" in n for n in names)


def test_ernie_pipeline_trains_pp4():
    """Config 5 shape: ERNIE blocks through the compiled pp=4 pipeline."""
    from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import \
        PipelineLayer
    from paddle_tpu.distributed.pipeline_schedule import \
        make_pipeline_train_step
    from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                                 set_hybrid_mesh)
    from paddle_tpu.framework.functional import get_params
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.nn import functional as F

    cfg = ernie_tiny(num_layers=4, hidden_dropout=0.0, attention_dropout=0.0)

    def loss_fn(logits, labels):
        return jnp.mean(F.cross_entropy(logits, labels, reduction="none"))

    def build():
        paddle.seed(4)
        return PipelineLayer(layers=ernie_pipeline_descs(cfg), num_stages=4,
                             loss_fn=loss_fn)

    def train(pl, mesh_kwargs):
        mesh = create_hybrid_mesh(**mesh_kwargs)
        set_hybrid_mesh(mesh)
        opt = AdamW(learning_rate=1e-3)
        step = make_pipeline_train_step(pl, opt, n_microbatch=4)
        params = get_params(pl)
        st = opt.init(params)
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(2):
            ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32)
            labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                                 jnp.int32)
            params, st, loss = step(params, st, ids, labels,
                                    jnp.float32(1e-3))
            losses.append(float(loss))
        set_hybrid_mesh(None)
        return losses

    pp = train(build(), dict(pp=4, dp=2))
    single = train(build(), dict(dp=1, devices=jax.devices()[:1]))
    np.testing.assert_allclose(pp, single, rtol=2e-4)
