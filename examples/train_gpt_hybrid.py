"""GPT training with hybrid parallelism (BASELINE config 4 shape).

One `jax.sharding.Mesh` carries every axis: data parallel, ZeRO/FSDP
sharding, tensor parallel, and (optionally) sequence/context parallel.
On a single chip the axes collapse to degree 1 and the same jitted step
runs unchanged — run under more devices (or
`XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu`)
to see the sharded version compile.

    python examples/train_gpt_hybrid.py [--dp N] [--mp N] [--sharding N]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import paddle_tpu as paddle
from paddle_tpu.core.chip import enable_compile_cache
from paddle_tpu.distributed.topology import create_hybrid_mesh
from paddle_tpu.framework.functional import functional_call
from paddle_tpu.framework.sharded import make_sharded_train_step
from paddle_tpu.optimizer import AdamW
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=512)
    args = ap.parse_args()

    enable_compile_cache()
    need = args.dp * args.mp * args.sharding
    devices = jax.devices()[:need]
    assert len(devices) == need, \
        f"need {need} devices, have {len(jax.devices())}"
    mesh = create_hybrid_mesh(dp=args.dp, mp=args.mp,
                              sharding=args.sharding, devices=devices)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=8192, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=8,
                    max_position_embeddings=512,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=3e-4, weight_decay=0.01)

    def loss_fn(model, params, batch):
        ids, labels = batch
        return functional_call(model, params, ids, labels, training=True)

    ts = make_sharded_train_step(model, opt, loss_fn, mesh=mesh)

    rng = np.random.default_rng(0)
    batch = max(8, 2 * args.dp * args.sharding)
    for step in range(args.steps):
        ids = rng.integers(0, cfg.vocab_size, (batch, 512), dtype=np.int32)
        labels = np.roll(ids, -1, axis=1)
        loss = ts.step((jnp.asarray(ids), jnp.asarray(labels)))
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
