"""Deviceless compiles for the real chip: the Pallas kernels of the main
path, at real widths, handed to the TPU's own compiler for a described
(not attached) ``v5e:2x2`` topology. Nothing runs — a pass says the chip's
compiler accepts the program (what interpret mode cannot say), never that
its results or its speed are right.

This is the one file that describes the topology. It does so inside a
module-scoped fixture (only the xdist worker that is handed this file
loads the TPU library), never at import; the persistent compile cache is
off around these compiles (a deviceless executable cannot be read back).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` on ShapeDtypeStructs placed on the described chip and
    compile it; returns the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _qkv(b, s, h, d, dtype=jnp.bfloat16):
    return [((b, s, h, d), dtype)] * 3


# GPT-1.3B attention shape (per-head kernel, d=128) and the BERT-base
# shape (head-packed kernel, d=64).
FLASH_SHAPES = {"gpt13b_d128": (4, 2048, 16, 128, True),
                "bert_d64_packed": (64, 512, 12, 64, False)}


@pytest.mark.parametrize("which", sorted(FLASH_SHAPES))
@pytest.mark.parametrize("pass_", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, which, pass_):
    from paddle_tpu.ops._pallas.flash_attention import flash_attention_pallas
    b, s, h, d, causal = FLASH_SHAPES[which]

    def fwd(q, k, v):
        return flash_attention_pallas(q, k, v, causal=causal)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if pass_ == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _compile(fn, one_chip, *_qkv(b, s, h, d))
    # fwd is one custom call; bwd re-runs fwd and adds the backward
    # kernel(s) (dq and dk/dv apart at d=128, one at packed d=64)
    assert text.count("tpu_custom_call") >= (1 if pass_ == "fwd" else 2)


@pytest.mark.parametrize("variant", ["segment_ids", "dropout"])
@pytest.mark.parametrize("pass_", ["fwd", "bwd"])
def test_flash_variants_compile(one_chip, variant, pass_):
    """The packed-varlen (flash_attn_unpadded's segment ids) and the
    attention-prob dropout variants of the d=128 kernel."""
    from paddle_tpu.ops._pallas.flash_attention import flash_attention_pallas
    b, s, h, d = 4, 2048, 16, 128

    def fwd(q, k, v, extra):
        if variant == "segment_ids":
            return flash_attention_pallas(q, k, v, causal=True,
                                          segment_ids=extra)
        return flash_attention_pallas(q, k, v, causal=True, dropout=0.1,
                                      dropout_seed=extra)

    def loss(q, k, v, extra):
        return fwd(q, k, v, extra).astype(jnp.float32).sum()

    fn = fwd if pass_ == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    extra = ((b, s), jnp.int32) if variant == "segment_ids" \
        else ((1,), jnp.int32)
    text = _compile(fn, one_chip, *_qkv(b, s, h, d), extra)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("causal", [False, True])
def test_ring_hop_flash_compiles(one_chip, causal):
    """The (o, lse) call one ring-attention hop makes on its resident KV
    block (context_parallel: S=2048 over a 4-way ring -> 512 per rank),
    forward and backward through the lse cotangent."""
    from paddle_tpu.ops._pallas.flash_attention import (
        flash_attention_with_lse)

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=causal)
        return o.astype(jnp.float32).sum() + lse.sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
                    *_qkv(4, 512, 16, 128))
    assert text.count("tpu_custom_call") >= 2


# ResNet-50 stage-1 1x1 shapes (batch 256, 56x56): reduce 256->64 and
# expand 64->256, as RESNET50_TOP3_SHAPES lists them.
STAGE1_1X1 = {"reduce_256_64": (256, 56, 56, 256, 64),
              "expand_64_256": (256, 56, 56, 64, 256)}


@pytest.mark.parametrize("which", sorted(STAGE1_1X1))
def test_fused_matmul_bn_compiles(one_chip, which):
    from paddle_tpu.ops._pallas.fused_matmul_bn import fused_matmul_bn_act
    n, h, w, cin, cout = STAGE1_1X1[which]
    m = n * h * w

    def loss(x, wgt, scale, shift):
        y, s, ss = fused_matmul_bn_act(x, wgt, scale, shift)
        return y.astype(jnp.float32).sum() + s.sum() + ss.sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                    ((m, cin), jnp.bfloat16), ((cin, cout), jnp.bfloat16),
                    ((cin,), jnp.float32), ((cin,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("which", sorted(STAGE1_1X1))
def test_pallas_conv1x1_compiles(one_chip, which):
    """The 1x1-as-matmul conv kernel with its BN+ReLU prologue and stat
    epilogue, compiled for Mosaic (interpret=False), fwd + dgrad + wgrad."""
    from paddle_tpu.ops._pallas import conv as pconv
    n, h, w, cin, cout = STAGE1_1X1[which]
    x_shape, w_shape = (n, h, w, cin), (cout, cin, 1, 1)

    def fwd(x, wgt, scale, shift):
        return pconv.conv2d_fwd(x, wgt, scale, shift, act="relu",
                                interpret=False)

    def bwd(x, wgt, dy):
        return (pconv.conv2d_dgrad(dy, wgt, x_shape, interpret=False),
                pconv.conv2d_wgrad(x, dy, w_shape, interpret=False))

    bf16 = jnp.bfloat16
    text = _compile(fwd, one_chip, (x_shape, bf16), (w_shape, bf16),
                    ((cin,), jnp.float32), ((cin,), jnp.float32))
    assert "tpu_custom_call" in text
    text = _compile(bwd, one_chip, (x_shape, bf16), (w_shape, bf16),
                    ((n, h, w, cout), bf16))
    assert text.count("tpu_custom_call") >= 2


def test_pallas_conv3x3_is_refused_and_not_routable_on_tpu(one_chip,
                                                           monkeypatch):
    """The 3x3 kernels have only ever run in interpret mode: Mosaic
    refuses them (value-level dynamic_slice in _c3_taps). Pin the
    compiler's answer, and that ``supports`` says no for 3x3 on a TPU —
    announced (P005), not an interpret-mode run or a silent lax hand-over.
    When the kernel is rewritten to compile, this becomes its compile
    test; if it is deleted (ROADMAP A2/C2), this goes with it."""
    from paddle_tpu.analysis import pallas_check
    from paddle_tpu.ops._pallas import conv as pconv
    x_shape, w_shape = (256, 56, 56, 64), (64, 64, 3, 3)

    def fwd(x, wgt):
        return pconv.conv2d_fwd(x, wgt, padding=(1, 1), interpret=False)

    with pytest.raises(NotImplementedError, match="dynamic_slice"):
        _compile(fwd, one_chip, (x_shape, jnp.bfloat16),
                 (w_shape, jnp.bfloat16))

    kw = dict(stride=(1, 1), padding=(1, 1), dtype=jnp.bfloat16)
    assert pconv.supports(x_shape, w_shape, **kw)  # interpret mode: yes
    monkeypatch.setattr(pconv, "_interpret_default", lambda: False)
    monkeypatch.setattr(pallas_check, "_FALLBACKS_REPORTED", set())
    assert not pconv.supports(x_shape, w_shape, **kw)
    assert any(k == "pallas_conv3x3"
               for k, _ in pallas_check._FALLBACKS_REPORTED)
    # 1x1 stays routable on the chip
    assert pconv.supports((256, 56, 56, 256), (64, 256, 1, 1),
                          dtype=jnp.bfloat16)


def test_serving_decode_program_compiles(one_chip):
    """One paged decode program of the serving engine at GPT-1.3B widths
    (depth cut to 2 layers to keep the compile to seconds): the engine's
    own jitted decode step, lowered on described-device shapes with the
    weights as arguments (never baked in as constants)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=2,
                    num_heads=16, intermediate_size=8192,
                    max_position_embeddings=2048)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.astype(paddle.bfloat16)
    eng = ServingEngine(model, block_size=16, num_blocks=129,
                        max_batch=8, prefill_buckets=[256],
                        decode_buckets=[8])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    pages = on_chip(eng.cache.k)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    compiled = eng._decode_fn.jitted.lower(
        params, i32((8,)), pages, pages,
        i32((8, eng.max_blocks_per_seq)), i32((8,)),
        # the last launch's result and the rows that read their token there
        i32((8,)), i32((8,))).compile()
    # the 50304x2048 bf16 embedding is an argument of the program, not a
    # literal inside it
    assert len(compiled.as_text()) < 5_000_000
    assert compiled.memory_analysis().argument_size_in_bytes \
        > 2 * 50304 * 2048


# The serving cell's decode shapes (benchmark/traffic/batch-closed.json on
# gpt3-1.3b): 32 rows, tables of 80 pages of 16 tokens, 16 heads of 128,
# bf16, the 8 GB pool of 32 x 80 + 1 pages in 24 layers.
CELL_DECODE = dict(b=32, m=80, bs=16, h=16, kh=16, d=128, layers=24,
                   nb=32 * 80 + 1)


def test_paged_decode_kernel_compiles(one_chip):
    """The paged single-query attention kernel alone, at the serving cell's
    shapes: tables, lengths and the layer index as device operands, the
    whole pool handed over in HBM (its reshape to 2-D pages a bitcast, no
    copy of it anywhere in the program)."""
    from paddle_tpu.ops._pallas.paged_attention import (
        paged_attention_pallas, supported_shapes)
    c = CELL_DECODE
    pool = ((c["layers"], c["nb"], c["bs"], c["kh"], c["d"]), jnp.bfloat16)
    assert supported_shapes(jnp.bfloat16, jax.ShapeDtypeStruct(*pool))

    def fn(q, k, v, tables, lengths, layer):
        return paged_attention_pallas(q, k, v, tables, lengths, layer=layer)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((c["b"], 1, c["h"], c["d"]), jnp.bfloat16), pool, pool,
        ((c["b"], c["m"]), jnp.int32), ((c["b"],), jnp.int32),
        ((), jnp.int32))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # nothing the size of a layer's pool (168 MB) is ever materialised
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


def test_serving_decode_program_with_paged_kernel_compiles(one_chip,
                                                           monkeypatch):
    """The engine's decode program as the chip runs it: the entry point
    picks the kernel from the platform, which is the CPU here, so the test
    steers that one question and nothing else. Two layers at the cell's
    widths: one custom call a layer, sharing one lowered function, and no
    gathered copy of the pool among the temporaries (the gather program
    holds 2 x 168 MB of them)."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")

    c = CELL_DECODE
    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=2,
                    num_heads=16, intermediate_size=8192,
                    max_position_embeddings=2048)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.astype(paddle.bfloat16)
    eng = ServingEngine(model, block_size=c["bs"], num_blocks=c["m"] + 1,
                        max_batch=c["b"], max_seq_len=c["m"] * c["bs"],
                        prefill_buckets=[512], decode_buckets=[c["b"]])
    assert eng._decode_paged

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    pages = jax.ShapeDtypeStruct((2, c["nb"]) + eng.cache.k.shape[2:],
                                 jnp.bfloat16, sharding=one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    lowered = eng._decode_fn.jitted.lower(
        params, i32((c["b"],)), pages, pages, i32((c["b"], c["m"])),
        i32((c["b"],)), i32((c["b"],)), i32((c["b"],)))
    mlir = lowered.as_text()
    assert mlir.count("func.func private @_paged_call") == 1
    assert mlir.count("call @_paged_call") == 2
    compiled = lowered.compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


# The latent serving cell's decode shapes (benchmark/traffic/ctx4k-closed.json
# on deepseek-v2-ep16-l5): 256 rows, tables of 320 pages of 16 tokens, 128
# heads against a latent row of 576 stored as 640, bf16, a pool of 49,152
# pages in 5 layers (4.7 GiB).
CELL_LATENT = dict(b=256, m=320, bs=16, h=128, w=640, v=512, layers=5,
                   nb=49152)


def test_latent_decode_kernel_compiles(one_chip):
    """The absorbed latent (MLA) decode kernel alone, at its cell's shapes:
    a 327 KB block table in scalar memory, the whole pool handed over in
    HBM, a page moved by one aligned DMA (a row of 576 is refused: the
    chip lays it out as 640 and cannot slice it)."""
    from paddle_tpu.ops._pallas.latent_paged_attention import (
        latent_paged_attention_pallas, supported_shapes)
    c = CELL_LATENT
    pool = ((c["layers"], c["nb"], c["bs"], c["w"]), jnp.bfloat16)
    assert supported_shapes(jnp.bfloat16, jax.ShapeDtypeStruct(*pool),
                            c["v"])

    def fn(q, pool, tables, lengths, layer):
        return latent_paged_attention_pallas(
            q, pool, tables, lengths, value_dim=c["v"], scale=0.1147,
            layer=layer)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((c["b"], 1, c["h"], c["w"]), jnp.bfloat16), pool,
        ((c["b"], c["m"]), jnp.int32), ((c["b"],), jnp.int32),
        ((), jnp.int32))]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    # nothing the size of a layer's pool (1 GB) is ever materialised
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


def _largest_moved(text):
    """The most bytes any one copy or transpose of a compiled program
    writes (its result's shape; fused computations included)."""
    sizes = [0]
    for m in re.finditer(r"= [a-z]+(\d+)\[([\d,]*)\]\S* (?:copy|transpose)\(",
                         text):
        dims = [int(d) for d in m.group(2).split(",") if d]
        sizes.append(int(np.prod(dims, dtype=np.int64)) * int(m.group(1)) // 8)
    return max(sizes)


def test_latent_serving_decode_program_compiles(one_chip, monkeypatch):
    """The engine's decode program over a latent pool as the chip runs it,
    one dense and one expert layer at the published widths (weights as
    zeros: nothing runs): one latent kernel call a layer sharing one
    lowered function; the expert layer in its dense form at the bucket of
    256 (no ``ragged-dot`` call, and the held experts' stacks read where
    they lie: no copy or transpose as large as one); the one pool donated
    and aliased, and no gathered copy of it among the temporaries."""
    import importlib
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.deepseek_v2 import (DeepseekV2Config,
                                                    DeepseekV2ForCausalLM)
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")

    c = CELL_LATENT
    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=12800, num_hidden_layers=2, experts_held=(0, 10),
        dtype="bfloat16", init_weights=False))
    eng = ServingEngine(model, block_size=c["bs"], num_blocks=c["m"] + 1,
                        max_batch=c["b"], max_seq_len=c["m"] * c["bs"],
                        prefill_buckets=[4096], decode_buckets=[c["b"]])
    assert eng._decode_paged and eng.cache.rows == ((c["w"],),)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    pool = jax.ShapeDtypeStruct((2, c["nb"], c["bs"], c["w"]), jnp.bfloat16,
                                sharding=one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    lowered = eng._decode_fn.jitted.lower(
        params, i32((c["b"],)), pool, i32((c["b"], c["m"])), i32((c["b"],)),
        # the last launch's result (tokens, then the experts' loads) and the
        # rows that read their token there
        i32((c["b"] + eng._n_counts,)), i32((c["b"],)))
    mlir = lowered.as_text()
    assert mlir.count("func.func private @_latent_paged_call") == 1
    assert mlir.count("call @_latent_paged_call") == 2
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("%latent_paged_attention") >= 2
    assert "ragged-dot" not in text
    # the router's top-k sorts; the expert layer itself no longer does
    assert not [line for line in text.splitlines()
                if " sort(" in line and "moe/experts" in line]
    # one stack of the held experts: 10 x 5120 x 1536 in bf16, 157 MB
    assert _largest_moved(text) < model.model.layers[1].mlp.w_gate.nbytes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * c["nb"] * c["bs"] * c["w"] * 2
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


def test_latent_serving_prefill_program_compiles(one_chip, monkeypatch):
    """The engine's prefill program at the cell's longest bucket, one dense
    and one expert layer at the published widths (weights as zeros: nothing
    runs): one flash forward a layer, keys padded to 256 beside values of
    128, so no 256-wide value or output exists; 4,096 tokens are more than
    the dense form takes, so the expert layer's three products are grouped
    (XLA's own ``ragged-dot`` calls over the sorted pairs)."""
    import importlib
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.deepseek_v2 import (DeepseekV2Config,
                                                    DeepseekV2ForCausalLM)
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")

    c = CELL_LATENT
    s = 4096
    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=12800, num_hidden_layers=2, experts_held=(0, 10),
        dtype="bfloat16", init_weights=False))
    eng = ServingEngine(model, block_size=c["bs"], num_blocks=c["m"] + 1,
                        max_batch=c["b"], max_seq_len=c["m"] * c["bs"],
                        prefill_buckets=[s], decode_buckets=[c["b"]])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._prefill_fn.params)
    pool = jax.ShapeDtypeStruct((2, c["nb"], c["bs"], c["w"]), jnp.bfloat16,
                                sharding=one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    lowered = eng._prefill_fn.jitted.lower(
        params, i32((1, s)), pool, i32((s // c["bs"],)), i32(()))
    mlir = lowered.as_text()
    heads = model.cfg.num_attention_heads
    assert f"tensor<{heads}x{s}x128xbf16>" in mlir      # values and output
    assert f"tensor<{heads}x{s}x256xbf16>" in mlir      # padded q and k
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3


# The block-diffusion serving cell's shapes (benchmark/traffic/
# reason1k-closed.json on sdar-30b-a3b-ep8-l16): 128 rows of a block of 4,
# tables of 128 pages of 16 tokens, 32 query heads over 4 kv heads of 128,
# bf16, ONE pool of 10,240 pages in 16 layers of fused keys-and-values rows
# stored heads first (5.0 GiB, what the two pools it replaced took).
CELL_BLOCK = dict(b=128, lq=4, m=128, bs=16, h=32, kh=4, d=128, layers=16,
                  nb=10240)


def test_block_paged_kernel_compiles(one_chip):
    """The block paged attention kernel alone, at the cell's shapes: the
    whole pool of fused rows handed over in HBM, heads first, and stored
    without padding (32 KB a token over the 16 layers, a page 32 KB in one
    stretch)."""
    from paddle_tpu.ops._pallas.block_paged_attention import (
        block_paged_attention_pallas, supported_shapes)
    from paddle_tpu.ops.paged_layout import page_shape
    c = CELL_BLOCK
    page = page_shape((2 * c["kh"], c["d"]), c["bs"], jnp.bfloat16)
    assert page == (2 * c["kh"], c["bs"], c["d"])
    pool = ((c["layers"], c["nb"]) + page, jnp.bfloat16)
    assert supported_shapes(jnp.bfloat16, jax.ShapeDtypeStruct(*pool))

    def fn(q, kv, tables, lengths, layer):
        return block_paged_attention_pallas(q, kv, tables, lengths,
                                            layer=layer)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((c["b"], c["lq"], c["h"], c["d"]), jnp.bfloat16), pool,
        ((c["b"], c["m"]), jnp.int32), ((c["b"],), jnp.int32),
        ((), jnp.int32))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # 32 query rows a kv head: the per-head form, not the heads-joint one
    assert "block_paged_attention" in text
    assert "block_paged_attention_one_query" not in text
    mem = compiled.memory_analysis()
    stored = c["layers"] * c["nb"] * c["bs"] * 2 * c["kh"] * c["d"] * 2
    assert stored == c["nb"] * c["bs"] * 32 * 1024
    # the arguments are the one pool as counted (no padded axis) and the
    # small operands; only the queries' regrouping is a temporary
    assert stored < mem.argument_size_in_bytes < stored + 16 * 2 ** 20
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


def test_block_decode_program_compiles(one_chip, monkeypatch):
    """The engine's block-decode program as the chip runs it (the entry
    point picks the kernel from the platform, which is the CPU here, so the
    test steers that one question): two layers at SDAR's widths with a
    quarter of its vocabulary, ONE pool argument, one custom call a layer
    sharing one lowered function, no gathered copy of the pool among the
    temporaries, the pool aliased in place."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.sdar_moe import (SdarMoeConfig,
                                                 SdarMoeForCausalLM)
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    c = CELL_BLOCK
    cfg = SdarMoeConfig(vocab_size=37984, num_hidden_layers=2,
                        experts_held=(0, 16), mask_token_id=37000,
                        dtype="bfloat16", init_weights=False)
    paddle.seed(0)
    model = SdarMoeForCausalLM(cfg)
    eng = ServingEngine(model, block_size=c["bs"], num_blocks=c["m"] + 1,
                        max_batch=c["b"], max_seq_len=c["m"] * c["bs"],
                        prefill_buckets=[256], decode_buckets=[c["b"]])
    assert eng._decode_paged
    (pool,) = eng.cache.pools
    assert pool.shape[2:] == (2 * c["kh"], c["bs"], c["d"])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    pages = jax.ShapeDtypeStruct((2, c["nb"]) + pool.shape[2:],
                                 jnp.bfloat16, sharding=one_chip)
    tail = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in eng._decode_tail_spec(c["b"])]
    head = eng._decode_head_spec(c["b"])
    lowered = eng._decode_fn.jitted.lower(
        params, jax.ShapeDtypeStruct(head.shape, head.dtype,
                                     sharding=one_chip),
        pages, *tail)
    mlir = lowered.as_text()
    assert mlir.count("func.func private @_block_call") == 1
    assert mlir.count("call @_block_call") == 2
    compiled = lowered.compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    mem = compiled.memory_analysis()
    pool_bytes = 2 * c["nb"] * c["bs"] * 2 * c["kh"] * c["d"] * 2
    assert pool_bytes == 2 * c["nb"] * c["bs"] * 2 * 1024   # 2 layers' share
    # the pool is updated in place, and nothing the size of a layer's pages
    # (336 MB) is gathered: the temporaries are the pass's activations and
    # its float32 logits (512 x 37,984 x 4 B = 78 MB)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 512 * 2 ** 20


# Olmo-Hybrid's cell (serve.olmo-hybrid-7b-l4.reason1k-closed256): 256 rows;
# a linear layer's state [96, 30 x 192] float32 in a pool of 257 slots over
# the 3 linear layers; the full layer's fused rows of 60 heads (a 240 KB
# page), 24,576 pages.
CELL_STATE = dict(b=256, layers=3, slots=257, h=30, dk=96, dv=192, m=128,
                  bs=16, nb=24576, hidden=3840)


def test_gated_delta_decode_kernel_compiles(one_chip):
    """The state kernel alone at the cell's shapes: the pool stays where it
    lies (aliased to the output: no copy of its 1.7 GB), one custom call."""
    from paddle_tpu.ops._pallas.gated_delta_decode import (
        gated_delta_decode_pallas, supported_shapes)
    c = CELL_STATE
    width = c["h"] * c["dv"]
    pool = ((c["layers"], c["slots"], c["dk"], width), jnp.float32)
    assert supported_shapes(jax.ShapeDtypeStruct(*pool), c["h"])

    def fn(q, k, v, g, beta, pool, slots, layer):
        return gated_delta_decode_pallas(q, k, v, g, beta, pool, slots,
                                         layer=layer)

    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((c["b"], c["h"], c["dk"]), f32), ((c["b"], c["h"], c["dk"]), f32),
        ((c["b"], c["h"], c["dv"]), f32), ((c["b"], c["h"]), f32),
        ((c["b"], c["h"]), f32), pool, ((c["b"],), jnp.int32),
        ((), jnp.int32))]
    compiled = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gated_delta_decode" in text
    mem = compiled.memory_analysis()
    pool_bytes = c["layers"] * c["slots"] * c["dk"] * width * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_block_kernel_compiles_at_one_position_over_60_heads(one_chip):
    """The block kernel as the full layer's decode runs it: one query a row,
    30 heads over a fused row of 60 (keys and values of 30), pages of 240 KB:
    the heads-joint form, eight pages a step (a slot of ~2 MiB; the per-head
    form's slot of 1 MiB holds 32 of SDAR's 32 KB)."""
    from paddle_tpu.ops._pallas.block_paged_attention import (
        block_paged_attention_pallas, pages_for, supported_shapes)
    from paddle_tpu.ops.paged_layout import page_shape
    c = CELL_STATE
    page = page_shape((2 * c["h"], 128), c["bs"], jnp.bfloat16)
    assert page == (2 * c["h"], c["bs"], 128)
    assert pages_for(2 * c["h"] * c["bs"] * 128 * 2, 1) == 8
    assert pages_for(2 * c["h"] * c["bs"] * 128 * 2, 32) == 4
    assert pages_for(8 * 16 * 128 * 2, 32) == 32
    pool = ((1, c["nb"]) + page, jnp.bfloat16)
    assert supported_shapes(jnp.bfloat16, jax.ShapeDtypeStruct(*pool))

    def fn(q, kv, tables, lengths, layer):
        return block_paged_attention_pallas(q, kv, tables, lengths,
                                            layer=layer)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((c["b"], 1, c["h"], 128), jnp.bfloat16), pool,
        ((c["b"], c["m"]), jnp.int32), ((c["b"],), jnp.int32),
        ((), jnp.int32))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.findall(r'/(block_paged_attention\w*)/pallas_call', text) == [
        "block_paged_attention_one_query"]
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def test_state_decode_program_compiles(one_chip, monkeypatch):
    """The engine's decode program for a model with state layers as the chip
    runs it (the entry points pick their kernels from the platform, which is
    the CPU here, so the test steers that one question): one linear and one
    full layer at Olmo-Hybrid's widths with an eighth of its vocabulary,
    256 rows; one state kernel call and one block kernel call; the page pool
    and the slot pools updated in place; no gathered copy of a row's state
    among the temporaries."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.olmo_hybrid import (OlmoHybridConfig,
                                                    OlmoHybridForCausalLM)
    for mod in ("paddle_tpu.ops.flash_attention",
                "paddle_tpu.ops.gated_delta"):
        monkeypatch.setattr(importlib.import_module(mod), "_platform_of",
                            lambda x: "tpu")
    c = CELL_STATE
    cfg = OlmoHybridConfig(vocab_size=12544, num_hidden_layers=2,
                           layer_types=["linear_attention", "full_attention"],
                           dtype="bfloat16", init_weights=False)
    paddle.seed(0)
    model = OlmoHybridForCausalLM(cfg)
    # a small engine: the program is lowered at the cell's shapes below
    eng = ServingEngine(model, block_size=c["bs"], num_blocks=c["m"] + 1,
                        max_batch=1, max_seq_len=c["m"] * c["bs"],
                        prefill_buckets=[1024], decode_buckets=[c["b"]])
    assert eng._decode_paged and eng._state_paged
    (pool,) = eng.cache.pools
    state, tail = eng.cache.states
    assert state.shape[2:] == (c["dk"], c["h"] * c["dv"])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    arrays = [jax.ShapeDtypeStruct((1, c["nb"]) + pool.shape[2:],
                                   jnp.bfloat16, sharding=one_chip),
              jax.ShapeDtypeStruct((1, c["slots"]) + state.shape[2:],
                                   jnp.float32, sharding=one_chip),
              jax.ShapeDtypeStruct((1, c["slots"]) + tail.shape[2:],
                                   jnp.bfloat16, sharding=one_chip)]
    tail_args = [on_chip(s) for s in eng._decode_tail_spec(c["b"])]
    lowered = eng._decode_fn.jitted.lower(
        params, on_chip(eng._decode_head_spec(c["b"])), *arrays, *tail_args)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    mem = compiled.memory_analysis()
    state_bytes = c["slots"] * c["dk"] * c["h"] * c["dv"] * 4
    page_bytes = c["nb"] * c["bs"] * 2 * c["h"] * 128 * 2
    assert mem.alias_size_in_bytes >= state_bytes + page_bytes
    # the temporaries are the step's activations and its float32 logits
    # (256 x 12,544 x 4 B = 13 MB), not a gathered state (256 x 2.2 MB)
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


def test_state_decode_program_writes_tails_by_whole_tiles(one_chip,
                                                         monkeypatch):
    """The decode program at the Olmo cell's shapes (3 linear layers and 1
    full layer, 256 rows, 257 slots, an eighth of the vocabulary): each
    linear layer's convolution tails are written back by slot as whole
    tiles, in place: no ``while`` loop of row writes, no
    ``dynamic-update-slice`` into the tail pool, and every pool aliased to
    its output."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.olmo_hybrid import (PERIOD, OlmoHybridConfig,
                                                    OlmoHybridForCausalLM)
    for mod in ("paddle_tpu.ops.flash_attention",
                "paddle_tpu.ops.gated_delta"):
        monkeypatch.setattr(importlib.import_module(mod), "_platform_of",
                            lambda x: "tpu")
    c = CELL_STATE
    cfg = OlmoHybridConfig(vocab_size=12544, num_hidden_layers=4,
                           layer_types=list(PERIOD), dtype="bfloat16",
                           init_weights=False)
    paddle.seed(0)
    model = OlmoHybridForCausalLM(cfg)
    eng = ServingEngine(model, block_size=c["bs"], num_blocks=c["m"] + 1,
                        max_batch=1, max_seq_len=c["m"] * c["bs"],
                        prefill_buckets=[1024], decode_buckets=[c["b"]])
    (pool,) = eng.cache.pools
    state, tail = eng.cache.states
    # (K - 1) * C = 3 * 11,520 bf16: 270 rows of 128 lanes a slot
    assert tail.shape[2:] == (270, 128)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    lead = (c["layers"], c["slots"])
    arrays = [jax.ShapeDtypeStruct((1, c["nb"]) + pool.shape[2:],
                                   jnp.bfloat16, sharding=one_chip),
              jax.ShapeDtypeStruct(lead + state.shape[2:], jnp.float32,
                                   sharding=one_chip),
              jax.ShapeDtypeStruct(lead + tail.shape[2:], jnp.bfloat16,
                                   sharding=one_chip)]
    tail_args = [on_chip(s) for s in eng._decode_tail_spec(c["b"])]
    compiled = eng._decode_fn.jitted.lower(
        params, on_chip(eng._decode_head_spec(c["b"])), *arrays,
        *tail_args).compile()
    text = compiled.as_text()
    # a state kernel call a linear layer and the block kernel's one
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert " while(" not in text
    # the tail pool (bf16 [3 layers, 257 slots, ...]) in any layout
    assert not re.search(r"bf16\[3,257,[\d,]+\]\S* dynamic-update-slice\(",
                         text)
    assert len(re.findall(r"bf16\[3,257,270,128\]\S* scatter\(", text)) == 3
    mem = compiled.memory_analysis()
    state_bytes = 3 * c["slots"] * c["dk"] * c["h"] * c["dv"] * 4
    tail_bytes = 3 * c["slots"] * 272 * 128 * 2     # 270 rows pad to 272
    page_bytes = c["nb"] * c["bs"] * 2 * c["h"] * 128 * 2
    assert mem.alias_size_in_bytes >= state_bytes + tail_bytes + page_bytes
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


def _serving_engine(family):
    """A two-layer engine of each family at its published widths (weights
    as zeros where the family builds them so: nothing runs), small buckets:
    the programs' scopes do not depend on the sizes."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    if family == "gpt":
        from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=2048, num_layers=2, num_heads=16,
            intermediate_size=8192, max_position_embeddings=2048))
        model.astype(paddle.bfloat16)
    elif family == "deepseek":
        from paddle_tpu.text.models.deepseek_v2 import (
            DeepseekV2Config, DeepseekV2ForCausalLM)
        model = DeepseekV2ForCausalLM(DeepseekV2Config(
            vocab_size=12800, num_hidden_layers=2, experts_held=(0, 10),
            dtype="bfloat16", init_weights=False))
    elif family == "sdar":
        from paddle_tpu.text.models.sdar_moe import (SdarMoeConfig,
                                                     SdarMoeForCausalLM)
        model = SdarMoeForCausalLM(SdarMoeConfig(
            vocab_size=37984, num_hidden_layers=2, experts_held=(0, 16),
            mask_token_id=37000, dtype="bfloat16", init_weights=False))
    else:
        from paddle_tpu.text.models.olmo_hybrid import (
            OlmoHybridConfig, OlmoHybridForCausalLM)
        model = OlmoHybridForCausalLM(OlmoHybridConfig(
            vocab_size=12544, num_hidden_layers=2,
            layer_types=["linear_attention", "full_attention"],
            dtype="bfloat16", init_weights=False))
    return ServingEngine(model, block_size=16, num_blocks=129, max_batch=16,
                         max_seq_len=2048, prefill_buckets=[256],
                         decode_buckets=[16])


@pytest.mark.parametrize("family", ["gpt", "deepseek", "sdar", "olmo"])
def test_serving_programs_name_every_instruction_by_seam(one_chip,
                                                         monkeypatch, family):
    """The decode and prefill programs as the chip runs them (the entry
    points steered to the chip's kernels): every instruction traced from
    the engine's code lies under a seam scope, and in the name table every
    leaf instruction the programs execute does (what the compiler made
    takes its user's scope); the head's product lies under ``head``, the
    argmax under ``sample``."""
    import importlib
    from paddle_tpu.observability import device_names as DN
    for mod in ("paddle_tpu.ops.flash_attention",
                "paddle_tpu.ops.gated_delta"):
        monkeypatch.setattr(importlib.import_module(mod), "_platform_of",
                            lambda x: "tpu")
    eng = _serving_engine(family)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._decode_fn.params)
    arrays = [on_chip(a) for a in eng.cache.arrays]
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    slot = (i32(()),) if eng.cache.states else ()
    texts = {
        "decode": eng._decode_fn.jitted.lower(
            params, on_chip(eng._decode_head_spec(16)), *arrays,
            *[on_chip(s) for s in eng._decode_tail_spec(16)]),
        "prefill": eng._prefill_fn.jitted.lower(
            params, i32((1, 256)), *arrays, i32((256 // 16,)), i32(()),
            *slot)}
    exempt = ("parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "while", "conditional", "call")
    for kind, lowered in texts.items():
        text = lowered.compile().as_text()
        names = re.findall(r'op_name="(jit\([^"]*)"', text)
        assert names and [n for n in names if not DN.scopes(n)[0]] == []
        assert any(re.search(r"/head/(.*/)?dot_general$", n) for n in names)
        assert any(re.search(r"/sample/(.*/)?(argmax|reduce)$", n)
                   for n in names)
        prog = DN.parse(kind, text)
        assert prog.module.startswith("jit_serve_")
        assert [t for t, (seam, _) in prog.ops.items()
                if DN.opcode(t) not in exempt and not seam] == []
