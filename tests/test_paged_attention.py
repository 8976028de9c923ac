"""The paged single-query decode kernel (``ops/_pallas/paged_attention.py``)
in interpret mode on the CPU, against the dense path it replaces in the
serving decode program: gather every table's pages, then
``single_query_attention`` behind a length mask. The tier-1 engine tests run
that dense path (off the chip it is the declared one), so the kernel is
exercised here directly, and once through a whole ``ServingEngine`` run with
the entry point steered to the interpreted kernel.
"""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import pallas_check
from paddle_tpu.observability import metrics
from paddle_tpu.ops._pallas import paged_attention as PA
from paddle_tpu.ops.flash_attention import (paged_single_query_attention,
                                            single_query_attention)
from paddle_tpu.serving import NULL_BLOCK, Request, ServingEngine
from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

# ``paddle_tpu.ops.flash_attention`` the attribute is the function of that
# name; the module is reached through importlib
FA = importlib.import_module("paddle_tpu.ops.flash_attention")

BS, M, D, NB, L = 16, 5, 128, 48, 2
FULL = M * BS
# a pad row, one key, a whole page, one less, one more, the table's width
LENGTHS = [0, 1, BS, BS - 1, BS + 1, FULL, 3 * BS, 37]

kernel = functools.partial(PA.paged_attention_pallas, interpret=True)


def _pool_and_tables(lengths, kh, dtype, seed=0, heads=4):
    """Random pools, queries and block tables: each row's pages drawn
    scattered and unordered from the pool, the table's tail ``NULL_BLOCK``."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, 1, heads, D)), dtype)
    k = jnp.asarray(rng.standard_normal((L, NB, BS, kh, D)), dtype)
    v = jnp.asarray(rng.standard_normal((L, NB, BS, kh, D)), dtype)
    tables = np.full((b, M), NULL_BLOCK, np.int32)
    free = rng.permutation(np.arange(1, NB))      # non-monotonic page order
    at = 0
    for i, n in enumerate(lengths):
        pages = -(-n // BS)
        tables[i, :pages] = free[at:at + pages]
        at += pages
    return q, k, v, tables, np.asarray(lengths, np.int32)


def _dense(q, k, v, tables, lengths, layer):
    keys = k[layer][tables].reshape(len(lengths), FULL, *k.shape[3:])
    vals = v[layer][tables].reshape(len(lengths), FULL, *v.shape[3:])
    return single_query_attention(q, keys, vals, lengths=jnp.asarray(lengths))


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("pages_per_step", [1, 2, 8])
@pytest.mark.parametrize("kh", [4, 2, 1], ids=["mha", "gqa2", "mqa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_matches_gather_then_dense(dtype, kh, pages_per_step):
    q, k, v, tables, lengths = _pool_and_tables(LENGTHS, kh, dtype)
    for layer in range(L):
        out = kernel(q, k, v, jnp.asarray(tables), jnp.asarray(lengths),
                     layer=layer, pages_per_step=pages_per_step)
        assert out.shape == q.shape and out.dtype == q.dtype
        np.testing.assert_allclose(
            _f32(out), _f32(_dense(q, k, v, tables, lengths, layer)),
            atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("length", LENGTHS)
def test_each_length_alone_and_pad_rows_are_zero(length):
    """One real row between two pad rows: the pad rows (length 0, a table
    of ``NULL_BLOCK``) return zeros, the masked-row convention."""
    q, k, v, tables, lengths = _pool_and_tables([0, length, 0], 4,
                                                jnp.float32, seed=length)
    out = _f32(kernel(q, k, v, jnp.asarray(tables), jnp.asarray(lengths),
                      layer=1, pages_per_step=2))
    assert not out[0].any() and not out[2].any()
    if length == 0:
        assert not out[1].any()
    np.testing.assert_allclose(out, _f32(_dense(q, k, v, tables, lengths, 1)),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pages_per_step", [2, 8])
def test_kernel_reads_nothing_it_should_not_use(dtype, pages_per_step):
    """NaN in every pool block that is in no row's table (the null block
    among them), in every slot past a row's length inside its last page, and
    in the whole other layer, leaves the output as it was."""
    q, k, v, tables, lengths = _pool_and_tables(LENGTHS, 2, dtype, seed=3)
    args = (jnp.asarray(tables), jnp.asarray(lengths))
    clean = _f32(kernel(q, k, v, *args, layer=1,
                        pages_per_step=pages_per_step))
    poison = np.ones((L, NB, BS), bool)
    poison[1] = True
    for i, n in enumerate(lengths):
        for j in range(-(-int(n) // BS)):
            used = min(BS, int(n) - j * BS)
            poison[1, tables[i, j], :used] = False
    assert poison[1, NULL_BLOCK].all() and poison[0].all()
    mask = jnp.asarray(poison)[..., None, None]
    kp = jnp.where(mask, jnp.nan, k)
    vp = jnp.where(mask, jnp.nan, v)
    out = _f32(kernel(q, kp, vp, *args, layer=1,
                      pages_per_step=pages_per_step))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)


def test_one_layer_pool_and_traced_layer_index():
    """A ``[NB, bs, KH, D]`` pool is one layer's; a traced layer index picks
    the layer of a whole pool (the unrolled layers of a decode program share
    one lowered kernel that way)."""
    q, k, v, tables, lengths = _pool_and_tables([40, 7], 4, jnp.float32)
    t, n = jnp.asarray(tables), jnp.asarray(lengths)
    whole = kernel(q, k, v, t, n, layer=jnp.asarray(1))
    np.testing.assert_array_equal(_f32(whole),
                                  _f32(kernel(q, k[1], v[1], t, n)))
    assert np.abs(_f32(whole) - _f32(kernel(q, k, v, t, n, layer=0))).max() \
        > 1e-3


def test_entry_point_off_the_chip_is_the_dense_path():
    """On the CPU ``paged_single_query_attention`` is gather + dense, bit
    for bit, for a layer's pool and for the whole pool with an index."""
    q, k, v, tables, lengths = _pool_and_tables(LENGTHS, 2, jnp.float32)
    t, n = jnp.asarray(tables), jnp.asarray(lengths)
    want = _f32(_dense(q, k, v, tables, lengths, 1))
    for got in (paged_single_query_attention(q, k, v, t, n, block_size=BS,
                                             layer=1),
                paged_single_query_attention(q, k[1], v[1], t, n,
                                             block_size=BS)):
        np.testing.assert_array_equal(_f32(got), want)
    with pytest.raises(ValueError, match="block_size"):
        paged_single_query_attention(q, k, v, t, n, block_size=8)


SHAPES = {          # q dtype, pool dtype, (bs, kh, d) -> the kernel takes it
    "cell": (jnp.bfloat16, jnp.bfloat16, (16, 16, 128), True),
    "gqa32of16": (jnp.bfloat16, jnp.bfloat16, (32, 16, 128), True),
    "f32": (jnp.float32, jnp.float32, (16, 16, 128), False),
    "f32_pool": (jnp.bfloat16, jnp.float32, (16, 16, 128), False),
    "d64": (jnp.bfloat16, jnp.bfloat16, (16, 16, 64), False),
    "bs8": (jnp.bfloat16, jnp.bfloat16, (8, 16, 128), False),
    "kh8": (jnp.bfloat16, jnp.bfloat16, (16, 8, 128), False),
}


@pytest.mark.parametrize("which", sorted(SHAPES))
def test_selection_is_from_platform_and_shapes(which, monkeypatch):
    """Off the chip: never. On a TPU (the platform steered here, as
    ``test_chip_compile`` does): the supported shapes, and an unsupported
    one is announced once through P005."""
    qd, pd, (bs, kh, d), takes = SHAPES[which]
    pool = jnp.zeros((2, 3, bs, kh, d), pd)
    assert not FA.takes_paged_kernel(qd, pool)          # the CPU
    monkeypatch.setattr(FA, "_platform_of", lambda x: "tpu")
    monkeypatch.setattr(pallas_check, "_FALLBACKS_REPORTED", set())
    assert FA.takes_paged_kernel(qd, pool) is takes
    announced = {k for k, _ in pallas_check._FALLBACKS_REPORTED}
    assert announced == (set() if takes else {"paged_single_query_attention"})


def test_kernel_spec_fits_the_vmem_budget_at_the_cell_size():
    spec = pallas_check.spec_for_paged_decode(32, 80, 16, 16, 16, 128,
                                              dtype=jnp.bfloat16)
    assert not pallas_check.check_kernel_spec(spec)
    # sixty-four pages a step would not fit: the check says so
    big = pallas_check.spec_for_paged_decode(32, 80, 16, 16, 16, 128,
                                             pages_per_step=64,
                                             dtype=jnp.bfloat16)
    assert {d.rule for d in pallas_check.check_kernel_spec(big)} >= {"P001"}


# ---------------------------------------------------------------------------
# Through the engine
# ---------------------------------------------------------------------------

def _micro_model():
    paddle.seed(7)
    m = GPTForCausalLM(gpt_tiny(vocab_size=128, hidden_size=48, num_layers=2,
                                num_heads=4, max_position_embeddings=64))
    m.eval()
    return m


def _requests():
    rng = np.random.default_rng(5)
    return [Request(rid=f"r{i}", max_new_tokens=int(rng.integers(3, 9)),
                    prompt_ids=rng.integers(0, 128, int(rng.integers(3, 15))))
            for i in range(5)]


def _serve(model):
    engine = ServingEngine(model, block_size=4, num_blocks=32, max_batch=2)
    kv = metrics.counter("serving.kv_tokens")
    before = {k: kv.labels(kind=k).get() for k in ("needed", "gathered")}
    results = engine.serve(_requests())
    counted = {k: kv.labels(kind=k).get() - before[k] for k in before}
    return engine, results, counted


def test_engine_tokens_equal_with_the_kernel_and_counter_moves(monkeypatch):
    """Greedy tokens of a whole ``ServingEngine`` run (five requests through
    two decode rows, so rows are refilled) with the paged entry point
    steered to the interpreted kernel equal the dense path's, token for
    token; ``serving.kv_tokens{kind=gathered}`` then counts the pages the
    kernel reads, not the tables' width."""
    model = _micro_model()
    dense_eng, dense, dense_kv = _serve(model)
    assert not dense_eng._decode_paged

    engine_mod = importlib.import_module("paddle_tpu.serving.engine")
    calls = []

    def interpreted(*a, **kw):
        calls.append(1)
        return kernel(*a, **kw)

    monkeypatch.setattr(FA, "takes_paged_kernel", lambda *a: True)
    monkeypatch.setattr(engine_mod, "takes_paged_kernel", lambda *a: True)
    monkeypatch.setattr(PA, "paged_attention_pallas", interpreted)
    paged_eng, paged, paged_kv = _serve(model)
    assert paged_eng._decode_paged
    # a call a layer each time the decode program is traced (the engine's
    # lint traces it once more), none at a later step
    assert calls and len(calls) % model.cfg.num_layers == 0
    assert len(calls) <= 2 * model.cfg.num_layers
    assert set(paged) == set(dense) and len(paged) == 5
    for rid in dense:
        np.testing.assert_array_equal(paged[rid].output, dense[rid].output)

    assert paged_kv["needed"] == dense_kv["needed"] > 0
    # the dense program is handed every row's whole table, the kernel a
    # row's pages up to the key it wrote: never less than needed, never
    # a page more than that a row
    table_tokens = dense_eng.max_blocks_per_seq * 4
    rows = dense_kv["gathered"] // table_tokens    # bucket rows, pads too
    assert dense_kv["gathered"] == rows * table_tokens
    assert paged_kv["needed"] < paged_kv["gathered"] \
        <= paged_kv["needed"] + rows * 4
    assert paged_kv["gathered"] < dense_kv["gathered"] / 2


# ---------------------------------------------------------------------------
# The kernel's own roofline, as the benchmark reads it from a trace
# ---------------------------------------------------------------------------

def _roofline_reader():
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import run as R
    from benchmark.lib import peaks, readers, trace
    return (root, R.load_reader(root, "kernels.paged_attention_roofline"),
            peaks, readers, trace)


def _serve_ctx(root, readers, peaks, tr, steps):
    from benchmark.lib.family import load_family
    cfg = {"model": "gpt", "num_layers": 2, "hidden_size": 2048}
    return readers.Ctx(
        run={"traced": {"steps": steps}}, cfg=cfg, mix={}, cell={}, chips=1,
        peaks=peaks.PEAKS["TPU v5 lite"], family=load_family(root, cfg),
        trace=tr, win=(0.0, 1.0))


def test_roofline_reader_counts_only_the_decode_programs_custom_calls():
    """Two traced steps: a prefill program with a flash custom call and a
    decode program with one kernel call a layer. The kernel's least time is
    K and V of the rows' real contexts at the bandwidth peak; the flash call
    and a decode program's other ops are not its time."""
    root, read, peaks, readers, TR = _roofline_reader()
    call = ('%paged_single_query_attention.{} = bf16[32,16,128] custom-call'
            '(...), custom_call_target="tpu_custom_call"')
    flash = '%flash.1 = bf16[16,512,128] custom-call(...), ' \
            'custom_call_target="tpu_custom_call"'
    ops = [TR.Ev(flash, 0.101, 0.002),
           TR.Ev("%fusion.1 = bf16[1] fusion(...)", 0.111, 0.001),
           TR.Ev(call.format(1), 0.112, 50e-6),
           TR.Ev(call.format(2), 0.113, 50e-6),
           TR.Ev(call.format(1), 0.212, 30e-6),
           TR.Ev(call.format(2), 0.213, 30e-6)]
    mods = [TR.Ev("jit_step(1)", 0.100, 0.005),     # the prefill
            TR.Ev("jit_step(2)", 0.110, 0.005),     # its step's decode
            TR.Ev("jit_step(2)", 0.210, 0.005)]
    spans = [TR.Ev("bench.engine_step", 0.099, 0.02),
             TR.Ev("bench.engine_step", 0.209, 0.02)]
    tr = TR.Trace([TR.Device("/device:TPU:0", ops, mods)], spans)
    steps = [{"prefills": [300], "decode_ctx": [301, 500]},
             {"prefills": [], "decode_ctx": [302, 501]}]
    got = read(_serve_ctx(root, readers, peaks, tr, steps))
    keys = (301 + 500 + 302 + 501) * 2                 # a layer each
    least = keys * 2 * 2048 * 2 / 819e9                # K and V, bf16
    assert got["value"] == pytest.approx(100 * least / 160e-6, rel=1e-9)
    assert got["bound"] == "memory"
    assert got["calls"] == 4 and got["calls_per_program"] == 2
    assert got["ms_per_call"] == pytest.approx(0.04)
    # the gather-and-dense decode program has no such call: nothing to read
    tr.devices[0].ops = [e for e in ops if "paged" not in e.name]
    assert read(_serve_ctx(root, readers, peaks, tr, steps)) is None


def test_roofline_reader_reads_nothing_in_a_trace_of_the_gather_program(
        tmp_path):
    """The trace the benchmark keeps, recorded on the chip before the kernel
    existed (five decode programs of gathers and fusions): None, as at the
    parent commit."""
    import gzip
    import os
    root, read, peaks, readers, TR = _roofline_reader()
    dst = tmp_path / "serve_2layer.xplane.pb"
    with gzip.open(os.path.join(
            root, "benchmark/testdata/serve_2layer.xplane.pb.gz")) as f:
        dst.write_bytes(f.read())
    tr = TR.load(str(dst))
    steps = [{"prefills": [400, 900, 400, 900], "decode_ctx": [401] * 4}] \
        + [{"prefills": [], "decode_ctx": [402 + i] * 4} for i in range(4)]
    ctx = _serve_ctx(root, readers, peaks, tr, steps)
    ctx.win = (tr.spans[0].start, tr.spans[-1].end)
    assert len(readers.decode_programs(ctx)) == 5
    assert read(ctx) is None


# -- the block kernel: several queries a row over ONE heads-first pool of
# -- fused rows (a token's keys the first KH heads of its row, values the rest)

from paddle_tpu.ops._pallas import block_paged_attention as BPA  # noqa: E402
from paddle_tpu.ops.flash_attention import (  # noqa: E402
    block_paged_attention, flash_attention, multi_query_attention,
    reference_attention)
from paddle_tpu.ops.paged_layout import (  # noqa: E402
    gather_pages, heads_first, page_shape, split_keys_values, write_blocks,
    write_tokens)
from paddle_tpu.serving.paged_cache import PagedKVCache  # noqa: E402
from paddle_tpu.text.models.sdar_moe import (  # noqa: E402
    SdarMoeForCausalLM, sdar_moe_tiny)

block_kernel = functools.partial(BPA.block_paged_attention_pallas,
                                 interpret=True)


def _block_case(lengths, kh, heads, lq, dtype, seed=0):
    """Queries, the one pool of fused rows (pages heads first) and tables:
    each row's pages drawn scattered from the pool, the tail ``NULL_BLOCK``."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, lq, heads, D)), dtype)
    kv = jnp.asarray(rng.standard_normal((L, NB, 2 * kh, BS, D)), dtype)
    tables = np.full((b, M), NULL_BLOCK, np.int32)
    free = rng.permutation(np.arange(1, NB))
    at = 0
    for i, n in enumerate(lengths):
        pages = -(-n // BS)
        tables[i, :pages] = free[at:at + pages]
        at += pages
    return q, kv, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def _block_dense(q, kv, tables, lengths, layer):
    keys, vals = split_keys_values(gather_pages(kv[layer], tables, BS))
    pos = jnp.broadcast_to((lengths - 1)[:, None], q.shape[:2])
    return multi_query_attention(q, keys, vals, pos)


@pytest.mark.parametrize("pages", [1, 2, 5])
@pytest.mark.parametrize("kh,heads,lq", [(4, 32, 4), (2, 4, 4), (1, 8, 2),
                                         (3, 3, 1), (4, 4, 1), (30, 30, 1)])
def test_block_kernel_equals_dense_attention_over_ragged_contexts(
        pages, kh, heads, lq):
    """Rows of unequal contexts (a pad row, one block, page edges, the whole
    table): every query of a row over the row's first ``lengths[b]`` keys,
    against gather-and-dense behind the length mask, in either layer; at one
    query row a kv head (the last three cases) in the heads-joint form."""
    q, kv, tables, lengths = _block_case(LENGTHS[:1] + [4] + LENGTHS[2:],
                                         kh, heads, lq, jnp.bfloat16)
    for layer in (0, 1):
        got = block_kernel(q, kv, tables, lengths, layer=layer,
                           pages_per_step=pages)
        want = _block_dense(q, kv, tables, lengths, layer)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)
        assert not np.asarray(got, np.float32)[0].any()     # the pad row
        # and off the chip the entry point is that dense path
        np.testing.assert_array_equal(
            np.asarray(block_paged_attention(q, kv, tables, lengths,
                                             block_size=BS, layer=layer),
                       np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("pages", [1, 2, 5])
@pytest.mark.parametrize("kh,heads,lq", [(2, 8, 4), (4, 4, 1)])
def test_block_kernel_reads_nothing_past_a_rows_pages(pages, kh, heads, lq):
    """A row that ends inside a page and a row of length 0 beside full rows:
    every page no row's length reaches holds NaN, in its keys half and its
    values half (the null page too, and the unused tokens of a row's last
    page), and nothing of it arrives in the output: a page past a row's
    length is not fetched, and what a fetched page holds past the length is
    masked out of scores and values (in the heads-joint form too, where a
    query's product spans every head's columns)."""
    lengths = [FULL, 0, BS + 5, 2 * BS, 3]
    q, kv, tables, lens = _block_case(lengths, kh, heads, lq, jnp.bfloat16,
                                      seed=3)
    clean = np.asarray(kv, np.float32)
    live = np.zeros((NB, BS), bool)
    for row, n in zip(np.asarray(tables), lengths):
        for p in range(-(-n // BS)):
            live[row[p], :min(BS, n - p * BS)] = True
    assert not live[NULL_BLOCK].any() and live.sum() == sum(lengths)
    planted = np.where(live[None, :, None, :, None], clean, np.nan)
    for half in planted[:, :, :kh], planted[:, :, kh:]:   # keys, values
        assert np.isnan(half).any()
    planted = jnp.asarray(planted, jnp.bfloat16)
    for layer in (0, 1):
        got = np.asarray(block_kernel(q, planted, tables, lens, layer=layer,
                                      pages_per_step=pages), np.float32)
        assert np.isfinite(got).all()
        want = block_kernel(q, kv, tables, lens, layer=layer,
                            pages_per_step=pages)
        np.testing.assert_array_equal(got, np.asarray(want, np.float32))
        assert not got[1].any()                             # the empty row


@pytest.mark.parametrize("kh,heads,lq,joint", [
    (30, 30, 1, True), (3, 3, 1, True), (4, 32, 4, False),
    (2, 4, 4, False), (1, 8, 2, False), (4, 8, 1, False)])
def test_block_kernel_form_follows_the_query_rows_a_kv_head(kh, heads, lq,
                                                             joint):
    """The heads-joint form is taken where a kv head has ONE query row
    (``Lq * H / KH``), whatever the model: its pages a step fill the joint
    form's slot, and the kernel it lowers to carries the joint form's name;
    SDAR's 32 rows (and any other count) keep the per-head form."""
    qrows = lq * heads // kh
    assert BPA.one_query(qrows) is joint
    page = 2 * kh * BS * D * 2
    slot = BPA.ONE_QUERY_SLOT_BYTES if joint else BPA.SLOT_BYTES
    assert BPA.pages_for(page, qrows) == max(1, slot // page)
    q, kv, tables, lengths = _block_case([FULL, 3], kh, heads, lq,
                                         jnp.bfloat16)
    text = jax.jit(BPA.block_paged_attention_pallas).trace(
        q, kv, tables, lengths).lower(lowering_platforms=("tpu",)).as_text()
    names = re.findall(r'kernel_name = "(block_paged_attention\w*)"', text)
    assert names == (["block_paged_attention_one_query"] if joint
                     else ["block_paged_attention"])


def test_block_kernel_takes_the_cells_shapes_and_refuses_others():
    pool = jnp.zeros((1, 2, 8, 16, 128), jnp.bfloat16)
    assert BPA.supported_shapes(jnp.bfloat16, pool)
    assert not BPA.supported_shapes(jnp.float32, pool)
    assert not BPA.supported_shapes(jnp.bfloat16,
                                    jnp.zeros((1, 2, 8, 8, 128),
                                              jnp.bfloat16))
    assert not BPA.supported_shapes(jnp.bfloat16,
                                    jnp.zeros((1, 2, 8, 16, 64),
                                              jnp.bfloat16))
    # a fused row has keys and values: an even number of heads
    assert not BPA.supported_shapes(jnp.bfloat16,
                                    jnp.zeros((1, 2, 7, 16, 128),
                                              jnp.bfloat16))
    assert not FA.takes_paged_kernel(jnp.bfloat16, pool, None, 16)  # the CPU
    with pytest.raises(ValueError, match="keys and values"):
        block_kernel(jnp.zeros((1, 4, 6, 128), jnp.bfloat16),
                     jnp.zeros((1, 2, 3, 16, 128), jnp.bfloat16),
                     jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32))


def test_flash_splits_a_fused_row():
    """``flash_attention(q, kv)`` with no values is the attention over the
    fused row's halves: what a model that caches one row a token hands its
    prefill."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 8, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 8, 2, 16)), jnp.float32)
    kv = jnp.concatenate([k, v], axis=2)
    for got in split_keys_values(kv), split_keys_values(kv[0]):
        assert got[0].shape[-2:] == (2, 16)
    np.testing.assert_array_equal(np.asarray(split_keys_values(kv)[1]),
                                  np.asarray(v))
    for block in (1, 4):
        np.testing.assert_array_equal(
            np.asarray(flash_attention(q, kv, causal=True, training=False,
                                       causal_block=block)),
            np.asarray(reference_attention(q, k, v, True,
                                           causal_block=block)))


def test_the_pools_layout_follows_the_rows_shape():
    """Rows of (4, 128) in bfloat16 are stored heads first (tokens first the
    head axis of 4 would be padded to the tile's 16 on the chip), and so is
    the fused keys-and-values row (8, 128); rows of (16, 128) and the latent
    row keep tokens first, their bytes unchanged; shapes that fill no tile
    either way (the tests' small heads) keep it too. Spill and restore move
    whole pages and follow."""
    bf = jnp.bfloat16
    assert page_shape((4, 128), 16, bf) == (4, 16, 128)
    assert page_shape((8, 128), 16, bf) == (8, 16, 128)
    assert page_shape((16, 128), 16, bf) == (16, 16, 128)
    assert page_shape((640,), 16, bf) == (16, 640)
    assert page_shape((2, 128), 8, jnp.float32) == (2, 8, 128)
    assert page_shape((4, 32), 8, jnp.float32) == (8, 4, 32)
    assert page_shape((2, 8), 4, jnp.float32) == (4, 2, 8)
    few = PagedKVCache(2, 6, 16, dtype=bf, rows=((4, 128), (4, 128)))
    one = PagedKVCache(2, 6, 16, dtype=bf, rows=((8, 128),))
    gpt = PagedKVCache(2, 6, 16, dtype=bf, rows=((16, 128), (16, 128)))
    mla = PagedKVCache(2, 6, 16, dtype=bf, rows=((640,),))
    assert few.k.shape == (2, 6, 4, 16, 128) and heads_first(few.k, 16)
    assert [p.shape for p in one.pools] == [(2, 6, 8, 16, 128)] \
        and heads_first(one.pools[0], 16)
    assert gpt.k.shape == (2, 6, 16, 16, 128) and not heads_first(gpt.k, 16)
    assert mla.pools[0].shape == (2, 6, 16, 640)
    for cache, per_token in ((few, 2 * 4 * 128), (one, 8 * 128),
                             (gpt, 2 * 16 * 128), (mla, 640)):
        assert cache.bytes_per_block == 2 * 16 * per_token * 2
        assert sum(p.nbytes for p in cache.pools) == 6 * cache.bytes_per_block
    # the one pool of fused rows takes the bytes of the two it replaces
    assert one.pools[0].nbytes == few.k.nbytes + few.v.nbytes
    # writes and reads agree, whatever the layout
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((32, 4, 128)), bf)
    ids = jnp.asarray([3, 5], jnp.int32)
    pool = write_blocks(few.k, 1, ids, rows, 16)
    got = gather_pages(pool[1], ids[None], 16)[0]
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(rows, np.float32))
    tok = jnp.asarray(rng.standard_normal((2, 3, 4, 128)), bf)
    bi = jnp.asarray([[3, 3, 3], [5, 5, 5]], jnp.int32)
    si = jnp.asarray([[4, 5, 6], [0, 1, 15]], jnp.int32)
    pool = write_tokens(pool, 1, bi, si, tok, 16)
    got = np.asarray(gather_pages(pool[1], ids[None], 16)[0], np.float32)
    np.testing.assert_array_equal(got[[4, 5, 6]],
                                  np.asarray(tok[0], np.float32))
    np.testing.assert_array_equal(got[[16, 17, 31]],
                                  np.asarray(tok[1], np.float32))
    # a fused row written whole or a token at a time reads back as its halves
    fused = jnp.asarray(rng.standard_normal((32, 8, 128)), bf)
    kv = write_blocks(one.pools[0], 0, ids, fused, 16)
    kv = write_tokens(kv, 0, bi, si, jnp.concatenate([tok, tok], axis=2), 16)
    keys, vals = split_keys_values(gather_pages(kv[0], ids[None], 16)[0])
    np.testing.assert_array_equal(np.asarray(keys[7], np.float32),
                                  np.asarray(fused[7, :4], np.float32))
    np.testing.assert_array_equal(np.asarray(vals[7], np.float32),
                                  np.asarray(fused[7, 4:], np.float32))
    for half in keys, vals:
        np.testing.assert_array_equal(np.asarray(half[31], np.float32),
                                      np.asarray(tok[1, 2], np.float32))
    # a spill and a restore into other pages keep every byte
    few.pools = (pool, few.v)
    blocks = few.allocator.alloc(2)
    assert blocks == [1, 2]
    few.allocator.free(blocks)
    held = few.allocator.alloc(5)
    page3 = np.asarray(pool[:, 3], np.float32)
    host = few.snapshot([3])
    few.restore(host, [4])
    np.testing.assert_array_equal(np.asarray(few.k[:, 4], np.float32), page3)
    few.allocator.free(held)


# -- SDAR's engine over the one pool -----------------------------------------

def _sdar_model():
    paddle.seed(11)
    m = SdarMoeForCausalLM(sdar_moe_tiny())
    m.eval()
    return m


def _sdar_serve(model):
    rng = np.random.default_rng(2)
    reqs = [Request(rid=f"s{i}", max_new_tokens=n,
                    prompt_ids=rng.integers(0, 500, p))
            for i, (p, n) in enumerate([(5, 6), (9, 9), (17, 5), (3, 11)])]
    eng = ServingEngine(model, block_size=8, num_blocks=24, max_batch=2,
                        max_seq_len=64, prefill_buckets=[8, 16, 32])
    fetches = metrics.counter("serving.kv_page_fetches").labels()
    gathered = metrics.counter("serving.kv_tokens").labels(kind="gathered")
    before = fetches.get(), gathered.get()
    done = eng.serve(reqs)
    return eng, done, (fetches.get() - before[0], gathered.get() - before[1])


def test_sdars_engine_builds_one_pool_of_the_bytes_of_two():
    """What the model says it caches decides the pools: ONE fused row of ``2
    x KH`` heads a token, so one pool ``[L, NB, 2 * KH, bs, D]`` whose bytes
    are those of the keys pool and the values pool it replaces."""
    model = _sdar_model()
    cfg = model.cfg
    kh, d = cfg.num_key_value_heads, cfg.head_dim
    assert model.serve_cache_rows() == ((2 * kh, d),)
    eng = ServingEngine(model, block_size=8, num_blocks=24, max_batch=2,
                        max_seq_len=64, prefill_buckets=[8, 16])
    assert eng._n_pools == 1
    (pool,) = eng.cache.pools
    assert pool.shape == (cfg.num_hidden_layers, 24, 2 * kh, 8, d)
    two = PagedKVCache(cfg.num_hidden_layers, 24, 8, dtype=pool.dtype,
                       rows=((kh, d), (kh, d)))
    assert pool.nbytes == two.k.nbytes + two.v.nbytes
    assert eng.cache.bytes_per_block == two.bytes_per_block
    # a layer hands the engine the one row, keys first
    layer = model.serve_layers()[0]
    x = jnp.ones((1, 4, cfg.hidden_size), jnp.float32)
    pos = jnp.arange(4)[None]
    q, rows = layer.serve_project(x, pos)
    assert len(rows) == 1 and rows[0].shape == (1, 4, 2 * kh, d)
    _, k, v = layer.self_attn.project(layer.input_layernorm(x), pos)
    keys, vals = split_keys_values(rows[0])
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(v))


def test_sdars_engine_tokens_equal_with_the_block_kernel(monkeypatch):
    """A whole ``ServingEngine`` run of a block-diffusion model (four requests
    through two rows, so rows are refilled and blocks straddle pages) with
    the entry point steered to the interpreted block kernel serves the dense
    path's tokens, and ``serving.kv_page_fetches`` counts ONE fetch a page the
    kernel reads: the pages ``serving.kv_tokens{kind=gathered}`` counts."""
    model = _sdar_model()
    dense_eng, dense, (dense_fetches, dense_tokens) = _sdar_serve(model)
    assert not dense_eng._decode_paged
    # the dense pass is handed every row's whole table, once (one pool)
    assert dense_fetches * 8 == dense_tokens > 0

    engine_mod = importlib.import_module("paddle_tpu.serving.engine")
    calls = []

    def interpreted(*a, **kw):
        calls.append(1)
        return block_kernel(*a, **kw)

    monkeypatch.setattr(FA, "takes_paged_kernel", lambda *a: True)
    monkeypatch.setattr(engine_mod, "takes_paged_kernel", lambda *a: True)
    monkeypatch.setattr(BPA, "block_paged_attention_pallas", interpreted)
    paged_eng, paged, (fetches, tokens) = _sdar_serve(model)
    assert paged_eng._decode_paged
    assert calls and len(calls) % model.cfg.num_hidden_layers == 0
    assert set(paged) == set(dense) and len(paged) == 4
    for rid in dense:
        np.testing.assert_array_equal(paged[rid].output, dense[rid].output)
    assert fetches * 8 == tokens and 0 < fetches < dense_fetches


def test_page_fetches_count_two_a_page_for_keys_and_values_apart():
    """GPT caches keys and values in two pools: 2 fetches a page read."""
    fetches = metrics.counter("serving.kv_page_fetches").labels()
    before = fetches.get()
    _, _, counted = _serve(_micro_model())
    assert (fetches.get() - before) * 4 == 2 * counted["gathered"] > 0
