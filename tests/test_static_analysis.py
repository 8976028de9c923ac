"""paddle_tpu.analysis: jaxpr linter rules (positive + negative per rule),
Pallas TPU-constraint checks, flag wiring, and the BERT lints-clean
regression (ISSUE 1 acceptance criteria)."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import (BlockUse, KernelSpec, check_kernel_spec,
                                 lint_fn, lint_jaxpr, spec_for_flash_packed)
from paddle_tpu.analysis.jaxpr_lint import GraphLintError
from paddle_tpu.core import flags


def rules_of(diags):
    return {d.rule for d in diags}


@pytest.fixture
def analysis_error_mode():
    flags.set_flags({"static_analysis": "error"})
    yield
    flags.set_flags({"static_analysis": "off"})


# ---------------------------------------------------------------------------
# J001 f64 promotion
# ---------------------------------------------------------------------------

def test_j001_f64_promotion_flagged():
    with jax.enable_x64(True):
        diags = lint_fn(lambda x: x.astype(jnp.float64) * 2.0,
                        jnp.ones((4,), jnp.float32))
    hits = [d for d in diags if d.rule == "J001"]
    assert hits and hits[0].severity == "error"
    # acceptance: rule id AND source location present in the message
    formatted = hits[0].format()
    assert "J001" in formatted
    assert "test_static_analysis.py" in formatted


def test_j001_negative_f32():
    diags = lint_fn(lambda x: x.astype(jnp.float32) * 2.0,
                    jnp.ones((4,), jnp.bfloat16))
    assert "J001" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J002 weak-typed python scalar argument
# ---------------------------------------------------------------------------

def test_j002_weak_scalar_arg():
    diags = lint_fn(lambda s, x: x * s, 3.0, jnp.ones((4,)))
    assert "J002" in rules_of(diags)


def test_j002_negative_typed_scalar():
    diags = lint_fn(lambda s, x: x * s, jnp.float32(3.0), jnp.ones((4,)))
    assert "J002" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J003 captured scalar constant
# ---------------------------------------------------------------------------

def test_j003_captured_scalar():
    c = jnp.asarray(2.5)  # 0-d device array closed over -> graph constant
    diags = lint_fn(lambda x: x * c, jnp.ones((4,)))
    assert "J003" in rules_of(diags)


def test_j003_negative_threaded_arg():
    diags = lint_fn(lambda c, x: x * c, jnp.asarray(2.5), jnp.ones((4,)))
    assert "J003" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J004 dead code
# ---------------------------------------------------------------------------

def test_j004_dead_code():
    def f(x):
        _unused = x * 3.0
        return x.sum()
    diags = lint_fn(f, jnp.ones((4,)))
    assert "J004" in rules_of(diags)


def test_j004_negative_all_used():
    diags = lint_fn(lambda x: (x * 3.0).sum(), jnp.ones((4,)))
    assert "J004" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J005 PRNG key reuse / J006 constant seed
# ---------------------------------------------------------------------------

def test_j005_key_reuse_and_j006_constant_seed():
    def f():
        k = jax.random.PRNGKey(0)
        return jax.random.normal(k, (2,)) + jax.random.normal(k, (2,))
    diags = lint_fn(f)
    assert "J005" in rules_of(diags)
    assert "J006" in rules_of(diags)


def test_j005_j006_negative_split_key_arg():
    def f(k):
        k1, k2 = jax.random.split(k)
        return jax.random.normal(k1, (2,)) + jax.random.normal(k2, (2,))
    diags = lint_fn(f, jax.random.PRNGKey(7))
    assert "J005" not in rules_of(diags)
    assert "J006" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J007 callback in loop / J008 host callback
# ---------------------------------------------------------------------------

def _noop(*_):
    pass


def test_j007_callback_in_scan_body():
    def f(x):
        def body(c, t):
            jax.debug.callback(_noop, c)
            return c + t, t
        c, _ = jax.lax.scan(body, x.sum(), x)
        return c
    diags = lint_fn(f, jnp.ones((4,)))
    hits = [d for d in diags if d.rule == "J007"]
    assert hits and hits[0].severity == "error"


def test_j007_negative_j008_top_level_callback():
    def f(x):
        jax.debug.callback(_noop, x)
        return x.sum()
    diags = lint_fn(f, jnp.ones((4,)))
    assert "J007" not in rules_of(diags)
    assert "J008" in rules_of(diags)  # info-severity note remains


def test_j008_negative_no_callback():
    diags = lint_fn(lambda x: x.sum(), jnp.ones((4,)))
    assert "J008" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J009 donated passthrough
# ---------------------------------------------------------------------------

def test_j009_donated_passthrough():
    diags = lint_fn(lambda x, y: (x, x + y), jnp.ones((4,)), jnp.ones((4,)),
                    donate_argnums=(0,))
    hits = [d for d in diags if d.rule == "J009"]
    assert hits and hits[0].severity == "error"


def test_j009_negative_transformed_output():
    diags = lint_fn(lambda x, y: (x * 2.0, x + y), jnp.ones((4,)),
                    jnp.ones((4,)), donate_argnums=(0,))
    assert "J009" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J010 gather index overflow
# ---------------------------------------------------------------------------

def test_j010_int32_overflow_gather():
    from jax import lax

    # trace with abstract shapes: no 9-GiB allocation happens
    big = jax.ShapeDtypeStruct((2 ** 31 + 8,), jnp.float32)
    idx = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    dnums = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(0,), start_index_map=(0,))
    diags = lint_fn(
        lambda t, i: lax.gather(t, i, dnums, slice_sizes=(1,)), big, idx)
    hits = [d for d in diags if d.rule == "J010"]
    assert hits and hits[0].severity == "error"


def test_j010_negative_small_table():
    diags = lint_fn(lambda t, i: jnp.take(t, i), jnp.ones((128,)),
                    jnp.zeros((4,), jnp.int32))
    assert "J010" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J011 nondeterministic reduction under deterministic mode
# ---------------------------------------------------------------------------

def test_j011_scatter_add_under_deterministic_mode():
    def loss(emb, idx):
        return jnp.take(emb, idx, axis=0).sum()
    emb = jnp.ones((16, 8))
    idx = jnp.zeros((4,), jnp.int32)
    flags.set_flags({"use_deterministic_reductions": True})
    try:
        diags = lint_fn(jax.grad(loss), emb, idx)
    finally:
        flags.set_flags({"use_deterministic_reductions": False})
    assert "J011" in rules_of(diags)


def test_j011_negative_flag_off():
    def loss(emb, idx):
        return jnp.take(emb, idx, axis=0).sum()
    diags = lint_fn(jax.grad(loss), jnp.ones((16, 8)),
                    jnp.zeros((4,), jnp.int32))
    assert "J011" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J012 host<->device transfer inside a compiled loop body
# ---------------------------------------------------------------------------

def _to_host_kind():
    return jax.memory.Space.Host


def test_j012_device_put_in_scan_body():
    tgt = _to_host_kind()

    def f(xs):
        def body(c, x):
            y = jax.device_put(x, tgt)  # tier move per iteration
            return c + y, y
        return jax.lax.scan(body, jnp.zeros(()), xs)

    diags = lint_fn(f, jnp.arange(4.0))
    assert "J012" in rules_of(diags)
    d = next(d for d in diags if d.rule == "J012")
    assert d.severity == "error"
    assert "prefetch" in d.hint


def test_j012_negative_top_level_transfer():
    """The offload streaming idiom — an explicit transfer BETWEEN loop
    iterations at the top level of the program — is exactly what the rule
    must not flag."""
    tgt = _to_host_kind()

    def f(xs):
        y = jax.device_put(xs, tgt)
        return jnp.sum(y)

    diags = lint_fn(f, jnp.arange(4.0))
    assert "J012" not in rules_of(diags)


def test_j012_negative_offload_block_update_clean():
    """framework/offload.StreamingUpdate's compiled block program carries
    no in-graph transfers (movement is dispatch-level)."""
    from paddle_tpu import nn as pnn
    from paddle_tpu.framework import offload
    from paddle_tpu.framework.functional import get_params
    from paddle_tpu.optimizer import AdamW

    paddle.seed(0)
    net = pnn.Sequential(pnn.Linear(8, 8), pnn.Tanh(), pnn.Linear(8, 4))
    params = get_params(net)
    su = offload.StreamingUpdate(AdamW(learning_rate=1e-3))
    state = su.init_state(params)
    grads = {k: jnp.ones_like(v) for k, v in params.items()}
    names = offload.group_by_block(list(params))[0][1]
    # the block program sees moments already prefetched to device memory
    # (StreamingUpdate.update's dispatch order), never host-committed ones
    dev = su._prefetch(names, params, state["param_states"])
    st_blk = {n: {**state["param_states"][n], **dev.get(n, {})}
              for n in names}
    diags = lint_fn(su._block_fn.__wrapped__,
                    {n: params[n] for n in names},
                    {n: grads[n] for n in names},
                    st_blk, state["step"], jnp.float32(1e-3))
    assert "J012" not in rules_of(diags)


# ---------------------------------------------------------------------------
# J013 telemetry callback in step graph
# ---------------------------------------------------------------------------

def _cb_fn(x):
    return np.asarray(x)


def _with_pure_callback(x):
    y = jax.pure_callback(_cb_fn, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    return y.sum()


@pytest.fixture
def telemetry_mode_restore():
    prev = flags.get_flags(["telemetry"])
    yield
    flags.set_flags(prev)


def test_j013_callback_flagged_when_telemetry_not_trace(
        telemetry_mode_restore):
    flags.set_flags({"telemetry": "metrics"})
    diags = lint_fn(_with_pure_callback, jnp.ones((4,)))
    hits = [d for d in diags if d.rule == "J013"]
    assert hits and hits[0].severity == "warning"
    assert "host-side" in hits[0].hint or "dispatch level" in hits[0].hint
    # off is even stricter a promise — still flagged
    flags.set_flags({"telemetry": "off"})
    assert "J013" in rules_of(lint_fn(_with_pure_callback, jnp.ones((4,))))


def test_j013_negative_under_trace_mode(telemetry_mode_restore):
    flags.set_flags({"telemetry": "trace"})
    diags = lint_fn(_with_pure_callback, jnp.ones((4,)))
    assert "J013" not in rules_of(diags)


def test_j013_negative_no_callback(telemetry_mode_restore):
    flags.set_flags({"telemetry": "metrics"})
    diags = lint_fn(lambda x: x.sum(), jnp.ones((4,)))
    assert "J013" not in rules_of(diags)


# ---------------------------------------------------------------------------
# Pallas / TPU-constraint checker
# ---------------------------------------------------------------------------

def test_p001_synthetic_vmem_overflow_kernel():
    spec = KernelSpec(
        name="synthetic_overflow",
        grid=(4,),
        blocks=[BlockUse((4096, 4096), np.float32, "x")],  # 64 MB tile
        dims=[("rows", 16384, 4096)])
    diags = check_kernel_spec(spec)
    hits = [d for d in diags if d.rule == "P001"]
    assert hits and hits[0].severity == "error"
    assert "synthetic_overflow" in hits[0].message


def test_p001_packed_flash_bwd_512_square_over_budget():
    # the hand-patched folklore from ops/_pallas/flash_attention_packed.py:
    # 512x512 backward score tiles overflow the 16MB scoped-VMEM stack
    bad = check_kernel_spec(
        spec_for_flash_packed(512, 512, 768, 512, 512, 12, bwd=True))
    assert any(d.rule == "P001" and d.severity == "error" for d in bad)
    # ... and the shipped 256x512 config fits
    good = check_kernel_spec(
        spec_for_flash_packed(512, 512, 768, 256, 512, 12, bwd=True))
    assert not [d for d in good if d.severity == "error"]


def test_p002_tile_alignment():
    spec = KernelSpec(name="misaligned",
                      blocks=[BlockUse((8, 192), np.float32, "x")])
    assert "P002" in rules_of(check_kernel_spec(spec))
    ok = KernelSpec(name="aligned",
                    blocks=[BlockUse((8, 256), np.float32, "x")])
    assert "P002" not in rules_of(check_kernel_spec(ok))


def test_p003_grid_divisibility():
    spec = KernelSpec(name="ragged", dims=[("seq", 500, 256)])
    hits = [d for d in check_kernel_spec(spec) if d.rule == "P003"]
    assert hits and hits[0].severity == "error"
    ok = KernelSpec(name="even", dims=[("seq", 512, 256)])
    assert "P003" not in rules_of(check_kernel_spec(ok))


def test_conv3x3_spec_vmem_includes_im2col_tiles():
    from paddle_tpu.analysis import spec_for_conv3x3
    # 512-channel 56x56 f32: image (6.9MB) + taps (9.4MB) alone overflow
    # the budget — and the im2col tap/acc tiles must appear in the message
    bad = check_kernel_spec(spec_for_conv3x3(2, 56, 56, 512, 512,
                                             block_h=56, stride=1))
    hits = [d for d in bad if d.rule == "P001"]
    assert hits and hits[0].severity == "error"
    assert "im2col" in hits[0].message
    # the shipped default (block_h=8, ResNet stage-1 bf16) fits
    good = check_kernel_spec(spec_for_conv3x3(256, 56, 56, 64, 64,
                                              block_h=8, stride=1,
                                              dtype=np.dtype("bfloat16")))
    assert not [d for d in good if d.severity == "error"]


def test_conv3x3_wgrad_spec_defaults_fit():
    from paddle_tpu.analysis import spec_for_conv3x3
    good = check_kernel_spec(spec_for_conv3x3(256, 56, 56, 64, 64,
                                              block_h=8, stride=1,
                                              dtype=np.dtype("bfloat16"),
                                              wgrad=True))
    assert not [d for d in good if d.severity == "error"]


def test_conv_matmul_spec_rules():
    from paddle_tpu.analysis import spec_for_conv_matmul
    # non-dividing row block -> P003
    ragged = check_kernel_spec(spec_for_conv_matmul(1000, 64, 256,
                                                    block_m=512))
    assert any(d.rule == "P003" and d.severity == "error" for d in ragged)
    # misaligned minor dim -> P002 warning (not an error)
    mis = check_kernel_spec(spec_for_conv_matmul(512, 64, 192, block_m=256))
    assert "P002" in rules_of(mis)
    # the shipped stage-1 1x1 default config is clean
    ok = check_kernel_spec(spec_for_conv_matmul(256 * 56 * 56, 256, 64,
                                                block_m=512,
                                                dtype=np.dtype("bfloat16")))
    assert not [d for d in ok if d.severity == "error"]


def test_conv_supports_refuses_what_checks_reject():
    """ops/_pallas/conv.py routability must agree with the checker: an
    over-VMEM shape falls back to lax instead of reaching Mosaic."""
    from paddle_tpu.ops._pallas import conv as pconv
    assert not pconv.supports((256, 112, 112, 512), (512, 512, 3, 3),
                              padding=(1, 1), dtype=np.float32)
    assert pconv.supports((2, 56, 56, 64), (64, 64, 3, 3), padding=(1, 1),
                          dtype=np.float32)


def test_packed_flash_entry_enforces_under_error_mode(analysis_error_mode):
    q = jnp.zeros((1, 512, 12, 64), jnp.float32)
    with pytest.raises(GraphLintError) as ei:
        paddle.analysis  # noqa: B018 — keep import referenced
        from paddle_tpu.ops._pallas.flash_attention_packed import (
            flash_attention_packed)
        flash_attention_packed(q, q, q, block_q=512, block_k=512)
    assert "P001" in str(ei.value)


# ---------------------------------------------------------------------------
# emit() modes + flag plumbing
# ---------------------------------------------------------------------------

def test_emit_error_mode_raises(analysis_error_mode):
    with jax.enable_x64(True):
        diags = lint_fn(lambda x: x.astype(jnp.float64),
                        jnp.ones((2,), jnp.float32))
    with pytest.raises(GraphLintError) as ei:
        analysis.emit(diags, where="test")
    assert "J001" in str(ei.value)


def test_emit_warn_mode_prints(capsys):
    flags.set_flags({"static_analysis": "warn"})
    try:
        with jax.enable_x64(True):
            diags = lint_fn(lambda x: x.astype(jnp.float64),
                            jnp.ones((2,), jnp.float32))
        with pytest.warns(UserWarning):
            analysis.emit(diags, where="test")
    finally:
        flags.set_flags({"static_analysis": "off"})
    assert "J001" in capsys.readouterr().err


def test_emit_off_mode_silent(capsys):
    diags = lint_fn(lambda x: x * 3.0, jnp.ones((2,)))
    analysis.emit(diags, where="test")  # off: no output, no raise
    assert capsys.readouterr().err == ""


def test_to_static_lints_under_error_mode(analysis_error_mode):
    @paddle.jit.to_static
    def f(x):
        _dead = x * 3.0
        k = jax.random.PRNGKey(0)  # J006 warning — not fatal
        return x.sum() + jax.random.normal(k, ()).sum() * 0.0
    # warnings only -> still runs
    out = f(jnp.ones((4,)))
    assert np.isfinite(float(out))


def test_dy2static_fallback_reports_under_warn_mode(capsys):
    from paddle_tpu.jit.dy2static import convert_to_static
    flags.set_flags({"static_analysis": "warn"})
    try:
        fn = convert_to_static(lambda x: x + 1)  # lambda: no source def
        assert fn(1) == 2
    finally:
        flags.set_flags({"static_analysis": "off"})
    # Y001 (was D001 before the donation-lifetime D-family took the
    # prefix — analysis/plan_check.py)
    assert "Y001" in capsys.readouterr().err


def test_unknown_flag_error_lists_valid_names():
    with pytest.raises(KeyError) as ei:
        flags.set_flags({"FLAGS_check_nan_inf_typo": 1})
    msg = str(ei.value)
    assert "check_nan_inf" in msg          # close-match suggestion
    assert "static_analysis" in msg        # full valid-name list surfaced


def test_static_analysis_flag_rejects_bad_value():
    with pytest.raises(ValueError):
        flags.set_flags({"static_analysis": "loud"})


def test_unknown_env_flags(monkeypatch):
    monkeypatch.setenv("FLAGS_not_a_real_flag", "1")
    assert "FLAGS_not_a_real_flag" in flags.unknown_env_flags()


# ---------------------------------------------------------------------------
# NaN/Inf scans report through the shared Diagnostic channel and cover
# optimizer state (satellite)
# ---------------------------------------------------------------------------

def test_check_optimizer_state_scans_pytree(capsys):
    from paddle_tpu.amp import debugging
    state = {"m": jnp.ones((2,)), "v": jnp.asarray([1.0, float("nan")])}
    flags.set_flags({"check_nan_inf": True, "check_nan_inf_level": 1})
    try:
        debugging.check_optimizer_state(state, where="unit")
        jax.effects_barrier()
    finally:
        flags.set_flags({"check_nan_inf": False, "check_nan_inf_level": 0})
    err = capsys.readouterr().err
    assert "N001" in err and "nan-inf" in err and "'v'" in err


# ---------------------------------------------------------------------------
# BERT regression: the full encoder lints clean
# ---------------------------------------------------------------------------

def test_bert_encoder_lints_clean():
    from paddle_tpu.framework.functional import functional_call, get_params
    from paddle_tpu.text.models.bert import Bert, bert_tiny
    model = Bert(bert_tiny())
    model.eval()
    params = get_params(model)
    ids = jnp.zeros((2, 64), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, x: functional_call(model, p, x))(params, ids)
    diags = lint_jaxpr(closed, where="bert")
    assert [d for d in diags if d.severity in ("error", "warning")] == []


def test_lint_graph_cli_bert_exits_zero():
    import subprocess
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "lint_graph.py"),
         "--model", "mlp"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "diagnostic" in r.stdout
