"""chip_smoke.py rehearsed on the CPU (``--tiny``: same code path, small
sizes), plus the fallbacks PR 21 repaired: nothing on the main path may
hide a missing or unused chip."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))


# ---------------------------------------------------------------------------
# the script itself
# ---------------------------------------------------------------------------

def test_tiny_run_phases_in_order_and_never_ok_off_chip(capsys, out_dir):
    rc = chip_smoke.main(["--tiny"])
    recs = _lines(capsys)
    assert [r["phase"] for r in recs[:-1]] == [
        "device", "train", "trace", "serve", "cache"]
    train, serve = recs[1], recs[3]
    assert train["entry"].endswith("make_sharded_train_step")
    assert train["losses"][-1] < train["losses"][0]
    assert train["flash_custom_call_in_step"] is False  # no Mosaic on CPU
    assert serve["completed"] == serve["requests"] == 8
    assert len(serve["vs_generate"]) == 2
    # the last line is the contract's object; it never claims a TPU here
    assert set(recs[-1]) == {"ok", "device"}
    assert recs[-1]["ok"] is False and rc != 0
    assert recs[-1]["device"]["platform"] == "cpu"


def test_chips4_runs_only_the_sharded_step_and_its_comparison(capsys,
                                                              out_dir):
    rc = chip_smoke.main(["--tiny", "--chips", "4"])
    recs = _lines(capsys)
    assert [r["phase"] for r in recs[:-1]] == ["device", "multichip",
                                               "cache"]
    mc = recs[1]
    assert mc["mesh"] == {"sharding": 2, "mp": 2}
    assert abs(mc["loss_sharded_step0"] - mc["loss_one_device"]) \
        <= mc["loss_rel_tol"] * mc["loss_one_device"]
    assert mc["collectives"].get("all-gather", 0) > 0
    assert recs[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert rc != 0


def test_failing_phase_fails_the_run(capsys, out_dir, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("train phase broke")
    monkeypatch.setattr(chip_smoke, "phase_train", boom)
    with pytest.raises(RuntimeError, match="train phase broke"):
        chip_smoke.main(["--tiny"])  # as a process: traceback, exit 1
    recs = _lines(capsys)
    assert recs[-1]["ok"] is False
    assert "serve" not in [r.get("phase") for r in recs]


def test_without_tiny_a_cpu_is_refused(capsys, out_dir):
    with pytest.raises(AssertionError, match="no TPU"):
        chip_smoke.main([])
    assert _lines(capsys)[-1]["ok"] is False


# ---------------------------------------------------------------------------
# peaks, compile cache
# ---------------------------------------------------------------------------

def test_peak_table_raises_on_unknown_device_kind():
    from paddle_tpu.analysis import comm_check
    from paddle_tpu.core.chip import chip_peaks
    assert chip_peaks("TPU v5 lite").bf16_tflops == 197.0
    assert comm_check.PEAK_TFLOPS == 197.0
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")


def test_compile_cache_helper(monkeypatch):
    from paddle_tpu.core import chip
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".cache", "jax")
    assert chip.compile_cache_dir() == want == chip.compile_cache_dir()
    assert chip.autotune_cache_path() == os.path.join(REPO, ".cache",
                                                      "autotune.json")
    before = jax.config.jax_compilation_cache_dir
    # off the chip nothing is switched on
    assert chip.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    # on the chip: the in-checkout path, unless the variable names another
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    assert chip.enable_compile_cache() == want
    assert set_to == [("jax_compilation_cache_dir", want)]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert chip.enable_compile_cache() == "/somewhere/else"
    assert len(set_to) == 1  # no code path sets another directory


# ---------------------------------------------------------------------------
# the repaired fallbacks
# ---------------------------------------------------------------------------

def test_use_pallas_announces_unsupported_shape_on_tpu(monkeypatch, capsys):
    from paddle_tpu.analysis import pallas_check
    flash = sys.modules["paddle_tpu.ops.flash_attention"]
    good = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16)
    odd = jax.ShapeDtypeStruct((1, 731, 16, 128), jnp.bfloat16)
    assert not flash._use_pallas(good, good)  # CPU: dense is the path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_check, "_FALLBACKS_REPORTED", set())
    assert flash._use_pallas(good, good)
    assert not flash._use_pallas(odd, odd)
    assert not flash._use_pallas(odd, odd)
    err = capsys.readouterr().err
    assert err.count("P005/kernel-fallback") == 1  # once, with the shape
    assert "731" in err


def test_autotune_names_refused_candidates(tmp_path):
    from paddle_tpu.ops._pallas.autotune import AutotuneCache, autotune
    cache = AutotuneCache(path=str(tmp_path / "a.json"))

    def run_fn(cfg):
        if cfg != "ok":
            raise NotImplementedError(f"compiler refuses {cfg}")
        return cfg

    with pytest.warns(UserWarning, match="compiler refuses bad"):
        assert autotune("k", "s", ["bad", "ok"], run_fn,
                        measure=lambda run: (run(), 1.0)[1],
                        cache=cache) == "ok"
    with pytest.raises(ValueError, match="compiler refuses worse"):
        autotune("k", "s2", ["worse"], run_fn,
                 measure=lambda run: (run(), 1.0)[1], cache=cache)


def test_unreadable_trace_is_an_error_on_tpu_only(tmp_path, monkeypatch):
    from paddle_tpu.profiler.statistic import device_total_ms
    assert device_total_ms(str(tmp_path)) is None  # CPU: no device plane
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="could not be read on a TPU"):
        device_total_ms(str(tmp_path))


def test_launcher_refuses_many_processes_on_a_tpu_host(monkeypatch):
    from paddle_tpu.distributed import launch
    monkeypatch.setattr(launch, "local_tpu_chips",
                        lambda: ["/dev/accel0", "/dev/accel1"])
    launch.check_one_process_per_chip(1, {})
    launch.check_one_process_per_chip(4, {"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="one process drives all local"):
        launch.check_one_process_per_chip(4, {})


def test_serving_programs_take_weights_as_arguments():
    """The engine's jitted steps must not bake the model in as constants
    (one copy per bucket program): weights are the leading argument."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(vocab_size=64, hidden_size=32,
                                    num_layers=1, num_heads=2,
                                    max_position_embeddings=32))
    eng = ServingEngine(model, block_size=8, num_blocks=9, max_batch=2)
    compiled, donated = eng.compile_decode()
    n_weights = len(jax.tree_util.tree_leaves(eng._decode_fn.params))
    assert n_weights > 0 and donated == 2
    n_args = len(jax.tree_util.tree_leaves(compiled.args_info))
    # tokens, two pools, tables, contexts, the last launch's result, the
    # row map
    assert n_args == n_weights + 7


def test_flash_kernel_runs_per_shard_on_a_hybrid_mesh(monkeypatch):
    """Mosaic kernels cannot be partitioned by GSPMD; under a hybrid mesh
    the call goes through shard_map (batch over data axes, heads over
    mp). Interpret mode on the virtual mesh: same values as one device."""
    import jax.experimental.pallas as pl
    from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                                 set_hybrid_mesh)
    from paddle_tpu.ops._pallas import flash_attention as fa
    orig = pl.pallas_call
    monkeypatch.setattr(
        fa.pl, "pallas_call",
        lambda *a, **k: orig(*a, **{**k, "interpret": True}))
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((4, 128, 4, 64)), jnp.float32)
               for _ in range(3))
    want = fa.flash_attention_pallas(q, k, v, causal=True)
    mesh = create_hybrid_mesh(sharding=2, mp=2, devices=jax.devices()[:4])
    set_hybrid_mesh(mesh)
    try:
        got = jax.jit(lambda q, k, v: fa.flash_attention_pallas(
            q, k, v, causal=True))(q, k, v)
        dq = jax.jit(jax.grad(lambda q: fa.flash_attention_pallas(
            q, k, v, causal=True).sum()))(q)
    finally:
        set_hybrid_mesh(None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    dq_want = jax.grad(lambda q: fa.flash_attention_pallas(
        q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_want),
                               atol=1e-4)
