"""Driver benchmark over the BASELINE workload configs.

Emits one JSON line per measured config, with the primary line — BASELINE
config 4's GPT per-chip slice — printed LAST (the driver records the final
line as the headline metric):

  config 2  ResNet-50 data-parallel        -> imgs/sec/chip
  config 3  BERT-base pretraining, AMP O2  -> tokens/sec/chip
  config 5  ERNIE-3.0 via pipeline step    -> tokens/sec/chip
  config 4  GPT decoder LM (PRIMARY)       -> tokens/sec/chip + MFU

The reference publishes no numbers (BASELINE.md), so ``vs_baseline``
reports measured MFU / 0.40 — 0.40 MFU being the strong H100+NCCL
Megatron-class utilization the north star asks us to match per chip (raw
FLOPs differ per accelerator; utilization is the comparable quantity).
Non-primary configs compute MFU from XLA's compiled cost analysis.

Single-chip notes: config 2's DP and config 5's pp=4 collapse to degree 1
on one chip — the multi-chip schedules are exercised by the driver's
``dryrun_multichip`` and the CPU-mesh test suite; the bench measures the
per-chip throughput term of the BASELINE metric basket.

Remat is OFF by default for the GPT config: the 254M bench model's
activations fit v5e HBM at this batch, and blanket block remat costs ~25%
step time (see PERF.md). Set BENCH_REMAT=1 for the memory-constrained
configuration.

Self-defense (VERDICT r4 #1): every config is timed over >=3 independent
windows guarded by a roofline floor computed from the compiled step's
FLOPs/bytes; windows slower than BENCH_ANOMALY_FACTOR (4x) the floor are
discarded and retried, and a config that never produces a clean window is
emitted with "anomaly": true plus the discard log. Modeled on the
reference's CI outlier gate (tools/check_op_benchmark_result.py). The pure
selection logic is fault-injection-tested in tests/test_bench_guard.py.

Env: BENCH_SMALL=1 (CPU smoke), BENCH_CONFIGS=gpt|all (default all),
BENCH_LAYERS/HIDDEN/HEADS/SEQ/BATCH/STEPS/REMAT/PEAK_TFLOPS,
BENCH_WINDOWS/ANOMALY_FACTOR/RETRY_WINDOWS (guard knobs),
BENCH_PALLAS_CONV=1 (Pallas-vs-XLA conv A/B: per-shape device-time table
at the top-3 ResNet byte shapes + the full-graph ResNet step with
FLAGS_pallas_conv=1 — the table VERDICT r5 asks the next chip round for),
BENCH_TELEMETRY=0 (skip the telemetry overhead A/B), BENCH_TRACE_OUT
(path for the run's step-timeline JSONL, default BENCH_timeline.jsonl —
render with tools/trace_view.py), BENCH_MULTISLICE=0 (skip the 2-slice
hierarchical-vs-flat DCN reduction dryrun), BENCH_SERVE=0 (skip the serving-engine
sweep; BENCH_SERVE_REQUESTS/MAX_NEW/LAYERS/HIDDEN/HEADS/VOCAB size it —
continuous batching vs the sequential one-shot Predictor on one ragged
trace, concurrency sweep, compile-budget/O001 gate; emits
serving_tokens_per_s + serving_p50_ms/serving_p99_ms and appends the
per-request phase records to the timeline JSONL; the resilience leg
additionally runs the subprocess serve drill — SIGKILL mid-decode +
mid-spill, exactly-once replay — and a fault-injected overload trace
with deadlines/bounded admission/shedding, emitting
serving_slo_attainment_pct + serving_shed_rate with the drill recovery
stats; the engine surviving pool exhaustion is asserted).
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
import time

import numpy as np


def _peak_flops(dev) -> float:
    """Peak bf16 FLOP/s of the chip ``dev``, from the one sourced table
    (paddle_tpu/core/chip.py); a chip not in it raises. Override:
    BENCH_PEAK_TFLOPS."""
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    from paddle_tpu.core.chip import chip_peaks
    return chip_peaks(dev.device_kind).bf16_tflops * 1e12


# ---------------------------------------------------------------------------
# Self-defending measurement (VERDICT r4 missing #3 / next-round #1).
#
# The round-4 driver capture recorded BERT at 0.048x — a 25x collapse from a
# transient device pathology that the bench accepted as truth. Defense,
# modeled on the reference's CI outlier gate (tools/
# check_op_benchmark_result.py — rejects runs outside a tolerance band):
#   1. >=3 independent timing windows per config; the reported number is the
#      min over windows that pass the sanity check.
#   2. A roofline floor computed from the compiled step's FLOPs and bytes
#      (XLA cost analysis): no valid window can beat max(flops/peak,
#      bytes/bw), and a window slower than ANOMALY_FACTOR x that floor is
#      physically implausible for these >=0.3-MFU configs — it is discarded
#      and the window retried.
#   3. If every window is anomalous after retries, the result is still
#      emitted but carries "anomaly": true and the discard log, so the
#      record can never silently present a stalled number as a clean
#      measurement.
# The pure window-selection logic (guarded_min) is fault-injection-tested in
# tests/test_bench_guard.py.
# ---------------------------------------------------------------------------

N_WINDOWS = int(os.environ.get("BENCH_WINDOWS", "3"))
ANOMALY_FACTOR = float(os.environ.get("BENCH_ANOMALY_FACTOR", "4.0"))
MAX_EXTRA_WINDOWS = int(os.environ.get("BENCH_RETRY_WINDOWS", "3"))


def _peak_hbm_bw(dev) -> float:
    """Peak HBM bandwidth (bytes/s) of the chip ``dev``, from the same
    table; a chip not in it raises. Override: BENCH_PEAK_HBM_GBS."""
    env = os.environ.get("BENCH_PEAK_HBM_GBS")
    if env:
        return float(env) * 1e9
    from paddle_tpu.core.chip import chip_peaks
    return chip_peaks(dev.device_kind).hbm_gbs * 1e9


def roofline_step_seconds(flops, bytes_accessed, peak_flops, peak_bw):
    """Lower-bound step time from compiled cost: max of the compute and
    memory rooflines. 0.0 when neither quantity is known (guard disabled)."""
    t = 0.0
    if flops and peak_flops:
        t = max(t, flops / peak_flops)
    if bytes_accessed and peak_bw:
        t = max(t, bytes_accessed / peak_bw)
    return t


def _roofline_for(dev, flops, nbytes):
    """Roofline floor for the guard — only on TPU, where the peak tables
    apply (a CPU smoke run would flag every window against a v5e peak)."""
    if getattr(dev, "platform", "") != "tpu":
        return 0.0
    return roofline_step_seconds(flops, nbytes, _peak_flops(dev),
                                 _peak_hbm_bw(dev))


def guarded_min(window_fn, n_windows, roofline_s, factor=None,
                max_extra=None):
    """Collect `n_windows` valid timing windows and return their min.

    window_fn() -> per-step seconds, or None when the window failed to
    measure (e.g. trace did not parse). A window slower than
    factor * roofline_s is an anomaly: it is recorded, discarded, and an
    extra window is attempted (up to n_windows + max_extra total attempts).

    Returns (best_seconds_or_None, anomaly, valid_times, discarded_times):
    anomaly=True means NO clean window was obtained and best is the min of
    the discarded (i.e. untrustworthy) times, or None if nothing measured.
    """
    factor = ANOMALY_FACTOR if factor is None else factor
    max_extra = MAX_EXTRA_WINDOWS if max_extra is None else max_extra
    # Sub-millisecond rooflines (tiny smoke shapes) are dominated by fixed
    # per-step overheads the FLOPs/bytes model can't see — the guard only
    # has meaning for the real >=100 ms configs.
    limit = factor * roofline_s if roofline_s and roofline_s >= 1e-3 \
        else None
    valid, discarded = [], []
    attempts = 0
    while len(valid) < n_windows and attempts < n_windows + max_extra:
        attempts += 1
        t = window_fn()
        if t is None:
            continue
        if limit is not None and t > limit:
            discarded.append(t)
            continue
        valid.append(t)
    if valid:
        return min(valid), False, valid, discarded
    if discarded:
        return min(discarded), True, valid, discarded
    return None, True, valid, discarded


def _measure_guarded(step, state, args, steps, roofline_s,
                     n_windows=None, args_seq=None):
    """Guarded wall + device timing for a donated-state step fn.

    Pre-warm: one compile call + one warm call run before any timed window
    (this is also where Pallas block selection consults the pre-loaded
    autotune cache — never inside a window). Then `n_windows` wall windows
    and `n_windows` device-trace windows, each guarded against the roofline
    floor. Device time is the preferred basis: it excludes the host's
    dispatch and whatever else shares the host's cores.

    args_seq: optional list of per-step arg tuples, cycled across ALL
    steps (warmup included) — a fresh batch per step, so reported losses
    reflect optimization rather than single-batch memorization (VERDICT
    r5 weak #3). Default: `args` every step.

    Returns dict(loss, wall_s, device_s, used_s, timing, anomaly,
    windows, discarded, state).
    """
    n_windows = N_WINDOWS if n_windows is None else n_windows
    seq = list(args_seq) if args_seq else None
    box = {"state": state, "loss": None, "i": 0}

    def next_args():
        if seq is None:
            return args
        a = seq[box["i"] % len(seq)]
        box["i"] += 1
        return a

    loss, state = step(state, *next_args())  # compile
    box["state"] = state
    loss, box["state"] = step(box["state"], *next_args())  # warm
    float(loss)

    def wall_window():
        t0 = time.perf_counter()
        st = box["state"]
        for _ in range(steps):
            loss, st = step(st, *next_args())
        box["loss"] = float(loss)
        box["state"] = st
        return (time.perf_counter() - t0) / steps

    # Wall windows: the guard still applies (a stall shows up here first),
    # but wall legitimately carries dispatch latency — it is the basis
    # only off the chip, where a trace has no device plane.
    wall_s, wall_anom, wall_ok, wall_disc = guarded_min(
        wall_window, n_windows, roofline_s)

    def device_window():
        dt, st, loss = _device_step_time(step, box["state"], next_args,
                                         steps)
        box["state"] = st
        if loss is not None:
            box["loss"] = loss
        return dt

    dev_s, dev_anom, dev_ok, dev_disc = guarded_min(
        device_window, n_windows, roofline_s)

    if dev_s is not None and not dev_anom:
        used, timing, anomaly = dev_s, "device", False
    elif wall_s is not None and not wall_anom:
        used, timing, anomaly = wall_s, "wall", False
    else:
        cands = [t for t in (dev_s, wall_s) if t is not None]
        used = min(cands) if cands else None
        timing = "device" if used == dev_s and dev_s is not None else "wall"
        anomaly = True
    return {
        "loss": box["loss"], "wall_s": wall_s, "device_s": dev_s,
        "used_s": used, "timing": timing, "anomaly": anomaly,
        "windows": {"device_ms": [round(t * 1e3, 2) for t in dev_ok],
                    "wall_ms": [round(t * 1e3, 2) for t in wall_ok]},
        "discarded": {"device_ms": [round(t * 1e3, 2) for t in dev_disc],
                      "wall_ms": [round(t * 1e3, 2) for t in wall_disc]},
        "roofline_ms": round(roofline_s * 1e3, 2) if roofline_s else None,
        "state": box["state"],
    }


def _guard_extra(m):
    """The guard fields every emitted config carries."""
    return {
        "anomaly": m["anomaly"], "timing": m["timing"],
        "windows": m["windows"], "discarded": m["discarded"],
        "roofline_ms": m["roofline_ms"],
        "wall_step_ms": round(m["wall_s"] * 1e3, 2) if m["wall_s"] else None,
    }


def _prewarm_autotune():
    """Load the persistent kernel-autotune cache before any timing so
    _pick_blocks-style selectors hit it at trace time (VERDICT r4 #1:
    'pre-warm the autotune cache inside bench before timing')."""
    from paddle_tpu.ops._pallas.autotune import get_cache
    get_cache().load()


def _device_step_time(step, state, args_fn, steps):
    """DEVICE time per step from a profiler trace (hlo_stats total).

    args_fn() supplies each step's args (fresh-batch cycling).
    Returns (device_dt, state, loss). device_dt is None only off the chip,
    where a trace has no device plane; on a TPU a trace that cannot be
    read raises (profiler.statistic.device_total_ms) — the wall clock is
    never substituted in silence.
    """
    import shutil
    import tempfile

    import jax

    from paddle_tpu.profiler.statistic import device_total_ms

    tracedir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        loss = None
        with jax.profiler.trace(tracedir):
            for _ in range(steps):
                loss, state = step(state, *args_fn())
            floss = float(loss)  # sync inside the trace window
        total_ms = device_total_ms(tracedir)
    finally:
        shutil.rmtree(tracedir, ignore_errors=True)
    if total_ms is None:
        return None, state, floss
    return total_ms / steps / 1e3, state, floss


# Per-leg compiled-HLO verify stats (analysis/hlo_check X-rules over the
# leg's own compiled step, measured in _compiled_cost): verifier wall
# time plus the undeclared-collective count — which must stay 0, so
# BENCH_timeline.jsonl tracks both the verifier's cost and any GSPMD
# drift across rounds. Reset per leg; None = the leg compiled nothing.
_HLO_VERIFY = {"hlo_verify_ms": None, "hlo_undeclared_collectives": None}


def _hlo_verify_compiled(compiled):
    """X-rule pass over one compiled bench step. Bench legs declare no
    plan (single-chip programs), so ANY compiled collective counts as
    undeclared — the drift signal the timeline diffs."""
    try:
        from paddle_tpu.analysis import hlo_check, plan_check
        t0 = time.perf_counter()
        diags = hlo_check.check_hlo(plan_check.StepPlan(), compiled,
                                    where="bench.hlo")
        _HLO_VERIFY["hlo_verify_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        _HLO_VERIFY["hlo_undeclared_collectives"] = sum(
            1 for d in diags if d.rule == "X001")
    except Exception:
        _HLO_VERIFY["hlo_verify_ms"] = None
        _HLO_VERIFY["hlo_undeclared_collectives"] = None


def _emit(name, value, unit, mfu, extra):
    import jax
    peak = _peak_flops(jax.devices()[0])
    print(json.dumps({
        "metric": name, "value": round(value, 1), "unit": unit,
        "vs_baseline": round(mfu / 0.40, 4) if mfu else 0.0,
        "extra": {**extra, "mfu": round(mfu, 4),
                  "device": str(jax.devices()[0]),
                  "peak_tflops": peak / 1e12,
                  **_HLO_VERIFY},
    }), flush=True)


def _compiled_cost(jitted, *args):
    """(flops, bytes_accessed) from XLA's compiled cost analysis — the
    inputs to the roofline floor the anomaly guard checks against. The
    same compiled executable feeds the leg's X-rule verify
    (_hlo_verify_compiled), so hlo_verify_ms / hlo_undeclared_collectives
    ride along in the leg's emitted extra."""
    try:
        compiled = jitted.lower(*args).compile()
    except Exception:
        return 0.0, 0.0
    _hlo_verify_compiled(compiled)
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return (float(cost.get("flops", 0.0)),
                float(cost.get("bytes accessed", 0.0)))
    except Exception:
        return 0.0, 0.0


# ---------------------------------------------------------------------------
# Config 2: ResNet-50 data parallel (imgs/sec/chip)
# ---------------------------------------------------------------------------

def bench_resnet(small: bool):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.functional import (functional_call,
                                                 get_buffers, get_params)
    from paddle_tpu.nn import functional as F
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.models import resnet18, resnet50

    # batch swept on-chip: 64 -> 1509 imgs/s, 128 -> 1912, 256 -> 2026,
    # 512 -> 1933 (HBM pressure); 256 is the per-chip sweet spot.
    batch = 2 if small else int(os.environ.get("BENCH_RN_BATCH", 256))
    img = 64 if small else 224
    steps = 2 if small else 10
    paddle.seed(0)
    # NHWC: channels ride the 128-lane minor dim; 1x1 convs lower to
    # matmuls (see nn/functional.conv2d fast path) which XLA fuses with
    # the surrounding BN/ReLU elementwise work. Profiled r3 on v5e.
    fmt = os.environ.get("BENCH_RN_FORMAT", "NHWC")
    # MLPerf space-to-depth stem (exact 7x7/s2 rewrite as 4x4/s1 over 2x2
    # s2d input): fills the MXU's input-channel lanes (12 vs 3)
    stem = os.environ.get("BENCH_RN_STEM", "space_to_depth"
                          if fmt == "NHWC" else "conv")
    model = resnet18(num_classes=10, data_format=fmt) if small \
        else resnet50(data_format=fmt, stem_mode=stem)  # small: 18 has no
    # 7x7 stem benefit worth modeling; BENCH_RN_STEM applies to the full run
    model.train()
    model.astype(paddle.bfloat16)
    opt = Momentum(learning_rate=0.1, momentum=0.9, multi_precision=True)
    params = get_params(model)
    buffers = get_buffers(model)
    opt_state = opt.init(params)

    def loss_of(p, buf, x, y):
        out, new_buf = functional_call(model, p, x, buffers=buf, mutable=True,
                                       training=True)
        return F.cross_entropy(out.astype(jnp.float32), y,
                               reduction="mean"), new_buf

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x, y):
        p, buf, st = state
        (loss, new_buf), grads = jax.value_and_grad(
            loss_of, has_aux=True)(p, buf, x, y)
        new_p, new_st = opt.apply_gradients(p, grads, st, 0.1)
        return loss, (new_p, new_buf, new_st)

    rng = np.random.default_rng(0)
    shape = (batch, 3, img, img) if fmt == "NCHW" else (batch, img, img, 3)
    x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 10 if small else 1000, (batch,)),
                    jnp.int32)
    state = (params, buffers, opt_state)
    dev = jax.devices()[0]
    flops, nbytes = _compiled_cost(step, state, x, y)
    roof = _roofline_for(dev, flops, nbytes)
    m = _measure_guarded(step, state, (x, y), steps, roof)
    dt_used = m["used_s"]
    imgs_s = batch / dt_used
    mfu = flops / dt_used / _peak_flops(dev) if flops else 0.0
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.nn import fused_conv_bn  # noqa: F401  (defines flag)
    from paddle_tpu.ops._pallas import conv as _pconv  # noqa: F401
    _emit("resnet50_dp_imgs_per_sec_per_chip", imgs_s, "imgs/sec/chip", mfu,
          {"loss": m["loss"], "batch": batch, "img": img,
           "step_ms": round(dt_used * 1e3, 2),
           "pallas_conv": int(bool(_flags.flag("pallas_conv"))),
           "fused_conv_bn": int(bool(_flags.flag("fused_conv_bn"))),
           **_guard_extra(m),
           "baseline_config": 2})


# ---------------------------------------------------------------------------
# BENCH_PALLAS_CONV=1: the Pallas-vs-XLA conv A/B VERDICT r5 demands —
# a per-shape device-time table at the top-3 ResNet byte shapes, then the
# full-graph ResNet step with the kernels swapped into the fused units
# ---------------------------------------------------------------------------

def bench_pallas_conv_ab(small: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.nn import fused_conv_bn  # noqa: F401  (defines flag)
    from paddle_tpu.ops._pallas import conv as pconv
    from paddle_tpu.ops._pallas.autotune import _measure

    shapes = [(k, 2 if small else n, h, w, ci, co, s)
              for k, n, h, w, ci, co, s in pconv.RESNET50_TOP3_SHAPES]
    if not small:
        # register block configs in the persistent device-time cache so
        # the full-graph run below traces against tuned blocks
        try:
            pconv.tune_conv_shapes()
        except Exception:
            pass
    rng = np.random.default_rng(0)
    rows = []
    for kind, n, h, w, cin, cout, s_ in shapes:
        k = 1 if kind == "conv1x1" else 3
        pad = (0, 0) if k == 1 else (1, 1)
        stride = (s_, s_)
        x = jnp.asarray(rng.standard_normal((n, h, w, cin)), jnp.bfloat16)
        wgt = jnp.asarray(rng.standard_normal((cout, cin, k, k)) * 0.05,
                          jnp.bfloat16)
        scale = jnp.ones((cin,), jnp.float32)
        shift = jnp.zeros((cin,), jnp.float32)

        pallas_fn = jax.jit(functools.partial(
            pconv.conv2d_fwd, act="relu", stride=stride, padding=pad))

        dn = lax.conv_dimension_numbers(x.shape, wgt.shape,
                                        ("NHWC", "OIHW", "NHWC"))

        @jax.jit
        def xla_fn(x, wgt, scale, shift):
            a = jnp.maximum(x * scale.astype(x.dtype) +
                            shift.astype(x.dtype), 0)
            o = lax.conv_general_dilated(
                a, wgt, stride, [(pad[0], pad[0]), (pad[1], pad[1])],
                dimension_numbers=dn)
            of = o.astype(jnp.float32)
            return o, jnp.sum(of, (0, 1, 2)), jnp.sum(of * of, (0, 1, 2))

        row = {"shape": f"{kind} n{n} {h}x{w} {cin}->{cout} s{s_}"}
        for tag, fn in (("pallas_ms", pallas_fn), ("xla_ms", xla_fn)):
            try:
                row[tag] = round(_measure(
                    lambda: fn(x, wgt, scale, shift), 2, 5), 4)
            except Exception as e:
                row[tag] = None
                row[tag + "_error"] = str(e)[:200]
        if row.get("pallas_ms") and row.get("xla_ms"):
            row["speedup"] = round(row["xla_ms"] / row["pallas_ms"], 3)
        rows.append(row)
    _emit("pallas_conv_shape_ab", len(rows), "shapes", 0.0,
          {"table": rows, "note": "fused fwd (BN prologue + stats "
           "epilogue) per shape, device time; full-graph A/B follows as "
           "resnet50_dp with pallas_conv=1"})
    # full-graph A/B: the same guarded ResNet measurement with the Pallas
    # kernels swapped into the fused_conv_bn units end-to-end
    prev = _flags.get_flags(["fused_conv_bn", "pallas_conv"])
    _flags.set_flags({"fused_conv_bn": 1, "pallas_conv": 1})
    try:
        bench_resnet(small)
    finally:
        _flags.set_flags(prev)


# ---------------------------------------------------------------------------
# Config 3: BERT-base pretraining, AMP O2 (tokens/sec/chip)
# ---------------------------------------------------------------------------

def bench_bert(small: bool):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.functional import functional_call, get_params
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.bert import (BertConfig, BertForPretraining,
                                             bert_tiny)

    # swept on-chip r3: 16 -> 110k tok/s, 32 -> 117k, 64 -> 131k (sweet
    # spot; amortizes fixed costs), 128 -> 113k (HBM pressure)
    batch = 2 if small else int(os.environ.get("BENCH_BERT_BATCH", 64))
    seq = 64 if small else 512
    steps = 2 if small else 10
    paddle.seed(0)
    cfg = bert_tiny() if small else BertConfig(max_position_embeddings=512)
    cfg.hidden_dropout = 0.0
    cfg.attention_dropout = 0.0
    model = BertForPretraining(cfg)
    model.train()
    model.astype(paddle.bfloat16)  # AMP O2: bf16 params + fp32 master
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, multi_precision=True)
    params = get_params(model)
    opt_state = opt.init(params)

    def loss_of(p, ids, labels, sop):
        return functional_call(model, p, ids, None, None, labels, sop,
                               training=True)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, ids, labels, sop):
        p, st = state
        loss, grads = jax.value_and_grad(loss_of)(p, ids, labels, sop)
        new_p, new_st = opt.apply_gradients(p, grads, st, 1e-4)
        return loss, (new_p, new_st)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    sop = jnp.asarray(rng.integers(0, 2, (batch, 1)), jnp.int32)
    state = (params, opt_state)
    dev = jax.devices()[0]
    flops, nbytes = _compiled_cost(step, state, ids, labels, sop)
    roof = _roofline_for(dev, flops, nbytes)
    m = _measure_guarded(step, state, (ids, labels, sop), steps, roof)
    state = m["state"]
    dt_used = m["used_s"]
    tok_s = batch * seq / dt_used
    mfu = flops / dt_used / _peak_flops(dev) if flops else 0.0

    extra = {"loss": m["loss"], "batch": batch, "seq": seq,
             "step_ms": round(dt_used * 1e3, 2),
             **_guard_extra(m),
             "baseline_config": 3}

    if not small:
        # VERDICT r4 asks #5/#8: masked attention on the flash path (key-
        # bias block) and the PACKED varlen path (segment ids), both at a
        # realistic padding ratio, real-token throughput reported.
        rng2 = np.random.default_rng(1)
        lengths = rng2.integers(seq // 4, seq + 1, batch)
        att = (np.arange(seq)[None, :] < lengths[:, None])
        real = int(att.sum())
        att_j = jnp.asarray(att.astype(np.int32))
        pl_labels = jnp.asarray(np.where(att, np.asarray(labels), -100),
                                jnp.int32)

        def loss_padded(p, ids, att, labels):
            return functional_call(model, p, ids, None, att, labels, None,
                                   training=True)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step_padded(state, ids, att, labels):
            p, st = state
            loss, grads = jax.value_and_grad(loss_padded)(p, ids, att,
                                                          labels)
            return loss, (*opt.apply_gradients(p, grads, st, 1e-4),)

        mp = _measure_guarded(step_padded, state, (ids, att_j, pl_labels),
                              steps, roof)
        state, dtp_used = mp["state"], mp["used_s"]

        # pack the SAME real tokens into fewer rows (greedy first-fit)
        rows, row, used = [], [], 0
        srow, snext = [], 1
        for ln in lengths:
            if used + ln > seq:
                rows.append((row, srow))
                row, srow, used, snext = [], [], 0, 1
            row.append(int(ln))
            srow.append(snext)
            used += int(ln)
            snext += 1
        if row:
            rows.append((row, srow))
        n_rows = len(rows)
        ids_np = np.asarray(ids)
        pk_ids = np.zeros((n_rows, seq), np.int32)
        pk_seg = np.zeros((n_rows, seq), np.int32)
        pk_lab = np.full((n_rows, seq), -100, np.int32)
        for r, (lens, segs) in enumerate(rows):
            off = 0
            for ln, sg in zip(lens, segs):
                pk_ids[r, off:off + ln] = ids_np[0, :ln]
                pk_seg[r, off:off + ln] = sg
                pk_lab[r, off:off + ln] = np.asarray(labels)[0, :ln]
                off += ln

        def loss_packed(p, ids, seg, labels):
            return functional_call(model, p, ids, None, None, labels, None,
                                   training=True, packed_segment_ids=seg)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step_packed(state, ids, seg, labels):
            p, st = state
            loss, grads = jax.value_and_grad(loss_packed)(p, ids, seg,
                                                          labels)
            return loss, (*opt.apply_gradients(p, grads, st, 1e-4),)

        pk_args = (jnp.asarray(pk_ids), jnp.asarray(pk_seg),
                   jnp.asarray(pk_lab))
        # packed rows < batch → fewer FLOPs; reuse the main roofline only
        # as a permissive floor scaled by row count
        mk = _measure_guarded(step_packed, state, pk_args, steps,
                              roof * n_rows / batch)
        state, dtk_used = mk["state"], mk["used_s"]
        extra.update({
            "padded_anomaly": mp["anomaly"],
            "packed_anomaly": mk["anomaly"],
            "padding_ratio": round(1 - real / (batch * seq), 3),
            "padded_real_tokens_per_sec": round(real / dtp_used, 1),
            "packed_real_tokens_per_sec": round(real / dtk_used, 1),
            "packed_rows": n_rows,
            "padded_step_ms": round(dtp_used * 1e3, 2),
            "packed_step_ms": round(dtk_used * 1e3, 2),
        })

    _emit("bert_base_amp_o2_tokens_per_sec_per_chip", tok_s,
          "tokens/sec/chip", mfu, extra)


# ---------------------------------------------------------------------------
# Config 5: ERNIE through the pipeline train step (tokens/sec/chip)
# ---------------------------------------------------------------------------

def _ernie_pp_probe(pl, params, ids, labels, dev, n_stages, n_micro,
                    steps):
    """Measure the pp schedule MACHINERY on one chip (VERDICT r5 ask #3,
    third carry-over): run the real n_stages-stage 1F1B tick schedule with
    all stages serially resident (pipeline_schedule.spmd_pipeline_serial —
    identical tick/ring/bubble structure, ppermute serialized) against the
    plain microbatch loop over the same stages. The ideal time ratio is
    the bubble, (n_micro + S - 1) / n_micro; anything beyond it is
    schedule machinery (tick scan, ring shifts, output masking), reported
    as pp{S}_machinery_overhead_pct. Rooflines come from each probe
    step's COMPILED executable cost, not the analytic 6N."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.pipeline_schedule import build_serial_probe
    from paddle_tpu.optimizer import AdamW

    probe = build_serial_probe(pl, n_stages, n_micro, remat=True)
    if probe is None:
        return {"error": f"trunk not homogeneous over {n_stages} stages"}
    loss_sched, loss_plain, _ = probe
    opt = AdamW(learning_rate=1e-4, multi_precision=True)

    def make_step(loss_of):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def stp(state, ids, labels):
            p, st = state
            loss, grads = jax.value_and_grad(loss_of)(p, ids, labels)
            return loss, opt.apply_gradients(p, grads, st, 1e-4)
        return stp

    out, times = {}, {}
    for tag, lf in (("plain", loss_plain), ("sched", loss_sched)):
        stp = make_step(lf)
        # fresh param copies per tag: the probe steps donate their state,
        # and the PipelineLayer's own arrays must survive for the main
        # measurement that follows
        p0 = {k: jnp.copy(v) for k, v in params.items()}
        state = (p0, opt.init(p0))
        flops, nbytes = _compiled_cost(stp, state, ids, labels)
        roof = _roofline_for(dev, flops, nbytes)
        m = _measure_guarded(stp, state, (ids, labels), steps, roof,
                             n_windows=2)
        m.pop("state")
        times[tag] = m["used_s"]
        out[tag] = {"step_ms": round(m["used_s"] * 1e3, 2),
                    "timing": m["timing"], "anomaly": m["anomaly"],
                    "roofline_ms": m["roofline_ms"],
                    "compiled_gflops": round(flops / 1e9, 2),
                    "compiled_gb": round(nbytes / 2**30, 3)}
    ratio = (n_micro + n_stages - 1) / n_micro
    overhead = times["sched"] / (times["plain"] * ratio) - 1.0
    out["n_stages"] = n_stages
    out["n_micro"] = n_micro
    out["ideal_bubble_ratio"] = round(ratio, 4)
    out["machinery_overhead_pct"] = round(100.0 * overhead, 2)
    return out


def bench_ernie(small: bool):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import \
        PipelineLayer
    from paddle_tpu.distributed.pipeline_schedule import \
        make_pipeline_train_step
    from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                                 set_hybrid_mesh)
    from paddle_tpu.framework.functional import get_params
    from paddle_tpu.nn import functional as F
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.ernie import (ernie_base, ernie_tiny,
                                              ernie_pipeline_descs)

    # swept on-chip r3: 16 -> 101k tok/s, 32 -> 109k, 64 -> 120k (sweet
    # spot), 128 -> 95k (HBM pressure)
    batch = 4 if small else int(os.environ.get("BENCH_ERNIE_BATCH", 64))
    seq = 32 if small else 512
    steps = 2 if small else 10
    n_micro = 4
    cfg = ernie_tiny(num_layers=2) if small else \
        ernie_base(max_position_embeddings=512)
    cfg.hidden_dropout = 0.0
    cfg.attention_dropout = 0.0
    paddle.seed(0)
    # One chip: pp degree 1 (the pp=4 schedule itself is validated by
    # dryrun_multichip and the CPU-mesh pipeline tests).
    mesh = create_hybrid_mesh(pp=1, dp=1, devices=jax.devices()[:1])
    set_hybrid_mesh(mesh)

    def loss_fn(logits, labels):
        return jnp.mean(F.cross_entropy(logits.astype(jnp.float32), labels,
                                        reduction="none"))

    pl = PipelineLayer(layers=ernie_pipeline_descs(cfg), num_stages=1,
                       loss_fn=loss_fn)
    pl.astype(paddle.bfloat16)
    opt = AdamW(learning_rate=1e-4, multi_precision=True)
    pstep = make_pipeline_train_step(pl, opt, n_microbatch=n_micro)
    params = get_params(pl)
    opt_state = opt.init(params)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)

    def step(state, ids, labels):
        p, st = state
        p, st, loss = pstep(p, st, ids, labels, jnp.float32(1e-4))
        return loss, (p, st)

    dev = jax.devices()[0]
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    # pp machinery probe FIRST — it copies params; the main measurement
    # below donates the PipelineLayer's own arrays through pstep.
    pp_stages = 4 if not small else 2
    try:
        pp_probe = _ernie_pp_probe(pl, params, ids, labels, dev,
                                   n_stages=pp_stages, n_micro=n_micro,
                                   steps=max(2, steps // 2))
    except Exception as e:
        pp_probe = {"error": f"{type(e).__name__}: {e}"[:300]}
    # Roofline from the step's COMPILED executable cost (VERDICT r5
    # weak #4: the strongest number had the weakest guard) — the pp=1
    # path of make_pipeline_train_step returns the jitted step itself,
    # so its compiled cost IS reachable; analytic 6N/token is the
    # fallback for the non-lowerable (het-dispatch) variant.
    if hasattr(pstep, "lower"):
        flops, nbytes = _compiled_cost(pstep, params, opt_state, ids,
                                       labels, jnp.float32(1e-4))
    else:
        flops, nbytes = 0.0, 0.0
    if flops:
        roof = _roofline_for(dev, flops, nbytes)
        roof_basis = "compiled"
    else:
        roof = (6 * n_params * batch * seq / _peak_flops(dev)
                if getattr(dev, "platform", "") == "tpu" else 0.0)
        roof_basis = "analytic_6N"
    m = _measure_guarded(step, (params, opt_state), (ids, labels), steps,
                         roof)
    dt_used = m["used_s"]
    tok_s = batch * seq / dt_used
    # Analytic MFU: 6N per token (encoder matmuls + untied MLM head).
    mfu = tok_s * 6 * n_params / _peak_flops(dev)
    set_hybrid_mesh(None)
    _emit("ernie_pipeline_tokens_per_sec_per_chip", tok_s, "tokens/sec/chip",
          mfu,
          {"loss": m["loss"], "batch": batch, "seq": seq, "n_micro": n_micro,
           "n_params": n_params, "step_ms": round(dt_used * 1e3, 2),
           **_guard_extra(m),
           "roofline_basis": roof_basis,
           "pp4_machinery_overhead_pct":
               pp_probe.get("machinery_overhead_pct"),
           "pp4_probe": pp_probe,
           "baseline_config": 5, "pp_degree": 1,
           "note": "single-chip: the pp=4 1F1B tick schedule is measured "
                   "with stages serially resident (pp4_probe); the "
                   "throughput metric runs num_stages=1 (microbatched) — "
                   "one chip cannot host 4 parallel stages"})


# ---------------------------------------------------------------------------
# Telemetry overhead A/B (paddle_tpu/observability): the always-on metrics
# layer must cost <1% step time — measured, not asserted.
# ---------------------------------------------------------------------------

def bench_telemetry_overhead(small: bool):
    """A/B the instrumented ``sharded.TrainStep`` with FLAGS_telemetry=off
    vs =metrics and emit ``telemetry_overhead_pct`` (min-of-windows wall
    per step, identical model/batch/seed both arms). Also exports this
    run's recorded step timeline as JSONL (BENCH_TRACE_OUT, default
    ``BENCH_timeline.jsonl``) — every bench run carries its own timeline,
    viewable with ``tools/trace_view.py``."""
    import jax  # noqa: F401
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.nn import functional as F
    from paddle_tpu.observability import metrics as _omx
    from paddle_tpu.observability import step_monitor
    from paddle_tpu.optimizer import AdamW

    batch = 32 if small else 64
    hidden = 512 if small else 2048
    steps = 20 if small else 30
    windows = 5 if small else 5

    def loss_fn(model, params, b):
        x, y = b
        return F.cross_entropy(functional_call(model, params, x), y).mean()

    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hidden)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int64)

    # ONE TrainStep serves both arms (telemetry is host-side only, outputs
    # are bitwise identical — tested in test_observability.py), so the A/B
    # compares the same executable on the same buffers and the arms can be
    # interleaved window-by-window to cancel machine drift.
    timeline = step_monitor.reset_default()  # this A/B's own timeline
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(hidden, hidden), nn.Tanh(),
                        nn.Linear(hidden, hidden), nn.Tanh(),
                        nn.Linear(hidden, 10))
    ts = make_sharded_train_step(net, AdamW(1e-3), loss_fn)
    prev = _flags.get_flags(["telemetry"])
    best = {"off": None, "metrics": None}
    try:
        float(ts.step((x, y)))  # compile + warm
        float(ts.step((x, y)))
        for _ in range(windows):
            for mode in ("off", "metrics"):
                _flags.set_flags({"telemetry": mode})
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = ts.step((x, y))
                float(loss)  # sync the window
                dt = (time.perf_counter() - t0) / steps
                best[mode] = dt if best[mode] is None \
                    else min(best[mode], dt)
    finally:
        _flags.set_flags(prev)
    t_off, t_on = best["off"], best["metrics"]
    overhead_pct = 100.0 * (t_on / t_off - 1.0)

    # timeline export: the per-step records from the metrics arm (plus any
    # earlier instrumented dispatches' series in the metrics snapshot)
    out_path = os.environ.get("BENCH_TRACE_OUT", "BENCH_timeline.jsonl")
    n_records = None
    try:
        n_records = timeline.export_jsonl(out_path)
        from paddle_tpu.observability import trace as _otrace
        n_records += _otrace.export_jsonl(out_path, append=True)
    except Exception:
        pass
    telem_series = {k: v for k, v in _omx.snapshot().items()
                    if k.startswith("telemetry.")}
    _emit("telemetry_overhead_pct", overhead_pct, "pct", 0.0, {
        "overhead_pct": round(overhead_pct, 3),
        "step_ms_off": round(t_off * 1e3, 3),
        "step_ms_metrics": round(t_on * 1e3, 3),
        "steps_per_window": steps, "windows": windows,
        "batch": batch, "hidden": hidden,
        "timeline": timeline.summary(),
        "timeline_jsonl": {"path": out_path, "records": n_records},
        "telemetry_series": telem_series,
        "note": "min-of-windows wall per instrumented sharded.TrainStep "
                "step, FLAGS_telemetry=off vs =metrics, identical "
                "model/batch/seed; view the JSONL with tools/trace_view.py",
    })


def bench_flight_recorder_overhead(small: bool):
    """A/B one instrumented ``sharded.TrainStep`` with
    FLAGS_flight_recorder=off vs =on (recorder armed to a scratch dir,
    FLAGS_telemetry=metrics both arms) and emit
    ``flight_recorder_overhead_pct`` — the crash-persistent black box
    must cost <2% step time on the CPU mesh, measured with interleaved
    windows exactly like the telemetry A/B."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.nn import functional as F
    from paddle_tpu.observability import flight_recorder as _flr
    from paddle_tpu.observability import step_monitor
    from paddle_tpu.optimizer import AdamW

    batch = 32 if small else 64
    hidden = 512 if small else 2048
    steps = 20 if small else 30
    windows = 5

    def loss_fn(model, params, b):
        x, y = b
        return F.cross_entropy(functional_call(model, params, x), y).mean()

    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hidden)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int64)

    step_monitor.reset_default()
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(hidden, hidden), nn.Tanh(),
                        nn.Linear(hidden, hidden), nn.Tanh(),
                        nn.Linear(hidden, 10))
    ts = make_sharded_train_step(net, AdamW(1e-3), loss_fn)
    run_dir = tempfile.mkdtemp(prefix="bench_flr_")
    box = _flr.arm(run_dir, role="bench", run_id="bench_flight_recorder")
    prev = _flags.get_flags(["flight_recorder", "telemetry"])
    best = {"off": None, "on": None}
    try:
        _flags.set_flags({"telemetry": "metrics"})
        float(ts.step((x, y)))  # compile + warm
        float(ts.step((x, y)))
        for _ in range(windows):
            for mode in ("off", "on"):
                _flags.set_flags({"flight_recorder": mode})
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = ts.step((x, y))
                float(loss)  # sync the window
                dt = (time.perf_counter() - t0) / steps
                best[mode] = dt if best[mode] is None \
                    else min(best[mode], dt)
    finally:
        _flags.set_flags(prev)
        _flr.disarm()
    t_off, t_on = best["off"], best["on"]
    overhead_pct = 100.0 * (t_on / t_off - 1.0)
    _meta, records, replay = _flr.replay(box.path)
    _emit("flight_recorder_overhead_pct", overhead_pct, "pct", 0.0, {
        "overhead_pct": round(overhead_pct, 3),
        "step_ms_off": round(t_off * 1e3, 3),
        "step_ms_on": round(t_on * 1e3, 3),
        "steps_per_window": steps, "windows": windows,
        "batch": batch, "hidden": hidden,
        "recorder_records": len(records),
        "recorder_frames_torn": replay["frames_torn"],
        "recorder_wrapped": replay["wrapped"],
        "note": "min-of-windows wall per instrumented sharded.TrainStep "
                "step, FLAGS_flight_recorder=off vs =on (mmap ring "
                "armed, FLAGS_telemetry=metrics both arms), identical "
                "model/batch/seed; replay the ring with "
                "tools/postmortem.py",
    })


def bench_fleet_telemetry_overhead(small: bool):
    """A/B one instrumented ``sharded.TrainStep`` with
    FLAGS_fleet_telemetry=off vs =on (exporter armed to a scratch dir,
    its daemon thread publishing CRC-framed registry snapshots at the
    default cadence, FLAGS_telemetry=metrics both arms) and emit
    ``fleet_telemetry_overhead_pct`` — the live fleet plane must cost
    <2% step time on the CPU mesh, measured with interleaved windows
    exactly like the recorder A/B above."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.nn import functional as F
    from paddle_tpu.observability import live as _live
    from paddle_tpu.observability import step_monitor
    from paddle_tpu.optimizer import AdamW

    batch = 32 if small else 64
    hidden = 512 if small else 2048
    # windows must span several export ticks at the drills' 0.2s
    # cadence, or min-of-windows would just pick an export-free window
    steps = 120 if small else 150
    windows = 4

    def loss_fn(model, params, b):
        x, y = b
        return F.cross_entropy(functional_call(model, params, x), y).mean()

    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hidden)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int64)

    step_monitor.reset_default()
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(hidden, hidden), nn.Tanh(),
                        nn.Linear(hidden, hidden), nn.Tanh(),
                        nn.Linear(hidden, 10))
    ts = make_sharded_train_step(net, AdamW(1e-3), loss_fn)
    run_dir = tempfile.mkdtemp(prefix="bench_fleet_")
    prev = _flags.get_flags(["fleet_telemetry", "telemetry"])
    best = {"off": None, "on": None}
    n = {"steps": 0}
    try:
        _flags.set_flags({"telemetry": "metrics"})
        # armed with the thread running BOTH arms: the off arm measures
        # the gate (the thread wakes, sees off, publishes nothing), the
        # on arm the full snapshot+publish path — at the 0.2s cadence
        # the drills themselves arm (FLAGS_fleet_export_interval=0.2)
        exp = _live.arm(run_dir, role="bench", interval_s=0.2)
        float(ts.step((x, y)))  # compile + warm
        float(ts.step((x, y)))
        for _ in range(windows):
            for mode in ("off", "on"):
                _flags.set_flags({"fleet_telemetry": mode})
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = ts.step((x, y))
                    n["steps"] += 1
                    _live.note_progress(n["steps"])
                float(loss)  # sync the window
                dt = (time.perf_counter() - t0) / steps
                best[mode] = dt if best[mode] is None \
                    else min(best[mode], dt)
        snap = _live.read_snapshot(exp.path)
    finally:
        _live.disarm(final_export=False)
        _flags.set_flags(prev)
    t_off, t_on = best["off"], best["on"]
    overhead_pct = 100.0 * (t_on / t_off - 1.0)
    _emit("fleet_telemetry_overhead_pct", overhead_pct, "pct", 0.0, {
        "overhead_pct": round(overhead_pct, 3),
        "step_ms_off": round(t_off * 1e3, 3),
        "step_ms_on": round(t_on * 1e3, 3),
        "steps_per_window": steps, "windows": windows,
        "batch": batch, "hidden": hidden,
        "exports_published": (snap or {}).get("seq"),
        "note": "min-of-windows wall per instrumented sharded.TrainStep "
                "step, FLAGS_fleet_telemetry=off vs =on (exporter "
                "thread armed both arms at the drills' 0.2s cadence, "
                "FLAGS_telemetry=metrics both arms), identical "
                "model/batch/seed; aggregate the snapshots with "
                "tools/fleet_top.py",
    })


# ---------------------------------------------------------------------------
# Config 4 (PRIMARY): GPT decoder LM
# ---------------------------------------------------------------------------

def bench_comm_overlap(small: bool):
    """A/B the communication-overlap tier (FLAGS_comm_overlap): the
    Megatron-SP column/row pair as decomposed bidirectional ppermute
    pipelines vs the GSPMD-scheduled step — same model/seed/batch both
    arms, loss parity asserted, min-of-windows step time per mode. Needs
    >= 2 devices on the mp axis; on a single chip the metric still emits
    the static hop plans (analysis/comm_check) for the next device round.
    """
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.analysis import comm_check
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear,
        sequence_parallel_constraint)
    from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                                 set_hybrid_mesh)
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.optimizer import AdamW

    # The GPT-1.3B per-layer hop plan (mp=4, bf16) — the A/B shapes the
    # next device round runs, emitted even when this host cannot.
    planned = [
        comm_check.spec_for_allgather_matmul(8, 512, 2048, 2048, 4, 2),
        comm_check.spec_for_matmul_reduce_scatter(8, 512, 2048, 2048, 4, 2),
    ]
    planned_rows = [{
        "op": s.name, "hops": s.hops,
        "bytes_per_hop_mb": round(s.bytes_per_hop / 2**20, 3),
        "diagnostics": [d.rule for d in comm_check.check_comm_spec(s)],
    } for s in planned]

    mp = 1
    while mp * 2 <= min(8, jax.device_count()):
        mp *= 2
    if mp < 2:
        print(json.dumps({
            "metric": "comm_overlap", "value": 0.0, "unit": "ratio",
            "extra": {"skipped": "needs >=2 devices on the mp axis",
                      "devices": jax.device_count(),
                      "planned_specs": planned_rows}}), flush=True)
        return

    d = 64 if small else 256
    seq = mp * (16 if small else 64)
    batch = 4 if small else 8
    steps = 10 if small else 20
    windows = 3

    class SPBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = ColumnSequenceParallelLinear(d, 4 * d,
                                                    gather_output=False)
            self.fc2 = RowSequenceParallelLinear(4 * d, d,
                                                 input_is_parallel=True)

        def forward(self, x):
            x = sequence_parallel_constraint(x)
            return self.fc2(jax.nn.gelu(self.fc1(x)))

    class Stack(nn.Layer):
        def __init__(self):
            super().__init__()
            self.blocks = nn.LayerList([SPBlock() for _ in range(4)])

        def forward(self, x):
            for b in self.blocks:
                x = b(x)
            return x

    def loss_fn(model, params, b):
        x, y = b
        return jnp.mean((functional_call(model, params, x,
                                         training=True) - y) ** 2)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, seq, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
    prev = _flags.get_flags(["comm_overlap"])
    results = {}
    try:
        for mode in ("off", "tp"):
            _flags.set_flags({"comm_overlap": mode})
            mesh = create_hybrid_mesh(mp=mp)
            set_hybrid_mesh(mesh)
            paddle.seed(0)
            ts = make_sharded_train_step(Stack(), AdamW(1e-3), loss_fn,
                                         mesh=mesh)
            loss = float(ts.step((x, y)))  # compile + warm
            best = None
            for _ in range(windows):
                t0 = time.perf_counter()
                for _ in range(steps):
                    out = ts.step((x, y))
                float(out)
                dt = (time.perf_counter() - t0) / steps
                best = dt if best is None else min(best, dt)
            results[mode] = {"loss": loss,
                             "step_ms": round(best * 1e3, 3)}
            set_hybrid_mesh(None)
    finally:
        _flags.set_flags(prev)
        set_hybrid_mesh(None)
    parity_ok = abs(results["tp"]["loss"] - results["off"]["loss"]) <= \
        5e-3 * max(1.0, abs(results["off"]["loss"]))
    speedup = results["off"]["step_ms"] / max(results["tp"]["step_ms"],
                                              1e-9)
    print(json.dumps({
        "metric": "comm_overlap", "value": round(speedup, 4),
        "unit": "step-time ratio off/tp",
        "extra": {"modes": results, "parity_ok": bool(parity_ok),
                  "mesh": {"mp": mp}, "shape": {"batch": batch, "seq": seq,
                                                "hidden": d, "blocks": 4},
                  "note": ("CPU-mesh wall times are not ICI-meaningful; "
                           "the device round reads this A/B on real chips"
                           if jax.default_backend() != "tpu" else
                           "device-measured"),
                  "planned_specs": planned_rows}}), flush=True)
    assert parity_ok, (
        f"comm_overlap parity failure: tp loss {results['tp']['loss']} "
        f"vs off {results['off']['loss']}")


def bench_multislice(small: bool):
    """The multi-slice tier (FLAGS_multislice, distributed/multislice):
    a 2-slice x 4-device dryrun on the CPU mesh — the hierarchical
    (ICI reduce-scatter -> DCN allreduce on the 1/ici shard -> ICI
    all-gather) TrainStep vs the naive flat per-axis psum baseline, with
    BITWISE loss parity asserted every step, the per-link hop-plan table
    emitted, and `multislice_dcn_bytes_per_step` measured from the
    declared plan (== bucket_bytes / ici_size; the flat plan's DCN bytes
    are the full bucket and comm_check C004 flags it). Chipless by
    design: the next chip round is a flag flip on a real 2-slice mesh."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.analysis import comm_check
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.distributed.multislice import (HierarchicalGradReducer,
                                                   SliceTopology)
    from paddle_tpu.distributed.topology import set_hybrid_mesh
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    n_dev = jax.device_count()
    if n_dev < 4:
        print(json.dumps({
            "metric": "multislice_dcn_bytes_per_step", "value": 0.0,
            "unit": "bytes",
            "extra": {"skipped": "needs >=4 devices for the 2-slice mesh",
                      "devices": n_dev}}), flush=True)
        return
    dp = 4 if n_dev >= 8 else n_dev // 2
    topo = SliceTopology(2, dp=dp)
    hidden = 64 if small else 128
    steps = 3 if small else 5
    cfg = GPTConfig(vocab_size=128, hidden_size=hidden, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_flash_attention=False)

    def loss_fn(m, p, b):
        ids, labels = b
        return functional_call(m, p, ids, labels, training=True)

    rng = np.random.default_rng(0)
    batches = [(jnp.asarray(rng.integers(0, 128, (2 * 2 * dp, 32)),
                            jnp.int32),) * 2 for _ in range(steps)]

    prev = _flags.get_flags(["multislice"])
    results = {}
    try:
        for mode in ("flat", "hierarchical"):
            _flags.set_flags({"multislice": mode})
            set_hybrid_mesh(topo.mesh)
            paddle.seed(0)
            ts = make_sharded_train_step(
                GPTForCausalLM(cfg), AdamW(1e-3), loss_fn,
                mesh=topo.mesh, fsdp_axis=None)
            t0 = time.perf_counter()
            losses = [float(ts.step(b)) for b in batches]
            dt = (time.perf_counter() - t0) / steps
            results[mode] = {"losses": losses,
                             "step_ms": round(dt * 1e3, 3),
                             "grads_bytes": sum(
                                 int(v.size) * v.dtype.itemsize
                                 for v in ts.params.values())}
            set_hybrid_mesh(None)
    finally:
        _flags.set_flags(prev)
        set_hybrid_mesh(None)

    parity_bitwise = results["flat"]["losses"] == \
        results["hierarchical"]["losses"]
    # the declared hop plans (per link class) + the DCN-bytes metric
    reducer = HierarchicalGradReducer(axis="dp", dcn_axis="slice")
    grads = {f"g{i}": np.zeros((results["hierarchical"]["grads_bytes"]
                                // 4,), np.float32) for i in range(1)}
    rows = []
    for mode in ("hierarchical", "flat"):
        for spec in reducer.hop_plan(grads, topo.ici_size,
                                     topo.num_slices, mode=mode):
            rows.append({
                "mode": mode, "stage": spec.name, "link": spec.link,
                "axis": spec.axis, "hops": spec.hops,
                "payload_mb": round(spec.payload_bytes / 2**20, 4),
                "diagnostics": [d.rule for d in
                                comm_check.check_comm_spec(spec)],
            })
    dcn_bytes = reducer.dcn_bytes_per_step(grads, topo.ici_size,
                                           topo.num_slices)
    flat_dcn = reducer.dcn_bytes_per_step(grads, topo.ici_size,
                                          topo.num_slices, mode="flat")
    c004_on_flat = any("C004" in r["diagnostics"] for r in rows
                      if r["mode"] == "flat")
    c004_on_hier = any("C004" in r["diagnostics"] for r in rows
                      if r["mode"] == "hierarchical")
    print(json.dumps({
        "metric": "multislice_dcn_bytes_per_step", "value": dcn_bytes,
        "unit": "bytes/rank (one direction)",
        "extra": {
            "mesh": {"slice": topo.num_slices, "dp": dp,
                     "ici_size": topo.ici_size},
            "modes": results,
            "parity_bitwise": bool(parity_bitwise),
            "flat_dcn_bytes_per_step": flat_dcn,
            "dcn_reduction_factor": round(flat_dcn / max(dcn_bytes, 1),
                                          2),
            "hop_plan": rows,
            "c004_fires_on_flat": bool(c004_on_flat),
            "c004_silent_on_hierarchical": bool(not c004_on_hier),
            "note": ("CPU-mesh wall times are not DCN-meaningful; the "
                     "plan table and the parity are the chipless "
                     "deliverable" if jax.default_backend() != "tpu"
                     else "device-measured"),
        }}), flush=True)
    assert parity_bitwise, (
        f"multislice parity failure: hierarchical losses "
        f"{results['hierarchical']['losses']} vs flat "
        f"{results['flat']['losses']}")
    assert c004_on_flat and not c004_on_hier, (
        "C004 must fire on the naive flat-over-DCN plan and stay silent "
        "on the hierarchical one")


def _gpt_measure(layers, hidden, heads, seq, batch, steps, remat, vocab):
    """Build + time one GPT train-step config under the anomaly guard.

    Returns (measurement_dict, n_params): guarded min-of-N wall + device
    windows against the compiled-cost roofline floor (_measure_guarded)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.functional import functional_call, get_params
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=seq,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    recompute=remat)
    model = GPTForCausalLM(cfg)
    model.train()
    # AMP O2: bf16 params/compute, fp32 master weights in the optimizer.
    model.astype(paddle.bfloat16)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, multi_precision=True)

    params = get_params(model)
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    opt_state = opt.init(params)

    def loss_fn(p, ids, labels):
        return functional_call(model, p, ids, labels, training=True)

    def one_step(state, ids, labels):
        p, st = state
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
        new_p, new_st = opt.apply_gradients(p, grads, st, 1e-4)
        return loss, (new_p, new_st)

    # (a lax.scan over steps — one dispatch — was tried to hide the
    # host's per-dispatch latency, but XLA double-buffers the multi-GB
    # carry at L=12, costing far more than it saves)
    step = functools.partial(jax.jit, donate_argnums=(0,))(one_step)

    batches = _gpt_batches(batch, seq, vocab)
    state = (params, opt_state)
    dev = jax.devices()[0]
    flops, nbytes = _compiled_cost(step, state, *batches[0])
    roof = _roofline_for(dev, flops, nbytes)
    m = _measure_guarded(step, state, batches[0], steps, roof,
                         args_seq=batches)
    m.pop("state")
    return m, n_params


def _gpt_batches(batch, seq, vocab, pool=16):
    """A pool of DISTINCT synthetic (ids, labels) batches, cycled one per
    step by the guarded measurement — the reported loss then reflects real
    optimization across batches, not memorization of a single batch
    (VERDICT r5 weak #3: loss_at_l6 = 0.027 after 10 same-batch steps)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    out = []
    for _ in range(pool):
        ids = rng.integers(0, vocab, (batch, seq))
        out.append((jnp.asarray(ids, jnp.int32),
                    jnp.asarray(np.roll(ids, -1, axis=1), jnp.int32)))
    return out


def _gpt_flops_per_token(n_params, layers, seq, hidden):
    # Model FLOPs per token: 6N (fwd+bwd matmuls) + causal attention
    # 12*L*seq*hidden/2 (QK^T + PV, fwd+bwd, halved by causal masking).
    return 6 * n_params + 6 * layers * seq * hidden


def _gpt_13b_measured_path(mode, layers, hidden, heads, seq, vocab,
                           steps=3, budget_gb=None):
    """One REAL full-depth fwd+bwd+update GPT step (ISSUE r6 tentpole).

    mode "sgd_no_moment": SGD(multi_precision) — no moments, everything
    resident (~6 B/param): the zero-transfer baseline that fits HBM.
    mode "adam_offload_moments": the BASELINE-faithful AdamW, its 8 B/param
    of moments parked in pinned host memory and streamed through HBM per
    block by framework/offload.StreamingUpdate — full-depth Adam on one
    chip, which 14 B/param resident cannot do.

    Batch is the largest of (4, 2, 1) whose tools/hbm_budget plan fits;
    the plan rides along in the result. Returns (measurement, n_params,
    batch, plan).
    """
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework import offload
    from paddle_tpu.framework.functional import functional_call, get_params
    from paddle_tpu.optimizer import SGD, AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    from tools import hbm_budget

    resident = mode == "sgd_no_moment"
    kwargs = dict(layers=layers, hidden=hidden, heads=heads, seq=seq,
                  vocab=vocab, optimizer="sgd" if resident else "adamw",
                  offload="off" if resident else "moments", remat=True)
    if budget_gb is not None:
        kwargs["budget_gb"] = budget_gb
    batch, plan = hbm_budget.choose_batch(**kwargs)
    if batch is None:
        raise RuntimeError(f"no batch in (4,2,1) fits HBM: {plan}")

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=seq,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    recompute=True)
    model = GPTForCausalLM(cfg)
    model.train()
    model.astype(paddle.bfloat16)
    params = get_params(model)
    n_params = sum(int(np.prod(p.shape)) for p in params.values())

    def loss_fn(p, ids, labels):
        return functional_call(model, p, ids, labels, training=True)

    dev = jax.devices()[0]
    if resident:
        opt = SGD(learning_rate=1e-4, multi_precision=True)
        state = (params, opt.init(params))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(st, ids, labels):
            p, s = st
            loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
            return loss, opt.apply_gradients(p, grads, s, 1e-4)

        batches = _gpt_batches(batch, seq, vocab, pool=8)
        flops, nbytes = _compiled_cost(step, state, *batches[0])
    else:
        opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                    multi_precision=True)
        stream = offload.StreamingUpdate(opt)
        # moments are born host-side param-by-param — the full 10.5 GB
        # moment set never exists in HBM (offload.init_state)
        state = (params, stream.init_state(params))
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))

        def step(st, ids, labels):
            p, s = st
            loss, grads = grad_fn(p, ids, labels)
            return loss, stream.update(p, grads, s, 1e-4)

        batches = _gpt_batches(batch, seq, vocab, pool=8)
        # roofline floor from the grad program only — a valid lower bound
        # (the streamed update adds compute + host-link time on top)
        flops, nbytes = _compiled_cost(grad_fn, params, *batches[0])
    roof = _roofline_for(dev, flops, nbytes)
    m = _measure_guarded(step, state, batches[0], steps, roof,
                         args_seq=batches)
    m.pop("state")
    return m, n_params, batch, plan


def bench_fault(small: bool):
    """Fault-tolerance goodput, measured (ISSUE 7 / ROADMAP item 5): run
    the elastic kill-and-resume drill (tools/fault_drill.py machinery —
    SIGKILL mid-step AND mid-checkpoint-write, relaunch, resume from
    latest_complete) and emit goodput = useful_step_time /
    wall_time_including_restart plus restart count, lost steps, and
    checkpoint save/restore durations. Bitwise loss parity vs the
    uninterrupted reference is asserted as part of the record — a bench
    number from a run that did NOT recover exactly would be meaningless."""
    import tempfile

    from paddle_tpu.fault import drill

    def _pm_summary(rep):
        pm = rep.get("postmortem") or {}
        pc = pm.get("plan_check") or {}
        return {
            "ok": pm.get("ok"), "coherent": pm.get("coherent"),
            "recorder_files": pm.get("recorder_files"),
            "last_committed_steps": pm.get("last_committed_steps"),
            "deaths": [(d["kind"], d["step"])
                       for d in pm.get("deaths", [])],
            "plan_matches": pc.get("matches"),
            "kill_order_ok": pc.get("kill_order_ok"),
        }

    def _pm_timeline(drill_name, rep):
        # machine-readable postmortem + live-fleet records per drill
        # run, riding the shared timeline JSONL like the serving/health
        # records do
        out_path = os.environ.get("BENCH_TRACE_OUT",
                                  "BENCH_timeline.jsonl")
        try:
            with open(out_path, "a") as f:
                f.write(json.dumps({"kind": "postmortem",
                                    "drill": drill_name,
                                    **_pm_summary(rep)}) + "\n")
                fl = rep.get("fleet")
                if fl:
                    f.write(json.dumps({
                        "kind": "fleet_live", "drill": drill_name,
                        **{k: fl.get(k) for k in (
                            "workers", "incarnations_seen",
                            "silent_incarnations", "final_status",
                            "final_step", "ok")}}) + "\n")
        except OSError:
            pass

    cfg = drill.quick_config()
    if not small:
        cfg.update(total_steps=16, ckpt_every=4)
    workdir = tempfile.mkdtemp(prefix="bench_fault_")
    report = drill.run_drill(workdir, **cfg)
    g = report.get("goodput_record", {})
    parity = report.get("parity", {})
    if report.get("rc") != 0 or "goodput" not in g:
        raise RuntimeError(f"fault drill failed: rc={report.get('rc')} "
                           f"{report.get('error', '')}")
    _emit("fault_tolerance_goodput_pct", g["goodput"] * 100.0,
          "pct useful-step/wall", 0.0,
          {"goodput": g["goodput"],
           "restarts": g["restarts"],
           "lost_steps": g["lost_steps"],
           "useful_step_s": g["useful_step_s"],
           "wall_s": g["wall_s"],
           "ckpt_save_ms": g["ckpt_save"],
           "ckpt_restore_ms": g["ckpt_restore"],
           "steps": cfg["total_steps"],
           "plan": report["plan"]["events"],
           "fired": report.get("fired_events"),
           "parity_bitwise": parity.get("bitwise_equal"),
           "postmortem": _pm_summary(report),
           "method": ("subprocess elastic drill on the CPU mesh: "
                      "deterministic FaultPlan kills the trainer mid-step "
                      "and mid-checkpoint-write; ElasticManager "
                      "relaunches; resume from latest_complete(); wall "
                      "time includes process startup, recompile, restore "
                      "and re-executed steps")})
    if not parity.get("bitwise_equal"):
        raise RuntimeError(f"fault drill parity broken: {parity}")
    _pm_timeline("fault", report)
    if report.get("postmortem") and not report["postmortem"]["ok"]:
        raise RuntimeError(
            f"fault drill postmortem incoherent: "
            f"{report['postmortem']['coherence']} "
            f"plan_check={report['postmortem']['plan_check']}")

    # -- the training-health leg: the chained --health drill (2 kills +
    # inject_nan + inject_hang over the guarded trainer) measured the
    # same way — detection latency in steps and the goodput of a run
    # that detected, rewound, skipped and still matched bitwise
    hcfg = drill.quick_health_config()
    hworkdir = tempfile.mkdtemp(prefix="bench_health_")
    hreport = drill.run_drill(hworkdir, **hcfg)
    hg = hreport.get("goodput_record", {})
    hparity = hreport.get("parity", {})
    hh = hreport.get("health", {})
    if hreport.get("rc") != 0 or "goodput" not in hg:
        raise RuntimeError(
            f"health drill failed: rc={hreport.get('rc')} "
            f"{hreport.get('error', '')}")
    latency = hg.get("detection_latency_steps", {})
    _emit("health_detection_latency_steps", float(latency.get("max", 0)),
          "steps (max over anomalies)", 0.0,
          {"latencies": hh.get("detection_latency_steps"),
           "anomalies": [
               {k: a.get(k) for k in ("kind", "step", "latency_steps")}
               for a in hh.get("anomalies", [])],
           "plan": hreport["plan"]["events"],
           "parity_bitwise": hparity.get("bitwise_equal")})
    _emit("health_recovery_goodput_pct", hg["goodput"] * 100.0,
          "pct useful-step/wall", 0.0,
          {"goodput": hg["goodput"],
           "restarts": hg["restarts"],
           "lost_steps": hg["lost_steps"],
           "rewound_steps": hg["rewound_steps"],
           "skipped_batches": hg["skipped_batches"],
           "parity_bitwise": hparity.get("bitwise_equal"),
           "postmortem": _pm_summary(hreport),
           "method": ("tools/fault_drill.py --quick --health machinery: "
                      "guarded trainer (fused sentinel, hang watchdog, "
                      "SDC canary, Guardian rewind-and-skip) under 2 "
                      "SIGKILLs + 1 injected NaN + 1 injected hang; "
                      "parity vs a clean run handed the same "
                      "poisoned-batch skip set")})
    if not hparity.get("bitwise_equal"):
        raise RuntimeError(f"health drill parity broken: {hparity}")
    _pm_timeline("health", hreport)
    if hreport.get("postmortem") and not hreport["postmortem"]["ok"]:
        raise RuntimeError(
            f"health drill postmortem incoherent: "
            f"{hreport['postmortem']['coherence']} "
            f"plan_check={hreport['postmortem']['plan_check']}")
    # the health records ride the shared timeline JSONL like the serving
    # request records do
    out_path = os.environ.get("BENCH_TRACE_OUT", "BENCH_timeline.jsonl")
    try:
        with open(out_path, "a") as f:
            f.write(json.dumps({
                "kind": "health_drill",
                "detection_latency_steps_max": latency.get("max", 0),
                "recovery_goodput": hg["goodput"],
                "restarts": hg["restarts"],
                "rewound_steps": hg["rewound_steps"],
                "skipped_batches": hg["skipped_batches"],
                "anomaly_kinds": [a.get("kind")
                                  for a in hh.get("anomalies", [])],
                "parity_bitwise": hparity.get("bitwise_equal"),
            }) + "\n")
    except OSError:
        pass


# ---------------------------------------------------------------------------
# BENCH_SERVE: serving engine — continuous batching vs one-shot predictor
# ---------------------------------------------------------------------------

def _serve_trace(n_req, vocab, lo, hi, max_new, seed=0):
    from paddle_tpu.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt_ids=rng.integers(
                        0, vocab, int(rng.integers(lo, hi + 1))
                    ).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n_req)]


def bench_serve(small: bool):
    """Serving tier (ISSUE 8 / ROADMAP item 1): measured tokens/s and
    exact p50/p99 request latency for the paged-KV continuous-batching
    engine over a concurrent ragged-request trace, A/B'd against the
    sequential one-shot ``Predictor.run`` baseline — the seed inference
    tier's serving story: one request at a time, a full forward over the
    growing context per token, no KV reuse (its compile count is held to
    the bucket ladder by the new symbolic-dim padding). Per-request
    outputs are anchored against ``model.generate`` (greedy); the
    compile-budget gate asserts <= n_buckets executable signatures with
    the O001 sentinel silent on BOTH paths. The concurrency sweep rises
    from max_batch=1 (sequential, still KV-cached) to the headline
    width — the continuous-batching win curve."""
    import tempfile

    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.observability import request_timeline
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

    e = os.environ.get
    n_req = int(e("BENCH_SERVE_REQUESTS", 6 if small else 12))
    max_new = int(e("BENCH_SERVE_MAX_NEW", 6 if small else 10))
    layers = int(e("BENCH_SERVE_LAYERS", 2 if small else 3))
    hidden = int(e("BENCH_SERVE_HIDDEN", 96 if small else 192))
    heads = int(e("BENCH_SERVE_HEADS", 4 if small else 6))
    vocab = int(e("BENCH_SERVE_VOCAB", 384 if small else 512))
    lo, hi, max_pos = 4, 40, 128
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=heads, max_position_embeddings=max_pos))
    model.eval()
    trace = _serve_trace(n_req, vocab, lo, hi, max_new)
    total_new = sum(r.max_new_tokens for r in trace)

    # correctness anchor: greedy generate with the dense per-request cache
    refs = {r.rid: np.asarray(model.generate(
        jnp.asarray(r.prompt_ids[None]),
        max_new_tokens=r.max_new_tokens))[0] for r in trace}

    def run_engine(max_batch):
        eng = ServingEngine(model, block_size=8, num_blocks=96,
                            max_batch=max_batch, max_seq_len=max_pos)
        eng.serve(trace)               # warm pass: pay the bucket compiles
        rt = request_timeline.reset_default()
        t0 = time.perf_counter()
        done = eng.serve(trace)
        wall = time.perf_counter() - t0
        s = rt.summary()
        match = sum(np.array_equal(done[r.rid].output, refs[r.rid])
                    for r in trace) / len(trace)
        return {"max_batch": max_batch,
                "tokens_per_s": round(total_new / wall, 2),
                "wall_s": round(wall, 4),
                "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                "ttft_p50_ms": s["ttft_p50_ms"],
                "ttft_p99_ms": s["ttft_p99_ms"],
                "preemptions": s["preemptions"],
                "match_fraction": round(match, 4)}, eng

    widths = [1, 2, 4] if small else [1, 2, 4, 8]
    sweep = []
    eng = None
    for w in widths:
        point, eng = run_engine(w)
        sweep.append(point)
    headline = sweep[-1]
    creport = eng.compile_report()
    # measured run's per-request phase records ride the shared timeline
    out_path = os.environ.get("BENCH_TRACE_OUT", "BENCH_timeline.jsonl")
    try:
        request_timeline.current().export_jsonl(out_path, append=True)
    except OSError:
        pass

    # sequential one-shot baseline (the seed predictor serving flow)
    workdir = tempfile.mkdtemp(prefix="bench_serve_")
    paddle.jit.save(model, os.path.join(workdir, "gpt"),
                    input_spec=[((1, "s"), "int32")])
    pred = create_predictor(Config(os.path.join(workdir, "gpt")))

    def one_shot(r):
        ids = list(r.prompt_ids)
        for _ in range(r.max_new_tokens):
            logits = pred.run([np.asarray([ids], np.int32)])[0]
            ids.append(int(np.argmax(logits[0, len(ids) - 1])))
        return np.asarray(ids, np.int32)

    for r in trace[:2]:
        one_shot(r)                    # warm the bucket executables
    t0 = time.perf_counter()
    seq_out = {r.rid: one_shot(r) for r in trace}
    seq_wall = time.perf_counter() - t0
    seq_tps = total_new / seq_wall if seq_wall else 0.0
    seq_match = sum(np.array_equal(seq_out[r.rid], refs[r.rid])
                    for r in trace) / len(trace)
    pred_report = pred.bucket_report()

    speedup = headline["tokens_per_s"] / seq_tps if seq_tps else 0.0
    extra = {
        "config": {"layers": layers, "hidden": hidden, "heads": heads,
                   "vocab": vocab, "requests": n_req, "max_new": max_new,
                   "prompt_lens": [int(r.prompt_ids.size) for r in trace]},
        "concurrency_sweep": sweep,
        "p50_ms": headline["p50_ms"], "p99_ms": headline["p99_ms"],
        "ttft_p50_ms": headline["ttft_p50_ms"],
        "ttft_p99_ms": headline["ttft_p99_ms"],
        "sequential_tokens_per_s": round(seq_tps, 2),
        "sequential_wall_s": round(seq_wall, 4),
        "speedup_vs_one_shot": round(speedup, 2),
        "match_fraction": headline["match_fraction"],
        "sequential_match_fraction": round(seq_match, 4),
        "compile_report": creport,
        "predictor_bucket_report": pred_report,
        "method": ("continuous batching (paged KV, bucketed shapes) vs "
                   "the one-shot AOT predictor re-running the full "
                   "forward per token, same ragged trace, greedy; "
                   "engine outputs anchored token-exact against "
                   "model.generate; both paths warmed before timing"),
    }
    _emit("serving_tokens_per_s", headline["tokens_per_s"], "tokens/s",
          0.0, extra)
    _emit("serving_p50_ms", headline["p50_ms"], "ms", 0.0,
          {"max_batch": headline["max_batch"]})
    _emit("serving_p99_ms", headline["p99_ms"], "ms", 0.0,
          {"max_batch": headline["max_batch"]})
    if headline["match_fraction"] < 0.75:
        raise RuntimeError(
            f"serving outputs diverged from model.generate: "
            f"match {headline['match_fraction']}")
    if not creport["within_budget"] or creport["o001_fired"]:
        raise RuntimeError(f"serving compile budget violated: {creport}")
    if pred_report["o001_fired"]:
        raise RuntimeError(
            f"predictor bucket padding failed (O001 fired): {pred_report}")
    if speedup < 2.0:
        raise RuntimeError(
            f"continuous batching speedup {speedup:.2f}x < 2x over the "
            f"sequential one-shot baseline")

    bench_serve_resilience(model, max_pos, vocab, small)
    if os.environ.get("BENCH_SERVE_TIERS", "1") != "0":
        bench_serve_throughput_tiers(small)


def bench_serve_resilience(model, max_pos, vocab, small: bool):
    """Serving resilience (ISSUE 9): the SLO half of BENCH_SERVE.

    Two measured components, emitted as serving_slo_attainment_pct +
    serving_shed_rate:

    - the **subprocess serve drill** (tools/serve_drill.py machinery):
      SIGKILL the serving worker mid-decode and mid-spill, relaunch,
      replay unacknowledged requests from the fsynced journal — zero
      lost, zero duplicated, survivors token-exact vs model.generate;
    - a **fault-injected overload trace** on a deliberately starved
      engine: tight deadlines + mixed priorities, bounded admission
      (max_waiting), the shed policy armed in degrade mode, one request
      that outgrows the pool (validate_capacity=False — it must FAIL
      per-request, never crash the loop), and a SpillError injected
      through the serve.mid_spill seam. SLO attainment = fraction of
      deadline-carrying requests answered in time; shed rate =
      (shed + rejected) / submitted.
    """
    import tempfile

    from paddle_tpu.fault.injection import register_fire_point
    from paddle_tpu.observability import request_timeline
    from paddle_tpu.serving import (Request, ServingEngine, ShedPolicy,
                                    SpillError, Status)
    from paddle_tpu.serving import drill as serve_drill

    # -- (1) the kill-and-replay drill (subprocess pod) ---------------------
    drill_dir = tempfile.mkdtemp(prefix="bench_serve_drill_")
    drill_report = serve_drill.run_serve_drill(drill_dir)
    if not drill_report.get("ok"):
        raise RuntimeError(f"serve drill failed: {drill_report}")
    once = drill_report["exactly_once"]
    fl = drill_report.get("fleet") or {}
    try:
        with open(os.environ.get("BENCH_TRACE_OUT",
                                 "BENCH_timeline.jsonl"), "a") as f:
            f.write(json.dumps({
                "kind": "fleet_live", "drill": "serve",
                **{k: fl.get(k) for k in (
                    "workers", "incarnations_seen",
                    "silent_incarnations", "final_status",
                    "live_goodput", "postmortem_goodput",
                    "goodput_match", "ok")}}) + "\n")
    except OSError:
        pass

    # -- (2) fault-injected overload trace ----------------------------------
    # The pool hog goes FIRST (closed-loop serve submits in order, so it
    # lands inside the bounded queue): its 120-token prompt takes all 15
    # usable blocks at admission and its first decode token needs a 16th
    # -> it must FAIL per-request (OutOfBlocks isolated), never a crash.
    rng = np.random.default_rng(11)
    n_over = 10 if small else 16
    trace = [Request(rid="hog", prompt_ids=rng.integers(0, vocab, 120),
                     max_new_tokens=8, deadline_s=120.0, priority=2)]
    for i in range(n_over):
        plen = int(rng.integers(16, 33))
        # a third of the trace gets an unattainable deadline (guaranteed
        # expiry), the rest a generous one; priorities split the classes;
        # 2-4 prompt blocks + 2 blocks of growth x 4-wide overcommits the
        # 15-block pool, so the LIFO preemption/spill path runs hot
        tight = i % 3 == 2
        trace.append(Request(
            rid=f"ov{i}", prompt_ids=rng.integers(0, vocab, plen),
            max_new_tokens=16, deadline_s=0.001 if tight else 120.0,
            priority=0 if tight else 1))

    rt = request_timeline.reset_default()
    eng = ServingEngine(
        model, block_size=8, num_blocks=16, max_batch=4,
        max_seq_len=max_pos, max_waiting=8,
        shed_policy=ShedPolicy(min_free_block_frac=0.2,
                               max_p99_decode_ms=5e3, degrade=True),
        validate_capacity=False)
    state = {"spills": 0}

    def spill_bomb():  # the in-process fault: first spill's host commit dies
        state["spills"] += 1
        if state["spills"] == 1:
            raise SpillError("injected host allocation failure "
                             "(BENCH_SERVE resilience leg)")

    register_fire_point("serve.mid_spill", spill_bomb)
    try:
        results = eng.serve(trace)
    finally:
        register_fire_point("serve.mid_spill", None)

    # the engine degraded instead of dying: loop drained, zero leaks
    eng.sched.assert_idle()
    if eng.cache.allocator.n_used != 0:
        raise RuntimeError(
            f"overload trace leaked {eng.cache.allocator.n_used} KV blocks")
    hog = results["hog"]
    if getattr(hog, "status", None) is not Status.FAILED:
        raise RuntimeError(
            "pool-exhaustion request was expected to FAIL per-request "
            f"(engine survival proof), got {hog!r}")

    s = rt.summary()
    slo = s["slo_attainment_pct"]
    if slo is None:
        raise RuntimeError(f"no deadline-carrying records: {s}")
    extra = {
        "outcomes": s["outcomes"],
        "requests": len(trace),
        "served": s["served"],
        "deadline_expired": s["outcomes"].get("expired", 0),
        "engine_mode_final": eng.mode,
        "injected_spill_fault": True,
        "pool_exhaustion_isolated": True,
        "drill": {
            "wall_s": drill_report["wall_s"],
            "fired_events": drill_report["fired_events"],
            "restarts": drill_report["restarts"],
            "lost": once["lost"], "duplicated": once["duplicated"],
            "token_exact": drill_report["token_exact"],
            "served": drill_report["served"],
        },
        "method": ("fault-injected overload trace on a starved engine "
                   "(16-block pool, max_waiting=8, shed policy armed in "
                   "degrade mode, SpillError injected at the first host "
                   "spill, one request outgrowing the pool) + the "
                   "subprocess serve drill (SIGKILL mid-decode and "
                   "mid-spill, exactly-once journal replay, token-exact "
                   "survivors)"),
    }
    _emit("serving_slo_attainment_pct", slo, "pct requests in deadline",
          0.0, extra)
    _emit("serving_shed_rate", s["shed_rate"],
          "shed+rejected / submitted", 0.0,
          {"outcomes": s["outcomes"], "max_waiting": 8,
           "shed_policy": repr(eng.shed_policy)})


def bench_serve_throughput_tiers(small: bool):
    """Serving throughput rung 2 (ISSUE 13): the three flag-gated tiers
    measured on a compute-dominant CPU-mesh config (prompts long enough
    that prefill FLOPs, not dispatch latency, carry the comparison):

    - **prefix leg** — a shared-system-prompt workload replayed at share
      ratios 0/0.5/0.8 through the engine with and without the radix
      tree: the prefix-hit-rate x tokens/s curve, with tokens/s >= 1.5x
      and peak live blocks (cache-idle tree holds excluded — they evict
      on demand) reduced >= 2x GATED at the 80% ratio;
    - **chunked leg** — residents decoding while a long prompt arrives:
      max step wall (the resident-visible stall) with the chunked
      budget must undercut the one-shot arm's unbounded stall;
    - **speculative leg** — a decode-heavy trace swept over gamma with
      the NGram drafter, greedy accept-prefix verify in one bucketed
      extend dispatch: best-arm speedup >= 1.0x GATED, accept stats
      recorded and the measured-winner gamma persisted into the
      autotune cache (``FLAGS_serve_speculative=-1`` reads it back);
      the record also lands in BENCH_timeline.jsonl.

    Every arm's outputs are asserted token-exact against
    ``model.generate`` — a throughput number never describes drifted
    tokens."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.serving.speculative import store_gamma
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

    e = os.environ.get
    vocab = int(e("BENCH_SERVE_TIERS_VOCAB", 512))
    hidden = int(e("BENCH_SERVE_TIERS_HIDDEN", 192))
    layers = int(e("BENCH_SERVE_TIERS_LAYERS", 3))
    max_pos = 256
    bs_, nb, mb = 16, 96, 4
    n_users = 6 if small else 8
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=6, max_position_embeddings=max_pos))
    model.eval()

    def check_exact(results, trace):
        bad = [r.rid for r in trace if not np.array_equal(
            results[r.rid].output,
            np.asarray(model.generate(jnp.asarray(r.prompt_ids[None]),
                                      max_new_tokens=r.max_new_tokens))[0])]
        if bad:
            raise RuntimeError(f"tier outputs diverged from "
                               f"model.generate: {bad}")

    # -- (1) prefix leg: hit-rate x tokens/s curve ---------------------------
    plen, max_new = 224, 3

    def prefix_trace(ratio, shift):
        rng = np.random.default_rng(17)
        sl = int(round(ratio * plen / bs_)) * bs_
        shared = (rng.integers(0, vocab, sl) + shift) % vocab
        return [Request(
            rid=f"u{i}s{shift}",
            prompt_ids=np.concatenate([
                shared,
                (rng.integers(0, vocab, max(1, plen - sl)) + shift)
                % vocab]).astype(np.int32),
            max_new_tokens=max_new) for i in range(n_users)]

    def prefix_arm(ratio, on):
        eng = ServingEngine(model, block_size=bs_, num_blocks=nb,
                            max_batch=mb, max_seq_len=max_pos,
                            prefix_cache=on)
        # two distinct-token warm passes: every bucket/width signature
        # compiles outside the timed window while the timed trace still
        # shares only among itself
        eng.serve(prefix_trace(ratio, 7))
        eng.serve(prefix_trace(ratio, 29))
        eng.reset_peaks()
        trace = prefix_trace(ratio, 0)
        t0 = time.perf_counter()
        results = eng.serve(trace)
        wall = time.perf_counter() - t0
        check_exact(results, trace)
        tps = sum(r.max_new_tokens for r in trace) / wall
        return tps, eng

    curve = []
    for ratio in (0.0, 0.5, 0.8):
        tps_off, eng_off = prefix_arm(ratio, False)
        tps_on, eng_on = prefix_arm(ratio, True)
        rep = eng_on.prefix_report()
        curve.append({
            "share_ratio": ratio,
            "prefix_hit_rate": rep["hit_rate"],
            "tokens_per_s_off": round(tps_off, 1),
            "tokens_per_s_on": round(tps_on, 1),
            "speedup": round(tps_on / tps_off, 3),
            "peak_live_blocks_off": eng_off.peak_live_blocks,
            "peak_live_blocks_on": eng_on.peak_live_blocks,
            "blocks_reduction": round(
                eng_off.peak_live_blocks
                / max(eng_on.peak_live_blocks, 1), 3),
        })
    head = curve[-1]                 # the 80%-share production point
    _emit("serving_prefix_tokens_per_s", head["tokens_per_s_on"],
          "tokens/s @ 80% share", 0.0, {
              "curve": curve,
              "speedup_at_80": head["speedup"],
              "blocks_reduction_at_80": head["blocks_reduction"],
              "config": {"prompt_len": plen, "max_new": max_new,
                         "users": n_users, "hidden": hidden,
                         "layers": layers, "block_size": bs_},
              "method": ("shared-system-prompt trace (tools/serve_bench"
                         ".py --prefix-trace shape) at share ratios "
                         "0/0.5/0.8, radix-tree arm vs private-KV arm, "
                         "two distinct-token warm passes, outputs "
                         "token-exact; peak live blocks exclude "
                         "evictable cache-idle tree holds")})
    if head["speedup"] < 1.5:
        raise RuntimeError(
            f"prefix-cache tokens/s {head['speedup']}x < 1.5x at 80% "
            f"share: {curve}")
    if head["blocks_reduction"] < 2.0:
        raise RuntimeError(
            f"prefix-cache peak live blocks reduced only "
            f"{head['blocks_reduction']}x < 2x: {curve}")

    # -- (2) chunked-prefill leg: bounded stall ------------------------------
    rng = np.random.default_rng(5)

    def chunk_arm(chunk):
        eng = ServingEngine(model, block_size=bs_, num_blocks=nb,
                            max_batch=mb, max_seq_len=max_pos,
                            chunked_prefill=chunk)
        mk = lambda rid, n, new: Request(  # noqa: E731
            rid=rid, prompt_ids=rng.integers(0, vocab, n).astype(np.int32),
            max_new_tokens=new)
        warm = [mk(f"w{i}", 16, 24) for i in range(3)] + \
            [mk("wl", 224, 2)]
        eng.serve(warm)
        residents = [mk(f"d{i}", 16, 24) for i in range(3)]
        long_req = mk("long", 224, 2)
        for r in residents:
            eng.submit(r)
        steps_ms, results = [], {}
        for it in range(200):
            t0 = time.perf_counter()
            done = eng.step()
            steps_ms.append((time.perf_counter() - t0) * 1e3)
            for s in done:
                results[s.rid] = s
            if it == 5:
                eng.submit(long_req)
            if not eng.sched.n_pending:
                break
        check_exact(results, residents + [long_req])
        tail = steps_ms[6:]
        return (max(tail),
                sorted(tail)[int(0.99 * (len(tail) - 1))])

    stall_off, p99_off = chunk_arm(0)
    stall_on, p99_on = chunk_arm(32)
    _emit("serving_chunked_prefill_stall_ms", stall_on, "ms max step "
          "wall during long-prompt arrival", 0.0, {
              "unchunked_stall_ms": round(stall_off, 2),
              "chunked_stall_ms": round(stall_on, 2),
              "p99_step_ms_unchunked": round(p99_off, 2),
              "p99_step_ms_chunked": round(p99_on, 2),
              "stall_reduction": round(stall_off / stall_on, 2),
              "chunk_tokens": 32, "long_prompt": 224,
              "method": ("3 short residents decoding, a 224-token "
                         "prompt arrives at iteration 5; max/p99 "
                         "engine-step wall over the remaining "
                         "iterations = the resident-visible stall; "
                         "chunked budget 32 tokens/iteration vs the "
                         "one-shot prefill")})
    if stall_on >= stall_off:
        raise RuntimeError(
            f"chunked prefill did not bound the long-prompt stall: "
            f"chunked {stall_on:.1f}ms >= one-shot {stall_off:.1f}ms")

    # -- (3) speculative leg: gamma sweep ------------------------------------
    def spec_trace():
        r = np.random.default_rng(9)
        return [Request(rid=f"s{i}",
                        prompt_ids=r.integers(
                            0, vocab, int(r.integers(8, 17))).astype(
                                np.int32),
                        max_new_tokens=24) for i in range(n_users)]

    def spec_arm(gamma):
        eng = ServingEngine(model, block_size=bs_, num_blocks=nb,
                            max_batch=mb, max_seq_len=max_pos,
                            speculative=gamma)
        tr = spec_trace()
        eng.serve(tr)        # identical warm: same widths, no tree
        t0 = time.perf_counter()
        results = eng.serve(tr)
        wall = time.perf_counter() - t0
        check_exact(results, tr)
        return sum(r.max_new_tokens for r in tr) / wall, eng

    tps_base, _ = spec_arm(0)
    arms = []
    for g in (2, 4, 6):
        tps_g, eng_g = spec_arm(g)
        r = eng_g.spec_report()
        arms.append({"gamma": g, "tokens_per_s": round(tps_g, 1),
                     "speedup": round(tps_g / tps_base, 3),
                     "accept_rate": r["accept_rate"],
                     "mean_accept_len": r["mean_accept_len"],
                     "tokens_per_verify": r["tokens_per_verify"]})
    best = max(arms, key=lambda a: a["tokens_per_s"])
    t_desc = f"gpt_l{layers}_h{hidden}_v{vocab}"
    store_gamma(t_desc, "ngram", best["gamma"],
                measured_ms=1e3 / max(best["tokens_per_s"], 1e-9))
    _emit("serving_speculative_speedup", best["speedup"],
          "x vs plain decode", 0.0, {
              "baseline_tokens_per_s": round(tps_base, 1),
              "arms": arms, "best_gamma": best["gamma"],
              "spec_accept_rate": best["accept_rate"],
              "drafter": "ngram",
              "method": ("decode-heavy trace (short prompts, 24 new "
                         "tokens), NGram prompt-lookup drafter, greedy "
                         "accept-prefix verify in one bucketed "
                         "decode-gamma extend dispatch; gamma swept "
                         "{2,4,6}, measured winner persisted to the "
                         "autotune cache; outputs token-exact")})
    if best["speedup"] < 1.0:
        raise RuntimeError(
            f"speculative speedup {best['speedup']}x < 1.0x: {arms}")
    out_path = os.environ.get("BENCH_TRACE_OUT", "BENCH_timeline.jsonl")
    try:
        with open(out_path, "a") as f:
            f.write(json.dumps({
                "kind": "spec_decode",
                "spec_accept_rate": best["accept_rate"],
                "mean_accept_len": best["mean_accept_len"],
                "speedup": best["speedup"],
                "gamma": best["gamma"],
                "drafter": "ngram",
                "prefix_curve": curve,
                "chunked_stall_ms": round(stall_on, 2),
                "unchunked_stall_ms": round(stall_off, 2),
            }) + "\n")
    except OSError:
        pass


def bench_gpt_13b():
    """BASELINE config 4, the PRIMARY metric: GPT-3 1.3B tokens/sec/chip.

    Two components, emitted as ONE record:

    - the r3-r5 per-layer extrapolation (measure the exact 1.3B layer
      shape at L=6 and L=12, fit t = a + b*L, report t(24)) — kept for
      continuity and as the cross-check target;
    - ``measured_full_depth`` (NEW, VERDICT r5 missing #1): one real
      24-layer fwd+bwd+update step, device-timed and anomaly-guarded,
      under both the SGD-no-moment resident path and the AdamW
      host-offloaded-moments path (framework/offload.py). The 18.4 GB
      > 15.75 GB capacity wall that forced the extrapolation for two
      rounds is gone — moments live in pinned host memory and stream
      through HBM per block.

    Headline: the measured AdamW number when it produced a clean window
    (the reference's methodology gates on measured runs only); the
    extrapolation is confirmed if within 5%, otherwise marked corrected
    and the MFU restated from the measurement.
    """
    import jax

    if os.environ.get("BENCH_13B_SMOKE") == "1":
        # CPU wiring smoke: tiny dims, same code path end to end
        seq, batch, heads, hidden, vocab = 32, 2, 2, 64, 128
        depths, full_depth, fit_steps, meas_steps = (1, 2), 4, 2, 2
    else:
        seq, batch, heads, hidden, vocab = 2048, 4, 16, 2048, 50304
        depths, full_depth, fit_steps, meas_steps = (6, 12), 24, 8, 3
    pts = []
    for L in depths:
        m, n_params = _gpt_measure(
            L, hidden, heads, seq, batch, steps=fit_steps, remat=True,
            vocab=vocab)
        pts.append((L, m, n_params))
    # headline on DEVICE time when a trace was parsed for BOTH depths
    # (host dispatch latency is not chip throughput); otherwise wall time
    # for both — never mixed
    ms = [p[1] for p in pts]
    # "device" only when BOTH depths produced CLEAN device windows —
    # m["timing"] is set to "device" only in that case (an all-anomalous
    # device trace must never become the headline basis).
    timing_basis = ("device" if all(m["timing"] == "device" for m in ms)
                    else "wall")
    times = [m["device_s" if timing_basis == "device" else "wall_s"]
             for m in ms]
    anomaly = any(m["anomaly"] for m in ms)
    (l1, l2), (t1, t2) = (pts[0][0], pts[1][0]), times
    per_layer = (t2 - t1) / (l2 - l1)
    fixed = t1 - l1 * per_layer
    t24 = fixed + full_depth * per_layer
    # param count of the true 24-layer model (trunk scales linearly; embed
    # + position table are the fixed part)
    n6 = pts[0][2]
    per_layer_params = (pts[1][2] - n6) / (l2 - l1)
    n24 = int(n6 + (full_depth - l1) * per_layer_params)
    extrap_tok_s = batch * seq / t24
    flops_per_token = _gpt_flops_per_token(n24, full_depth, seq, hidden)
    peak = _peak_flops(jax.devices()[0])
    extrap_mfu = extrap_tok_s * flops_per_token / peak

    # --- measured full depth, both paths -----------------------------------
    budget_gb = None if os.environ.get("BENCH_13B_SMOKE") != "1" else 1e9
    measured = {}
    for mode in ("sgd_no_moment", "adam_offload_moments"):
        try:
            m, n_meas, mbatch, plan = _gpt_13b_measured_path(
                mode, full_depth, hidden, heads, seq, vocab,
                steps=meas_steps, budget_gb=budget_gb)
            tok_s = mbatch * seq / m["used_s"]
            measured[mode] = {
                "tokens_per_sec": round(tok_s, 1),
                "mfu": round(tok_s * flops_per_token / peak, 4),
                "step_ms": round(m["used_s"] * 1e3, 2),
                "batch": mbatch, "loss": m["loss"],
                "n_params": n_meas,
                "hbm_plan": {"device_gb": plan["device_gb"],
                             "host_gb": plan["host_gb"],
                             "fits": plan["fits"],
                             "rows_gb": plan["rows_gb"]},
                **_guard_extra(m),
            }
        except Exception as e:  # OOM/compile failure must not kill primary
            measured[mode] = {"error": f"{type(e).__name__}: {e}"[:400]}

    adam = measured.get("adam_offload_moments", {})
    adam_ok = "tokens_per_sec" in adam and not adam.get("anomaly")
    if adam_ok:
        agree_pct = 100.0 * (adam["tokens_per_sec"] / extrap_tok_s - 1.0)
        confirmed = abs(agree_pct) <= 5.0
        headline_tok_s, headline_mfu = adam["tokens_per_sec"], adam["mfu"]
        method = ("measured_full_depth: real %d-layer fwd+bwd+update, "
                  "AdamW moments in pinned host memory streamed per block "
                  "(FLAGS_offload_optimizer=moments); extrapolation %s "
                  "(%.1f%% apart)" % (
                      full_depth,
                      "confirmed within 5%" if confirmed
                      else "CORRECTED — headline restated from measurement",
                      agree_pct))
    else:
        agree_pct, confirmed = None, None
        headline_tok_s, headline_mfu = extrap_tok_s, extrap_mfu
        method = ("per-layer extrapolation (measured full-depth run "
                  "unavailable this round — see measured_full_depth for "
                  "the failure record)")

    _emit("gpt3_1p3b_train_tokens_per_sec_per_chip", headline_tok_s,
          "tokens/sec/chip", headline_mfu,
          {"n_params": n24, "loss_at_l6": ms[0]["loss"],
           "anomaly": anomaly if not adam_ok else bool(adam.get("anomaly")),
           "config": {"layers": full_depth, "hidden": hidden,
                      "heads": heads, "seq": seq, "batch": batch,
                      "remat": True, "amp": "O2 (bf16 + f32 master)"},
           "method": method,
           "measured_full_depth": measured,
           "extrapolation": {
               "tokens_per_sec": round(extrap_tok_s, 1),
               "mfu": round(extrap_mfu, 4),
               "step_ms": round(t24 * 1e3, 2),
               "per_layer_ms": round(per_layer * 1e3, 2),
               "fixed_ms": round(fixed * 1e3, 2),
               "agreement_pct": (round(agree_pct, 2)
                                 if agree_pct is not None else None),
               "confirmed_within_5pct": confirmed,
               "anomaly": anomaly,
           },
           "measured_points": [
               {"layers": l, "step_ms": round(t * 1e3, 2),
                "wall_step_ms": round(m["wall_s"] * 1e3, 2)
                if m["wall_s"] else None,
                "anomaly": m["anomaly"],
                "windows": m["windows"], "discarded": m["discarded"],
                "roofline_ms": m["roofline_ms"]}
               for (l, m, _), t in zip(pts, times)],
           "timing": ("device (xprof hlo_stats; wall reported alongside)"
                      if timing_basis == "device" else "wall"),
           "step_ms": (adam["step_ms"] if adam_ok
                       else round(t24 * 1e3, 2)),
           "baseline_config": 4})


def bench_gpt(small: bool):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework.functional import functional_call, get_params
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    if not small and not os.environ.get("BENCH_LAYERS"):
        # Default full run reports the BASELINE-faithful 1.3B metric:
        # extrapolation + measured full depth (r6 tentpole).
        return bench_gpt_13b()

    # head_dim 128 (not 64) matches the BASELINE GPT-3 1.3B shape
    # (16 heads x 128 at d_model 2048) and fills the 128-lane MXU; batch 16
    # is the measured single-chip sweet spot (batch 32 spills HBM).
    layers = int(os.environ.get("BENCH_LAYERS", 2 if small else 16))
    hidden = int(os.environ.get("BENCH_HIDDEN", 128 if small else 1024))
    heads = int(os.environ.get("BENCH_HEADS", 4 if small else 8))
    seq = int(os.environ.get("BENCH_SEQ", 128 if small else 1024))
    batch = int(os.environ.get("BENCH_BATCH", 2 if small else 16))
    steps = int(os.environ.get("BENCH_STEPS", 2 if small else 10))
    remat = os.environ.get("BENCH_REMAT") == "1"
    vocab = 512 if small else 50304

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=seq,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    recompute=remat)
    model = GPTForCausalLM(cfg)
    model.train()
    # AMP O2: bf16 params/compute, fp32 master weights in the optimizer.
    model.astype(paddle.bfloat16)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01, multi_precision=True)

    params = get_params(model)
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    opt_state = opt.init(params)

    def loss_fn(p, ids, labels):
        return functional_call(model, p, ids, labels, training=True)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, ids, labels):
        p, st = state
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels)
        new_p, new_st = opt.apply_gradients(p, grads, st, 1e-4)
        return loss, (new_p, new_st)

    batches = _gpt_batches(batch, seq, vocab)
    dev = jax.devices()[0]
    flops, nbytes = _compiled_cost(step, (params, opt_state), *batches[0])
    roof = _roofline_for(dev, flops, nbytes)
    m = _measure_guarded(step, (params, opt_state), batches[0], steps,
                         roof, args_seq=batches)
    dt = m["used_s"]
    tokens_per_sec = batch * seq / dt
    # Model FLOPs per token: 6N (fwd+bwd matmuls) + causal attention
    # 12*L*seq*hidden/2 (QK^T + PV, fwd+bwd, halved by causal masking).
    flops_per_token = 6 * n_params + 6 * layers * seq * hidden
    mfu = tokens_per_sec * flops_per_token / _peak_flops(dev)
    _emit(f"gpt_{n_params/1e6:.0f}M_train_tokens_per_sec_per_chip",
          tokens_per_sec, "tokens/sec/chip", mfu,
          {"loss": m["loss"], "n_params": n_params,
           "config": {"layers": layers, "hidden": hidden, "heads": heads,
                      "seq": seq, "batch": batch, "steps": steps,
                      "remat": remat},
           **_guard_extra(m),
           "step_ms": round(dt * 1e3, 2), "baseline_config": 4})


_SNAPSHOT_RE = re.compile(r"BENCH_r(\d+)\.json$")


def _next_snapshot_n(root):
    """NN for this run's ``BENCH_r<NN>.json``: last COMMITTED snapshot + 1
    (so reruns in a dirty tree overwrite their own snapshot instead of
    walking the counter), falling back to the directory scan when git is
    unavailable."""
    names = []
    try:
        out = subprocess.run(
            ["git", "ls-files", "BENCH_r*.json"], cwd=root,
            capture_output=True, text=True, timeout=30)
        if out.returncode == 0:
            names = out.stdout.split()
    except (OSError, subprocess.SubprocessError):
        pass
    if not names:
        names = [n for n in os.listdir(root) if _SNAPSHOT_RE.search(n)]
    nums = [int(_SNAPSHOT_RE.search(n).group(1)) for n in names
            if _SNAPSHOT_RE.search(n)]
    return max(nums, default=0) + 1


def _write_snapshot(root, stdout_text, rc, cmd):
    """Persist the per-run snapshot (the driver's record shape,
    n/cmd/rc/tail/parsed) so the trajectory keeps its
    per-run anchors and not just the BENCH_timeline.jsonl stream.
    ``parsed`` is the last metric line — the driver's headline (GPT)."""
    n = _next_snapshot_n(root)
    parsed = None
    for line in reversed(stdout_text.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            parsed = rec
            break
    path = os.path.join(root, "BENCH_r%02d.json" % n)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"n": n, "cmd": cmd, "rc": rc,
                   "tail": stdout_text[-8000:], "parsed": parsed}, f)
        f.write("\n")
    return path


class _TeeStdout:
    """Pass-through stdout capture for the snapshot's ``tail``."""

    def __init__(self, inner):
        self.inner = inner
        self.chunks = []

    def write(self, s):
        self.chunks.append(s)
        return self.inner.write(s)

    def flush(self):
        self.inner.flush()

    def text(self):
        return "".join(self.chunks)


def _run_leg(fn, small, failed):
    """Run one secondary leg. A leg that raises prints ``<leg>_FAILED``
    and the run goes on to the next leg — but it is remembered, and the
    process exits non-zero (a failed leg is never a clean run)."""
    try:
        fn(small)
    except Exception as e:
        failed.append(fn.__name__)
        print(json.dumps({"metric": f"{fn.__name__}_FAILED",
                          "error": str(e)[:500]}), flush=True)


def _main_impl():
    """Run every selected leg; returns the process exit code (1 when any
    leg printed ``_FAILED``)."""
    small = os.environ.get("BENCH_SMALL") == "1"
    from paddle_tpu.core.chip import enable_compile_cache
    enable_compile_cache()
    _prewarm_autotune()
    which = os.environ.get("BENCH_CONFIGS", "all")
    selected = {w.strip() for w in which.split(",")}
    failed = []

    def on(env, default="1"):
        return os.environ.get(env, default) != "0"

    by_name = {"resnet": bench_resnet, "bert": bench_bert,
               "ernie": bench_ernie}
    for name, fn in by_name.items():
        if "all" in selected or name in selected:
            _run_leg(fn, small, failed)
    if os.environ.get("BENCH_PALLAS_CONV") == "1" and (
            "all" in selected or "resnet" in selected):
        _run_leg(bench_pallas_conv_ab, small, failed)
    # telemetry overhead A/B + this run's timeline export (before the
    # primary so the driver's final-line headline stays the GPT metric)
    if on("BENCH_TELEMETRY"):
        _run_leg(bench_telemetry_overhead, small, failed)
        _run_leg(bench_flight_recorder_overhead, small, failed)
        _run_leg(bench_fleet_telemetry_overhead, small, failed)
    # comm-overlap A/B (FLAGS_comm_overlap off vs tp): emits the
    # comm_overlap metric — measured on >=2-device meshes, static hop
    # plans only on a single chip
    if on("BENCH_COMM_OVERLAP"):
        _run_leg(bench_comm_overlap, small, failed)
    # multi-slice tier: 2-slice dryrun (hierarchical vs flat DP reduction,
    # bitwise parity + per-link hop plans + DCN bytes/step — chipless)
    if on("BENCH_MULTISLICE"):
        _run_leg(bench_multislice, small, failed)
    # fault-tolerance drill: kill/relaunch/resume with measured goodput
    # (subprocesses on the CPU mesh — runs chipless, ~30s quick config)
    if on("BENCH_FAULT"):
        _run_leg(bench_fault, small, failed)
    # serving engine: continuous batching + paged KV vs the one-shot
    # predictor, measured tokens/s and p50/p99 on a ragged trace (CPU-mesh
    # sized model — runs chipless; the request records join the timeline)
    if on("BENCH_SERVE"):
        _run_leg(bench_serve, small, failed)
    if "all" in selected or "gpt" in selected:
        bench_gpt(small)  # primary: printed last
    return 1 if failed else 0


def main():
    if os.environ.get("BENCH_SNAPSHOT", "1") == "0":
        return _main_impl()
    root = os.environ.get("BENCH_SNAPSHOT_DIR",
                          os.path.dirname(os.path.abspath(__file__)))
    tee = _TeeStdout(sys.stdout)
    sys.stdout = tee
    rc = 1
    try:
        rc = _main_impl()
    finally:
        sys.stdout = tee.inner
        try:
            _write_snapshot(root, tee.text(), rc,
                            "python " + " ".join(sys.argv))
        except OSError as e:
            print(json.dumps({"metric": "bench_snapshot_FAILED",
                              "error": str(e)[:200]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
