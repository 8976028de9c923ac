#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, weights from the seed, compile or cache read, warm-up), a
measured window of ``--seconds``, then the comparison with the plain reference
that decides ``correct``. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``compared``). Without an
accelerator, or with fewer chips than the cell asks for, it prints no result
and exits non-zero. See ``benchmark/README.md``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclass
class Cell:
    """One cell as it is run: the manifest's entry with its files read."""
    name: str
    cfg: Dict
    mix: Dict
    chips: int
    family: object                               # lib.family.Family
    extra: Dict = field(default_factory=dict)    # benchmark/cells/<name>.json
    end_to_end: List[Dict] = field(default_factory=list)
    per_layer: List[Dict] = field(default_factory=list)


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Cell:
    man = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in man["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in man['workloads']]}")
    conf = next(c for c in man["configs"] if c["name"] == entry["config"])
    from benchmark.lib.family import load_family
    from benchmark.lib.traffic import load_traffic
    cfg = _read_json(os.path.join(root, conf["file"]))
    cell_file = os.path.join(root, "benchmark", "cells", workload + ".json")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return Cell(
        name=workload, cfg=cfg, mix=load_traffic(root, entry["traffic"]),
        chips=entry["chips"], family=load_family(root, cfg),
        extra=_read_json(cell_file) if os.path.exists(cell_file) else {},
        end_to_end=mine(man["end_to_end"]), per_layer=mine(man["per_layer"]))


def load_reader(root: str, metric: str):
    """The reader of ``metric``: ``benchmark/metrics/<metric>.py``, found by
    name; no registry to edit."""
    path = os.path.join(root, "benchmark", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts programs compiled (or read from the cache) while armed."""

    def __init__(self):
        import jax
        self.n, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and "backend_compile" in event:
            self.n += 1


def device_info(chips: int) -> Dict:
    import jax
    d = jax.devices()[0]
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": max(peaks) if peaks else None}


# -- the two kinds of cell ---------------------------------------------------

def first_three(ts, cell: Cell, pool, seed: int, marks=None) -> Dict:
    """The object the window drives, through the window's own call, on its
    first three batches: each loss, the first gradient's norms as the
    optimizer's state holds them, the masters' change after the three. The
    reference follows the same three afterwards."""
    from benchmark.lib import system
    fam, cfg, beta1 = cell.family, cell.cfg, cell.cfg["optimizer"]["beta1"]
    prog = {"losses": [float(ts.step(pool[0]))]}
    if marks is not None:       # tracing, lowering, compile or cache read
        marks["first_step_s"] = time.perf_counter() - T_START
    prog["grad"] = system.train_state_norms(ts, fam, cfg, beta1)
    prog["losses"] += [float(ts.step(pool[1])), float(ts.step(pool[2]))]
    prog["delta"] = system.train_state_norms(ts, fam, cfg, beta1,
                                             start_seed=seed)
    return prog


def run_train(cell: Cell, seed: int, seconds: float, trace_dir, counter):
    import jax
    from benchmark.lib import drive, system, traffic, weights
    fam, cfg, mix = cell.family, cell.cfg, cell.mix
    mesh = system.build_mesh(cell.extra.get("mesh"), cell.chips)
    marks = {"imports_s": time.perf_counter() - T_START}
    w0 = weights.make_weights(fam.weights, cfg, seed,
                              out_shardings=system.replicated(mesh))
    ts = system.build_train_step(fam, cfg, w0, cfg["optimizer"], mesh)
    del w0
    marks["build_s"] = time.perf_counter() - T_START
    pool = traffic.train_batches(mix, cfg["vocab_size"], seed)
    prog = first_three(ts, cell, pool, seed, marks)
    setup_s = time.perf_counter() - T_START
    counter.armed = True
    rec = drive.train_window(ts.step, pool, 3, seconds, trace_dir)
    counter.armed = False
    rec.update(setup_s=setup_s, setup_marks=marks, attempted=rec["steps"],
               failed=sum(1 for x in rec["losses"] if x != x),
               tokens=rec["steps"] * mix["batch"] * mix["seq"])
    dev = device_info(cell.chips)
    del ts
    gc.collect()
    jax.clear_caches()
    return rec, dev, lambda: check_train(cell, seed, pool, prog, mesh)


def reference_three(cell: Cell, seed: int, pool, mesh, mode="float32",
                    rows=None, frozen=False) -> Dict:
    """The plain reference over the same first three batches. ``mode``,
    ``rows`` and ``frozen`` make the controls: a lower precision, part of the
    batch left out, a step that leaves its state unchanged."""
    from benchmark.lib import weights
    fam, cfg, opt = cell.family, cell.cfg, cell.cfg["optimizer"]
    # the float32 state of a sharded cell does not fit one chip
    shard = weights.row_sharded(fam.weights, cfg, mesh) \
        if mesh.size > 1 else None
    ref = fam.reference.Reference(cfg, mode)
    w0 = weights.make_weights(fam.weights, cfg, seed, out_shardings=shard)
    state = ref.init_state(w0)
    hp = (0.0 if frozen else opt["learning_rate"], opt["beta1"],
          opt["beta2"], opt["epsilon"], opt["weight_decay"])
    out = {"losses": []}
    for i in range(3):
        loss, gnorm = ref.train_step(state, *pool[i], hp, rows=rows)
        out["losses"].append(loss)
        if i == 0:
            out["grad"] = gnorm
    out["delta"] = ref.delta_norms(state, w0)
    return out


def check_train(cell: Cell, seed: int, pool, prog, mesh):
    from benchmark.lib import correct
    return correct.train_numbers(prog, reference_three(cell, seed, pool, mesh))


def run_serve(cell: Cell, seed: int, seconds: float, trace_dir, counter):
    import jax
    from benchmark.lib import drive, system, weights
    fam, cfg, mix = cell.family, cell.cfg, cell.mix
    marks = {"imports_s": time.perf_counter() - T_START}
    w0 = weights.make_weights(fam.weights, cfg, seed)
    eng = fam.adapter.build_engine(cfg, w0, mix["engine"])
    del w0
    marks["build_s"] = time.perf_counter() - T_START
    getattr(fam.adapter, "warm_engine", system.warm_engine)(
        eng, cfg, mix["engine"])
    marks["warm_s"] = time.perf_counter() - T_START
    counter.armed = True
    rec = drive.serve_window(eng, system, mix, cfg["vocab_size"], seed,
                             seconds, trace_dir)
    counter.armed = False
    rec["setup_s"] = rec["t_open"] - T_START
    rec["setup_marks"] = marks
    dev = device_info(cell.chips)
    del eng
    gc.collect()
    jax.clear_caches()
    return rec, dev, lambda: check_serve(cell, seed, rec)


def serve_sample(cell: Cell, seed: int, finished: List[Dict]) -> List[Dict]:
    """A sample of the finished requests drawn from the seed, the one with
    the most served tokens in it."""
    from benchmark.lib.traffic import rng_of
    n = cell.extra.get("check", {}).get("sample", 6)
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i]["tokens"]))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = list(rng_of(seed, 5).permutation(rest)[:max(0, n - 1)])
    return [finished[i] for i in [longest] + pick]


def check_serve(cell: Cell, seed: int, rec: Dict, control: str = None):
    """The reference once over each sampled prompt with its served tokens.
    With ``control`` the served tokens give way to those that the reference
    in that lower precision puts first at each position."""
    import numpy as np
    from benchmark.lib import correct, weights
    fam, cfg, eng_cfg = cell.family, cell.cfg, cell.mix["engine"]
    sample = serve_sample(cell, seed, rec["finished"])
    if not sample:
        return {}
    ref = fam.reference.Reference(cfg)
    low = fam.reference.Reference(cfg, control) if control else None
    p32 = weights.f32_weights(weights.make_weights(fam.weights, cfg, seed))
    max_out = cell.mix["output_len"]["hi"]
    gaps = []
    for r in sample:
        args = (p32, r["prompt"], r["tokens"], eng_cfg["max_seq_len"], max_out)
        logits, tokens = ref.served_logits(*args), r["tokens"]
        if low is not None:
            tokens = np.argmax(np.asarray(low.served_logits(*args)),
                               axis=-1)[:len(tokens)]
        gaps.append(correct.served_gaps(logits, tokens))
    return correct.serve_numbers(gaps)


# -- one run -------------------------------------------------------------------

def window_work(rec: Dict) -> Dict:
    """How much work fell into the window, beside the phases' times: when a
    rate differs between two runs, whether the work differed or the time."""
    if rec["kind"] == "train_steps":
        return {"steps": rec["steps"]}
    steps = rec["steps"]
    tenth = rec["window_s"] / 10.0
    by_tenth = [0] * 10
    for s in steps:     # a slow run: slow throughout, or one stall?
        by_tenth[min(9, int((s["t1"] - rec["t_open"]) / tenth))] += 1
    return {"steps": len(steps), "steps_by_tenth": by_tenth,
            "prefills": sum(len(s["prefills"]) for s in steps),
            "prefill_tokens": sum(sum(s["prefills"]) for s in steps),
            "decode_tokens": sum(len(s["decode_ctx"]) for s in steps),
            "decode_ctx_tokens": sum(sum(s["decode_ctx"]) for s in steps),
            "in_step_s": sum(s["t1"] - s["t0"] for s in steps),
            "finished": len(rec["finished"])}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_chip: bool = True) -> Dict:
    """Everything but finding the cell: returns the result object. Tests call
    it with ``require_chip=False`` to rehearse on the CPU; the result then
    says ``platform: cpu`` and nothing in it is a measurement."""
    import jax
    from benchmark.lib import correct, peaks, readers, system
    from benchmark.lib import trace as TR
    platform = jax.devices()[0].platform
    if require_chip and platform != "tpu":
        raise SystemExit(f"no accelerator: jax.devices() is {jax.devices()}")
    if jax.device_count() < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"finds {jax.device_count()}")
    if platform == "tpu":
        system.enable_compile_cache(root)
    counter = CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, ".cache", "bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = cell.mix["kind"]
    runner = run_train if kind == "train_steps" else run_serve
    rec, dev, check = runner(cell, seed, seconds, trace_dir, counter)
    t_window_done = time.perf_counter()

    ctx = readers.Ctx(
        run=rec, cfg=cell.cfg, mix=cell.mix, cell=cell.extra,
        chips=cell.chips, family=cell.family,
        peaks=peaks.peaks_of(dev["kind"]) if platform == "tpu" else None)
    breakdown = None
    if trace and "traced" in rec:
        ctx.trace = TR.load(TR.newest_xplane(trace_dir))
        ctx.win = ctx.trace.window()
        if ctx.win is not None:
            dev["busy_s"] = TR.busy_seconds(ctx.trace, ctx.win)
            dev["window_s"] = ctx.win[1] - ctx.win[0]
            breakdown = {"device_ops": TR.top_ops(ctx.trace, ctx.win),
                         "idle_gaps": TR.idle_gaps(ctx.trace, ctx.win)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        if platform != "tpu" and m["source"] == "device_trace":
            continue    # a CPU run has no device number to give
        got = load_reader(root, m["name"])(ctx)
        if got is None:
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        entry["unit"] = m["unit"]
        metrics[m["name"]] = entry

    t_read = time.perf_counter()
    numbers = check()
    phases = {"setup_marks": rec["setup_marks"], "setup_s": rec["setup_s"],
              "window_s": rec["window_s"], "work": window_work(rec),
              "reading_s": t_read - t_window_done,
              "reference_s": time.perf_counter() - t_read}
    numbers["compiles_in_window"] = {"value": counter.n}
    limits = dict(cell.extra.get("limits", {}))
    limits["compiles_in_window"] = 0
    ok, compared = correct.judge(numbers, limits)
    result = {"correct": ok, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["phases"] = phases
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
