#!/usr/bin/env python3
"""Readings from which a cell's limits are set (``PERF.md`` gives them beside
each limit): the program's numbers over many seeds, and on the first
``--controls`` seeds the control's (the reference in the precision below the
configuration's) and, for a training cell, the planted faults' (half of the
batch left out, the mean taken over the rest; a step that leaves its state
unchanged). One process, so the set-up is
paid once; never part of a benchmark run.

    python3 benchmark/limits.py --workload <cell> --seeds 1,2,3 --controls 3 [--seconds 30]

Prints one JSON line a reading:
``{"workload", "seed", "what": "program"|"control:<mode>"|"fault:<which>", "numbers"}``.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as R  # noqa: E402


def say(cell, seed, what, numbers):
    print(json.dumps({"workload": cell.name, "seed": seed, "what": what,
                      "numbers": numbers}), flush=True)


def train_readings(cell, seeds, n_controls):
    from benchmark.lib import correct, system, traffic, weights
    fam, cfg = cell.family, cell.cfg
    control = correct.control_mode(cfg)
    mesh = system.build_mesh(cell.extra.get("mesh"), cell.chips)
    ts, progs = None, {}
    for seed in seeds:
        w0 = weights.make_weights(fam.weights, cfg, seed,
                                  out_shardings=system.replicated(mesh))
        if ts is None:
            ts = system.build_train_step(fam, cfg, w0, cfg["optimizer"],
                                         mesh)
        else:
            system.reset_train_step(ts, fam, cfg, w0)
        del w0
        pool = traffic.train_batches(cell.mix, cfg["vocab_size"], seed)
        progs[seed] = R.first_three(ts, cell, pool, seed)
    del ts
    gc.collect()
    half = slice(0, cell.mix["batch"] // 2)
    for i, seed in enumerate(seeds):
        pool = traffic.train_batches(cell.mix, cfg["vocab_size"], seed)
        ref = R.reference_three(cell, seed, pool, mesh)
        say(cell, seed, "program", correct.train_numbers(progs[seed], ref))
        if i < n_controls:
            low = R.reference_three(cell, seed, pool, mesh, control)
            say(cell, seed, "control:" + control,
                correct.train_numbers(low, ref))
            cut = R.reference_three(cell, seed, pool, mesh, rows=half)
            say(cell, seed, "fault:half_batch",
                correct.train_numbers(cut, ref))
            still = R.reference_three(cell, seed, pool, mesh, frozen=True)
            say(cell, seed, "fault:state_unchanged",
                correct.train_numbers(still, ref))


def serve_readings(cell, seeds, n_controls, seconds):
    from benchmark.lib import correct
    control = correct.control_mode(cell.cfg)
    counter = R.CompileCounter()
    for i, seed in enumerate(seeds):
        rec, _, check = R.run_serve(cell, seed, seconds, None, counter)
        say(cell, seed, "program", check())
        if i < n_controls:
            say(cell, seed, "control:" + control,
                R.check_serve(cell, seed, rec, control))
        del rec, check
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no accelerator: jax.devices() is {jax.devices()}")
    from benchmark.lib import system
    system.enable_compile_cache(ROOT)
    cell = R.load_cell(ROOT, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell.mix["kind"] == "train_steps":
        train_readings(cell, seeds, args.controls)
    else:
        serve_readings(cell, seeds, args.controls, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
