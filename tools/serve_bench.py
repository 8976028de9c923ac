#!/usr/bin/env python
"""Replay a request trace through the serving engine and report
tokens/s + tail latency.

    python tools/serve_bench.py                       # synthetic ragged trace
    python tools/serve_bench.py --requests 16 --max-batch 8 --json
    python tools/serve_bench.py --trace trace.jsonl --arrivals
    python tools/serve_bench.py --sequential          # max_batch=1 baseline
    # shared-system-prompt workload x 8 users, radix tree armed:
    python tools/serve_bench.py --prefix-trace 8 --share-ratio 0.8 \
        --prompt-len 64 --prefix-cache
    python tools/serve_bench.py --chunked-prefill 32 --speculative 4

Trace file: one JSON object per line —
    {"rid": "r0", "prompt": [1, 5, 9], "max_new_tokens": 8,
     "arrival_s": 0.25}
``prompt_len`` (seeded random ids) may replace ``prompt``; ``arrival_s``
is honored only under ``--arrivals`` (otherwise the trace is closed-loop:
everything submitted up front). Without ``--trace`` a deterministic
ragged trace is synthesized from ``--seed``.

The report carries throughput (tokens/s over generated tokens), exact
p50/p99 request latency and TTFT from the request timeline, the compile
budget check (distinct executable signatures vs registered buckets — the
O001-silence criterion), preemption/spill counts, and per-phase totals.
``--json`` emits it as one machine-readable object on stdout;
``--timeline`` additionally writes the per-request JSONL records.

Resilience / SLO gating: ``--deadline-ms`` stamps every request with a
deadline (per-trace ``deadline_s`` fields win), the report then carries
``slo_attainment_pct`` (fraction of deadline-carrying requests answered
in time) and ``shed_rate``; ``--fail-on-slo <pct>`` exits nonzero when
attainment lands below the target — the CI gate
``tests/test_serve_drill.py`` runs. ``--max-waiting`` bounds admission
(rejected requests count against the SLO).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def synth_trace(n, seed, vocab, lo, hi, max_new):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(lo, hi + 1))
        out.append({"rid": f"r{i}",
                    "prompt": rng.integers(0, vocab, plen).tolist(),
                    "max_new_tokens": int(max_new),
                    "arrival_s": round(i * 0.01, 4)})
    return out


def prefix_trace(n_users, seed, vocab, share_ratio, prompt_len, max_new):
    """The production-shaped workload: one shared system prompt of
    ``share_ratio * prompt_len`` tokens, ``n_users`` requests that each
    append a private suffix — the trace every prefix-hit-rate x
    tokens/s curve in BENCH_SERVE replays. ``share_ratio=0`` degrades
    to fully private prompts of the same length."""
    rng = np.random.default_rng(seed)
    shared_len = int(round(share_ratio * prompt_len))
    shared = rng.integers(0, vocab, shared_len).tolist()
    out = []
    for i in range(n_users):
        suffix = rng.integers(0, vocab,
                              max(1, prompt_len - shared_len)).tolist()
        out.append({"rid": f"u{i}", "prompt": shared + suffix,
                    "max_new_tokens": int(max_new),
                    "arrival_s": round(i * 0.01, 4)})
    return out


def load_trace(path, seed, vocab):
    rng = np.random.default_rng(seed)
    out = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if "prompt" not in rec:
                rec["prompt"] = rng.integers(
                    0, vocab, int(rec.pop("prompt_len"))).tolist()
            rec.setdefault("rid", f"r{i}")
            out.append(rec)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--trace", help="request-trace JSONL to replay")
    p.add_argument("--requests", type=int, default=8,
                   help="synthetic trace size (no --trace)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompt-lo", type=int, default=4)
    p.add_argument("--prompt-hi", type=int, default=32)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--arrivals", action="store_true",
                   help="honor per-request arrival_s offsets")
    p.add_argument("--sequential", action="store_true",
                   help="max_batch=1: the sequential (still KV-cached) "
                        "baseline")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request deadline applied to the whole trace "
                        "(per-record deadline_s fields win)")
    p.add_argument("--fail-on-slo", type=float, default=None, metavar="PCT",
                   help="exit nonzero when SLO attainment < PCT")
    # synthetic prefix-sharing workload (ISSUE 13)
    p.add_argument("--prefix-trace", type=int, default=None, metavar="N",
                   help="generate a shared-system-prompt trace for N "
                        "users instead of the ragged trace (see "
                        "--share-ratio / --prompt-len)")
    p.add_argument("--share-ratio", type=float, default=0.8,
                   help="fraction of each --prefix-trace prompt that is "
                        "the common system prefix")
    p.add_argument("--prompt-len", type=int, default=64,
                   help="total prompt length per --prefix-trace user")
    # engine knobs
    p.add_argument("--block-size", type=int, default=4)
    p.add_argument("--num-blocks", type=int, default=64)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-waiting", type=int, default=None,
                   help="bounded admission: reject past this queue depth")
    p.add_argument("--prefix-cache", action="store_true",
                   help="arm the radix prefix-sharing KV cache")
    p.add_argument("--chunked-prefill", type=int, default=0, metavar="T",
                   help="chunked-prefill token budget (0 = one-shot)")
    p.add_argument("--speculative", type=int, default=0, metavar="G",
                   help="speculative draft depth gamma (0 = off, "
                        "-1 = autotuned)")
    # model knobs (tiny CPU-mesh GPT by default)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=0,
                   help="grouped-query KV heads (0 = MHA)")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--max-pos", type=int, default=128)
    p.add_argument("--timeline", help="write per-request JSONL here")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core.chip import enable_compile_cache
    from paddle_tpu.observability import metrics, request_timeline
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

    say = (lambda *a: print(*a, file=sys.stderr)) if args.json else print

    if args.trace:
        trace = load_trace(args.trace, args.seed, args.vocab)
    elif args.prefix_trace:
        trace = prefix_trace(args.prefix_trace, args.seed, args.vocab,
                             args.share_ratio, args.prompt_len,
                             args.max_new)
    else:
        trace = synth_trace(args.requests, args.seed, args.vocab,
                            args.prompt_lo, args.prompt_hi, args.max_new)
    default_deadline = (args.deadline_ms / 1e3
                        if args.deadline_ms is not None else None)
    requests = [Request(rid=r["rid"],
                        prompt_ids=np.asarray(r["prompt"], np.int32),
                        max_new_tokens=int(r["max_new_tokens"]),
                        eos_token_id=r.get("eos_token_id"),
                        arrival_s=float(r.get("arrival_s", 0.0)),
                        deadline_s=r.get("deadline_s", default_deadline),
                        priority=int(r.get("priority", 0)))
                for r in trace]

    # runs on whatever platform the caller's environment gives it; the
    # report names the device every number came from
    enable_compile_cache()
    dev = jax.devices()[0]
    paddle.seed(args.seed)
    cfg = gpt_tiny(vocab_size=args.vocab, hidden_size=args.hidden,
                   num_layers=args.layers, num_heads=args.heads,
                   num_kv_heads=args.kv_heads or None,
                   max_position_embeddings=args.max_pos)
    model = GPTForCausalLM(cfg)
    rt = request_timeline.reset_default()
    eng = ServingEngine(model, block_size=args.block_size,
                        num_blocks=args.num_blocks,
                        max_batch=1 if args.sequential else args.max_batch,
                        max_waiting=args.max_waiting,
                        prefix_cache=args.prefix_cache,
                        chunked_prefill=args.chunked_prefill,
                        speculative=args.speculative)
    tiers = [t for t, on in (("prefix", args.prefix_cache),
                             ("chunked", args.chunked_prefill),
                             ("spec", args.speculative)) if on]
    say(f"replaying {len(requests)} request(s) through "
        f"{'sequential' if args.sequential else 'continuous-batching'} "
        f"engine (blocks {args.num_blocks}x{args.block_size}, "
        f"max_batch {eng.sched.max_batch}"
        f"{', tiers: ' + '+'.join(tiers) if tiers else ''})")
    t0 = time.perf_counter()
    eng.serve(requests, respect_arrivals=args.arrivals)
    wall_s = time.perf_counter() - t0

    summary = rt.summary()
    new_tokens = summary["new_tokens"]
    report = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "requests": len(requests),
        "new_tokens": new_tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_s": round(new_tokens / wall_s, 2) if wall_s else 0.0,
        "p50_ms": summary["p50_ms"],
        "p99_ms": summary["p99_ms"],
        "ttft_p50_ms": summary["ttft_p50_ms"],
        "ttft_p99_ms": summary["ttft_p99_ms"],
        "phases": summary["phases"],
        "preemptions": summary["preemptions"],
        "kv_spills": metrics.counter("serving.kv_spills").get(),
        "outcomes": summary["outcomes"],
        "slo_attainment_pct": summary["slo_attainment_pct"],
        "shed_rate": summary["shed_rate"],
        "compile_report": eng.compile_report(),
        "mode": "sequential" if args.sequential else "continuous",
    }
    if args.prefix_cache:
        report["prefix_report"] = eng.prefix_report()
    if args.speculative:
        report["spec_report"] = eng.spec_report()
    if args.timeline:
        n = rt.export_jsonl(args.timeline)
        say(f"wrote {n} request record(s) to {args.timeline}")
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(f"tokens/s          {report['tokens_per_s']}")
        print(f"latency p50/p99   {report['p50_ms']} / "
              f"{report['p99_ms']} ms")
        print(f"ttft p50/p99      {report['ttft_p50_ms']} / "
              f"{report['ttft_p99_ms']} ms")
        print(f"preemptions       {report['preemptions']} "
              f"(spills {report['kv_spills']})")
        cr = report["compile_report"]
        ext = (f", extend {cr['extend_signatures']}"
               if cr.get("extend_signatures") else "")
        print(f"compiles          prefill {cr['prefill_signatures']}/"
              f"{len(cr['prefill_buckets'])} buckets, decode "
              f"{cr['decode_signatures']}/{len(cr['decode_buckets'])} "
              f"buckets{ext}, O001 fired: {cr['o001_fired']}")
        if report["slo_attainment_pct"] is not None:
            print(f"slo attainment    {report['slo_attainment_pct']}% "
                  f"(shed rate {report['shed_rate']}, "
                  f"outcomes {report['outcomes']})")
        if "prefix_report" in report:
            pr = report["prefix_report"]
            print(f"prefix cache      hit rate {pr['hit_rate']}, "
                  f"{pr['tree_nodes']} tree nodes, peak blocks "
                  f"{pr['peak_blocks_used']}")
        if "spec_report" in report:
            sr = report["spec_report"]
            print(f"speculative       gamma {sr['gamma']} "
                  f"({sr['drafter']}), accept rate "
                  f"{sr['accept_rate']}, {sr['tokens_per_verify']} "
                  f"tokens/verify")
    if report["compile_report"]["o001_fired"]:
        return 1
    if (args.fail_on_slo is not None
            and (report["slo_attainment_pct"] is None
                 or report["slo_attainment_pct"] < args.fail_on_slo)):
        say(f"SLO attainment {report['slo_attainment_pct']}% below the "
            f"--fail-on-slo target {args.fail_on_slo}%")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
