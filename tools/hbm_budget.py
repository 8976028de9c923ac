#!/usr/bin/env python
"""Static HBM-footprint accounting for training configs.

Answers, before any compile: does this (model, optimizer, offload mode,
batch) fit the chip? The categories mirror the runtime placement decided
by ``framework/offload.py``:

  params (bf16) | grads (bf16) | f32 master | moments (HBM-resident, or
  host-side with ~2 blocks in flight under FLAGS_offload_optimizer=
  moments) | activation checkpoints (remat: one block-boundary tensor per
  layer) | remat working set | logits/CE transient

The arithmetic is validated against the depths that are KNOWN to fit or
not: L=12 resident Adam at batch 4 fits (the benchmark's cell
``train.gpt3-1.3b-l12.b4s2048`` runs it), L=24 resident Adam does not
(18.4 GB state > 15.75 GB), L=24 offloaded Adam and L=24 SGD-no-moment
must.

CLI:
    python tools/hbm_budget.py --layers 24 --offload moments
    python tools/hbm_budget.py --layers 24 --optimizer sgd --batch 4
exits nonzero when the config does not fit the budget.
"""

from __future__ import annotations

import argparse
import json
import sys

GB = float(2 ** 30)

# v5e: 16 GiB HBM, 15.75 GiB addressable by the program (the remainder is
# runtime-reserved); the ISSUE/BASELINE budget figure.
DEFAULT_BUDGET_GB = 15.75

# f32 moment bytes per parameter, per optimizer family (matches
# Optimizer.offloadable_state_keys()).
MOMENT_BYTES = {"adam": 8, "adamw": 8, "lamb": 8, "momentum": 4,
                "lars": 4, "sgd": 0}


def gpt_param_counts(layers: int, hidden: int, seq: int, vocab: int):
    """(total, per_layer, misc) param counts of the repo's GPT decoder
    (qkv/out/mlp-4x + 2 LN per block; untied LM head reuses wte).
    Validated exactly against the built model: 1,315,819,520 at
    L=24 h=2048 seq=2048 vocab=50304."""
    per_layer = 12 * hidden * hidden + 13 * hidden
    misc = vocab * hidden + seq * hidden + 2 * hidden  # wte + wpe + ln_f
    return misc + layers * per_layer, per_layer, misc


def gpt_plan(layers: int = 24, hidden: int = 2048, heads: int = 16,
             seq: int = 2048, batch: int = 4, vocab: int = 50304,
             optimizer: str = "adamw", offload: str = "off",
             remat: bool = True, multi_precision: bool = True,
             param_bytes: int = 2, budget_gb: float = DEFAULT_BUDGET_GB):
    """Byte plan dict for one GPT training config. ``fits`` compares the
    device-resident total against ``budget_gb``."""
    n, per_layer, misc = gpt_param_counts(layers, hidden, seq, vocab)
    moment_b = MOMENT_BYTES.get(optimizer.lower())
    if moment_b is None:
        raise ValueError(f"unknown optimizer {optimizer!r}; "
                         f"known: {sorted(MOMENT_BYTES)}")
    rows = {
        "params": n * param_bytes,
        "grads": n * param_bytes,
        "master": n * 4 if (multi_precision and param_bytes < 4) else 0,
    }
    host_rows = {}
    moments = n * moment_b
    if offload == "moments" and moments:
        host_rows["host_moments"] = moments
        # in flight: current + prefetched block; worst pair is the misc
        # (embedding) block next to a trunk block
        rows["moments_in_flight"] = (misc + per_layer) * moment_b
    else:
        rows["moments"] = moments
    tok = batch * seq
    if remat:
        # saved: one bf16 block-boundary activation per layer; working
        # set: one block's recomputed fwd+bwd intermediates (qkv 3h +
        # attn out h + mlp 8h + norms ~2h ≈ 14h widths, bf16)
        rows["act_checkpoints"] = layers * tok * hidden * 2
        rows["remat_working"] = 14 * tok * hidden * 2
    else:
        rows["activations"] = layers * 14 * tok * hidden * 2
    # LM head transient: bf16 logits + f32 softmax/CE + f32 dlogits
    rows["logits_ce"] = tok * vocab * (2 + 4 + 4)
    device_total = sum(rows.values())
    return {
        "config": {"layers": layers, "hidden": hidden, "heads": heads,
                   "seq": seq, "batch": batch, "vocab": vocab,
                   "optimizer": optimizer, "offload": offload,
                   "remat": remat, "n_params": n},
        "rows_gb": {k: round(v / GB, 3) for k, v in rows.items()},
        "host_gb": round(sum(host_rows.values()) / GB, 3),
        "device_gb": round(device_total / GB, 3),
        "budget_gb": budget_gb,
        "headroom_gb": round(budget_gb - device_total / GB, 3),
        "fits": device_total / GB <= budget_gb,
    }


def choose_batch(candidates=(4, 2, 1), **kwargs):
    """Largest candidate batch whose plan fits (None if none do), plus
    that plan."""
    for b in candidates:
        plan = gpt_plan(batch=b, **kwargs)
        if plan["fits"]:
            return b, plan
    return None, gpt_plan(batch=candidates[-1], **kwargs)


def tier_plan(offload: str = "off", remat: bool = True,
              optimizer: str = "adamw", **kwargs):
    """The capacity plan a composed tier set is held to by the flag-matrix
    gate (``tools/lint_graph.py --matrix`` / ``analysis/plan_check`` rule
    D004): full-depth GPT-1.3B when the moments are offloaded, the L=12
    half-depth otherwise — resident Adam state alone exceeds HBM at L=24,
    which is exactly the wall the offload tier exists to remove. Returns
    the largest-fitting-batch plan (``fits`` False when even batch 1
    does not fit under the composition)."""
    layers = kwargs.pop("layers", 24 if offload == "moments" else 12)
    _, plan = choose_batch(layers=layers, optimizer=optimizer,
                           offload=offload, remat=remat, **kwargs)
    return plan


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=24)
    p.add_argument("--hidden", type=int, default=2048)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--optimizer", default="adamw",
                   choices=sorted(MOMENT_BYTES))
    p.add_argument("--offload", default="off", choices=["off", "moments"])
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--budget-gb", type=float, default=DEFAULT_BUDGET_GB)
    a = p.parse_args(argv)
    plan = gpt_plan(layers=a.layers, hidden=a.hidden, heads=a.heads,
                    seq=a.seq, batch=a.batch, vocab=a.vocab,
                    optimizer=a.optimizer, offload=a.offload,
                    remat=not a.no_remat, budget_gb=a.budget_gb)
    print(json.dumps(plan, indent=2))
    return 0 if plan["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
