"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside a limit of its own. The
limits are data (the cell's file, ``benchmark/cells/<cell>.json``), set from
readings that ``PERF.md`` gives; this file holds the arithmetic only.
"""

from __future__ import annotations

import statistics
import sys
from typing import Dict, List

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone (a key's bias under softmax): its
# change is not compared
DEAD_GRAD_SHARE = 1e-3

# the control of a comparison: the reference computed in the nearest
# precision below the one that the configuration states
NEXT_LOWER = {"float32": "bfloat16", "bfloat16": "float8",
              "float16": "float8", "float8": "int4", "int8": "int4"}


def control_mode(cfg: Dict) -> str:
    return NEXT_LOWER[cfg["precision"]["compute"]]


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   skip=()) -> Dict:
    """Largest |prog norm - ref norm| over the leaves, each against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; and the leaf that gave it."""
    floor = statistics.median(ref.values())
    worst, where = 0.0, None
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, floor, 1e-30)
        if gap > worst or where is None:
            worst, where = gap, name
    return {"value": worst, "leaf": where}


def dead_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return [n for n, g in ref_grad.items() if g < DEAD_GRAD_SHARE * med]


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """``prog`` / ``ref``: {"losses": [3], "grad": {leaf: norm}, "delta":
    {leaf: norm}} of the first three steps."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss{i}_rel"] = {"value": abs(a - b) / abs(b)}
    out["grad1_leaf_gap"] = worst_leaf_gap(prog["grad"], ref["grad"])
    out["delta3_leaf_gap"] = worst_leaf_gap(
        prog["delta"], ref["delta"], skip=dead_leaves(ref["grad"]))
    return out


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the reference agrees)."""
    lg = np.asarray(ref_logits, np.float32)[:len(tokens)]
    tok = np.asarray(tokens)
    return lg.max(axis=-1) - lg[np.arange(len(tok)), tok]


def serve_numbers(gaps_by_request: List[np.ndarray]) -> Dict[str, Dict]:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample."""
    allg = np.concatenate([np.asarray(g, np.float64)
                           for g in gaps_by_request])
    return {"served_logit_gap_max": {"value": float(allg.max()),
                                     "tokens": int(allg.size),
                                     "requests": len(gaps_by_request)}}


def judge(numbers: Dict[str, Dict], limits: Dict[str, float],
          stream=None) -> (bool, Dict):
    """Every number named in ``limits`` has to be there, finite and at or
    under its limit. Prints each beside its limit (the run's last lines on
    standard error) and returns the same for the result line."""
    stream = stream or sys.stderr
    ok, shown = True, {}
    for name, limit in limits.items():
        got = numbers.get(name, {}).get("value")
        good = got is not None and np.isfinite(got) and got <= limit
        ok = ok and bool(good)
        shown[name] = {"value": got, "limit": limit}
    for name, num in numbers.items():
        if name not in limits:
            shown[name] = {"value": num.get("value"), "limit": None}
    for name, row in shown.items():
        extra = {k: v for k, v in numbers.get(name, {}).items()
                 if k != "value"}
        print(f"compared {name} value={row['value']} limit={row['limit']}"
              f" {extra if extra else ''}", file=stream)
    print(f"correct={ok}", file=stream)
    return ok, shown
