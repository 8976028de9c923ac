"""One general generator per traffic kind; a mix is a data file of parameters
(``benchmark/traffic/<name>.json``) and never code.

Every seed gets the same set of sizes (and, in an open loop, of gaps between
arrivals) in another order, and its own token ids: the seed moves the order
of the work, not its amount.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Iterator, List, Tuple

import numpy as np

KINDS = ("train_steps", "closed_loop", "open_loop")


def load_traffic(root: str, name: str) -> Dict:
    path = os.path.join(root, "benchmark", "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind {mix.get('kind')!r}; one of {KINDS}")
    return mix


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


# -- training ----------------------------------------------------------------

def train_batches(mix: Dict, vocab: int, seed: int):
    """The host-side pool: ``pool`` batches of ``batch`` x ``seq`` token ids,
    every row different; labels are the ids shifted by one (the row's first
    token closes it). The window walks the pool round and round."""
    rng = rng_of(seed, 1)
    pool = []
    for _ in range(mix["pool"]):
        ids = rng.integers(0, vocab, size=(mix["batch"], mix["seq"]),
                           dtype=np.int32)
        labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        pool.append((ids, labels))
    return pool


# -- serving -----------------------------------------------------------------

def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def size_set(mix: Dict) -> List[Tuple[int, int]]:
    """The mix's fixed set of (prompt_len, output_len) pairs: ``n_sizes``
    evenly spaced quantiles of each distribution, paired by a permutation
    that belongs to the mix and not to the seed."""
    n = mix["n_sizes"]
    q = _quantiles(n)
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    if mix["prompt_len"]["dist"] == "log_uniform":
        prompts = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif mix["prompt_len"]["dist"] == "uniform":
        prompts = lo + q * (hi - lo)
    else:
        raise ValueError(f"prompt_len.dist {mix['prompt_len']['dist']!r}")
    olo, ohi = mix["output_len"]["lo"], mix["output_len"]["hi"]
    if mix["output_len"]["dist"] != "uniform":
        raise ValueError(f"output_len.dist {mix['output_len']['dist']!r}")
    outs = olo + q * (ohi - olo)
    pairing = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    return [(int(round(p)), int(round(outs[j])))
            for p, j in zip(prompts, pairing)]


def request_stream(mix: Dict, vocab: int, seed: int
                   ) -> Iterator[Tuple[np.ndarray, int]]:
    """Endless (prompt_ids, output_len): the size set in an order drawn from
    the seed, again and again, each prompt with token ids of its own (no two
    requests share a prefix unless the mix says so)."""
    sizes = size_set(mix)
    rng = rng_of(seed, 2)
    shared = None
    if mix.get("shared_prefix_len"):
        shared = rng.integers(0, vocab, size=mix["shared_prefix_len"],
                              dtype=np.int32)
    while True:
        for i in rng.permutation(len(sizes)):
            plen, olen = sizes[i]
            ids = rng.integers(0, vocab, size=plen, dtype=np.int32)
            if shared is not None:
                n = min(len(shared), plen - 1)
                ids[:n] = shared[:n]
            yield ids, olen


def arrival_gaps(mix: Dict, seed: int) -> Iterator[float]:
    """Endless gaps (s) between the arrivals of an open loop at ``rate_rps``.

    ``steady``: the quantiles of an exponential gap (a Poisson stream's), in
    an order drawn from the seed. ``bursty``: the same, but the stream is on
    for ``burst.on_s`` at ``burst.factor`` times the rate and then silent so
    that the mean rate holds."""
    n = mix["n_sizes"]
    gaps = -np.log(1.0 - _quantiles(n)) / mix["rate_rps"]
    gaps *= (1.0 / mix["rate_rps"]) / gaps.mean()
    rng = rng_of(seed, 3)
    burst = mix.get("burst")
    t_on = 0.0
    while True:
        for i in rng.permutation(n):
            if not burst:
                yield float(gaps[i])
                continue
            g = float(gaps[i]) / burst["factor"]
            t_on += g
            if t_on >= burst["on_s"]:
                g += burst["on_s"] * (burst["factor"] - 1.0)
                t_on = 0.0
            yield g
