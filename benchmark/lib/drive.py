"""The measured window of each traffic kind. One process, one thread: the
driver calls the program's entry (``TrainStep.step``; ``ServingEngine.submit``
and ``step``) itself and stamps what it sees on its own clock.

With a trace directory the last ``TRACE_SECONDS`` of the window run under the
JAX profiler, wrapped in a ``bench.window`` span; ``bench.train_step``,
``bench.batch``, ``bench.sync``, ``bench.submit`` and ``bench.engine_step``
spans name what the host was doing, so that the trace reduction can say what
each idle gap of the device waited for.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax

from . import traffic as T

TRACE_SECONDS = 4.0
clock = time.perf_counter


class _Tracing:
    """Starts the profiler when the window has ``TRACE_SECONDS`` left (or at
    once in a shorter window) and stops it after the window has closed."""

    def __init__(self, trace_dir: Optional[str], t_open: float,
                 seconds: float):
        self.dir = trace_dir
        self.start_at = t_open + max(0.0, seconds - TRACE_SECONDS)
        self.on = False
        self.t0 = self.t1 = None
        self._span = None

    def maybe_start(self, now: float, quiesce: Callable[[], None]) -> bool:
        if self.dir is None or self.on or now < self.start_at:
            return False
        quiesce()
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.on, self.t0 = True, clock()
        return True

    def stop(self) -> None:
        if not self.on:
            return
        self.t1 = clock()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


# -- training ----------------------------------------------------------------

def train_window(step: Callable, pool: List, first_index: int,
                 seconds: float, trace_dir: Optional[str] = None) -> Dict:
    """Optimizer steps for ``seconds``; one step stays queued behind the one
    that runs, so the device never waits for the host and the host never
    runs far ahead of the clock. The window closes when the last step's loss
    is ready. ``step(batch) -> loss`` is ``TrainStep.step`` itself."""
    losses, prev = [], None
    t_open = clock()
    tr = _Tracing(trace_dir, t_open, seconds)
    n = trace_n0 = 0
    while True:
        now = clock()
        if tr.maybe_start(now, lambda: prev is None or prev.block_until_ready()):
            trace_n0 = n
        if now - t_open >= seconds:
            break
        with span("bench.batch"):
            batch = pool[(first_index + n) % len(pool)]
        with span("bench.train_step"):
            loss = step(batch)
        if prev is not None:
            with span("bench.sync"):
                prev.block_until_ready()
        losses.append(loss)
        prev = loss
        n += 1
    with span("bench.sync"):
        jax.block_until_ready(prev)
    t_close = clock()
    tr.stop()
    rec = {"kind": "train_steps", "window_s": t_close - t_open, "steps": n,
           "losses": [float(x) for x in losses]}
    if tr.t0 is not None:
        rec["traced"] = {"window_s": tr.t1 - tr.t0, "steps": n - trace_n0}
    return rec


# -- serving -----------------------------------------------------------------

class _Live:
    __slots__ = ("rid", "seq", "prompt", "want", "t_due", "n_out",
                 "t_tokens", "client")

    def __init__(self, rid, seq, prompt, want, t_due, client):
        self.rid, self.seq, self.prompt, self.want = rid, seq, prompt, want
        self.t_due, self.client = t_due, client
        self.n_out = 0
        self.t_tokens: List[float] = []


def serve_window(eng, system, mix: Dict, vocab: int, seed: int,
                 seconds: float, trace_dir: Optional[str] = None) -> Dict:
    """A closed or an open loop against ``eng`` for ``preroll_s`` and then
    ``seconds``. Tokens become visible when ``eng.step()`` returns, and are
    stamped then; a request is timed from when it was due."""
    stream = T.request_stream(mix, vocab, seed)
    closed = mix["kind"] == "closed_loop"
    live: Dict[str, _Live] = {}
    done: List[_Live] = []
    refused: List[str] = []
    steps: List[Dict] = []
    late_ms: List[float] = []
    n_sub = 0

    def submit(t_due: float, client: int, out_scale: float = 1.0):
        nonlocal n_sub
        prompt, want = next(stream)
        want = max(1, int(round(want * out_scale)))
        rid = f"q{n_sub}"
        n_sub += 1
        with span("bench.submit"):
            now = clock()
            seq = eng.submit(system.make_request(rid, prompt, want))
        if system.request_failed(seq):
            refused.append(rid)
            return
        live[rid] = _Live(rid, seq, prompt, want, t_due, client)
        late_ms.append((now - t_due) * 1e3)

    t0 = clock()
    t_open = t0 + mix["preroll_s"]
    t_close = t_open + seconds
    tr = _Tracing(trace_dir, t_open, seconds)
    trace_step0 = None
    if closed:
        # every client starts somewhere inside a request: the first answers
        # are cut to a phase of their length, so the window opens on a loop
        # in its steady mixture and not on 32 prefills in a row
        n_cl = mix["clients"]
        phase = T.rng_of(seed, 4).permutation(n_cl)
        for c in range(n_cl):
            submit(t0, c, out_scale=(phase[c] + 0.5) / n_cl)
    else:
        gaps = T.arrival_gaps(mix, seed)
        next_due = t0 + next(gaps)

    while True:
        now = clock()
        if now >= t_close:
            break
        if tr.maybe_start(now, lambda: None):
            trace_step0 = len(steps)
        if not closed:
            while next_due <= now:
                submit(next_due, -1)
                next_due += next(gaps)
        if not live:    # an open loop between arrivals
            wake = t_close if closed else min(next_due, t_close)
            time.sleep(max(0.0, wake - clock()))
            continue
        ts0 = clock()
        with span("bench.engine_step"):
            eng.step()
        ts1 = clock()
        prefills, decode_ctx = [], []
        for rid in list(live):
            lv = live[rid]
            k = len(lv.seq.out_tokens)
            gained = k - lv.n_out
            if gained:
                if lv.n_out == 0:
                    prefills.append(len(lv.prompt))
                    decode_ctx += [len(lv.prompt) + j for j in range(1, gained)]
                else:
                    decode_ctx += [len(lv.prompt) + lv.n_out + j
                                   for j in range(gained)]
                lv.t_tokens += [ts1] * gained
                lv.n_out = k
            if system.request_ok(lv.seq) or system.request_failed(lv.seq):
                done.append(live.pop(rid))
                if closed:
                    submit(ts1, lv.client)
        steps.append({"t0": ts0, "t1": ts1, "prefills": prefills,
                      "decode_ctx": decode_ctx})
    tr.stop()

    def in_window(t):
        return t_open <= t < t_close

    everyone = done + list(live.values())
    gaps_ms, ttft_ms, tokens_out = [], [], 0
    for lv in everyone:
        tt = lv.t_tokens
        tokens_out += sum(1 for t in tt if in_window(t))
        if tt and in_window(tt[0]):
            ttft_ms.append((tt[0] - lv.t_due) * 1e3)
        gaps_ms += [(b - a) * 1e3 for a, b in zip(tt, tt[1:]) if in_window(b)]
    in_win = [s for s in steps if in_window(s["t1"])]
    attempted = [lv for lv in everyone
                 if not (lv.t_tokens and lv.n_out >= lv.want
                         and lv.t_tokens[-1] < t_open)]
    rec = {
        "kind": mix["kind"], "window_s": t_close - t_open,
        "t_open": t_open, "t_close": t_close,
        "steps": in_win, "tokens_out": tokens_out,
        "gaps_ms": gaps_ms, "ttft_ms": ttft_ms, "late_ms": late_ms,
        "attempted": len(attempted) + len(refused),
        "failed": len(refused) + sum(
            1 for lv in attempted if system.request_failed(lv.seq)),
        "finished": [
            {"rid": lv.rid, "prompt": lv.prompt,
             "tokens": list(lv.seq.out_tokens)}
            for lv in done if system.request_ok(lv.seq)
            and in_window(lv.t_tokens[-1]) and len(lv.seq.out_tokens) > 1],
    }
    if tr.t0 is not None:
        rec["traced"] = {"window_s": tr.t1 - tr.t0,
                         "steps": steps[trace_step0:]}
    return rec
