"""Client side of a serving run: the open- and closed-loop drivers and the
stamp arithmetic (copied from `ray_tpu/loadgen` `client.py` `StampCard` and
`runner.py`; the originals are listed in PERF.md for a later PR to delete).

`call(request) -> iterator of tokens` is the only thing taken from the
system under test. Nothing here raises into the run: a request that is
shed, fails, is late or returns the wrong number of tokens is a stamp with
an `error`, counted in `failed` against `attempted`.

Open loop: a request's clock starts at its DUE time, not when a thread
got round to sending it, so a stalled generator shows as latency and as
`lateness`, never as a faster server.
"""

from __future__ import annotations

import statistics
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence


class Stamp:
    """One request as the client saw it (perf_counter seconds)."""

    __slots__ = ("due", "sent", "token_t", "tokens", "asked", "prompt_len",
                 "done", "error", "measured")

    def __init__(self, due: float, asked: int, prompt_len: int,
                 measured: bool):
        self.due = due
        self.sent = 0.0
        self.token_t: List[float] = []
        self.tokens: List[int] = []
        self.asked = asked
        self.prompt_len = prompt_len
        self.done: Optional[float] = None
        self.error: Optional[str] = None
        self.measured = measured

    @property
    def ok(self) -> bool:
        return self.error is None and self.done is not None

    @property
    def ttft_s(self) -> Optional[float]:
        return self.token_t[0] - self.due if self.token_t else None

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean gap between this request's output tokens."""
        if len(self.token_t) < 2:
            return None
        return (self.token_t[-1] - self.token_t[0]) / (len(self.token_t) - 1)


def _issue(call: Callable, request: Dict, stamp: Stamp, vocab: int) -> None:
    stamp.sent = time.perf_counter()
    try:
        for tok in call(request):
            stamp.token_t.append(time.perf_counter())
            stamp.tokens.append(int(tok))
        stamp.done = time.perf_counter()
        if len(stamp.tokens) != stamp.asked:
            stamp.error = (f"asked for {stamp.asked} tokens, got "
                           f"{len(stamp.tokens)}")
        elif not all(0 <= t < vocab for t in stamp.tokens):
            stamp.error = "token id out of range"
    except Exception as e:  # noqa: BLE001 — the stamp is the error report
        stamp.error = f"{type(e).__name__}: {e}"[:300]


def drive_open(call: Callable, ramp: Sequence[Dict], window: Sequence[Dict],
               t0: float, vocab: int, drain_s: float
               ) -> List[Stamp]:
    """Send every request at `t0 + request["t"]` whether or not earlier
    ones have returned; one thread a request in flight. Returns when all
    have ended or `drain_s` after the last was due (what is still out
    then is a failure: "not done")."""
    stamps: List[Stamp] = []
    threads: List[threading.Thread] = []
    for measured, reqs in ((False, ramp), (True, window)):
        for r in reqs:
            due = t0 + r["t"]
            delay = due - time.perf_counter()
            while delay > 0:
                time.sleep(delay)
                delay = due - time.perf_counter()
            s = Stamp(due, r["max_new"], r["prompt_len"], measured)
            stamps.append(s)
            th = threading.Thread(target=_issue, args=(call, r, s, vocab),
                                  daemon=True)
            th.start()
            threads.append(th)
    deadline = time.perf_counter() + drain_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.perf_counter()))
    for s in stamps:
        if s.done is None and s.error is None:
            s.error = f"not done {drain_s:.0f}s after the last arrival"
    return stamps


def drive_closed(call: Callable, pool: Iterable[Dict], clients: int,
                 t0: float, seconds: float, vocab: int, drain_s: float,
                 stagger_s: float = 0.0) -> List[Stamp]:
    """`clients` callers, each sending its next request when its last
    returned, from now until `t0 + seconds`; requests begun before `t0`
    are the ramp. The callers start spread evenly over `stagger_s`: started
    together they prefill, decode and finish in waves that last the whole
    window, and which wave the window's edges cut moves the rate by a
    tenth. A client that finds the pool empty stops."""
    it = iter(pool)
    lock = threading.Lock()
    stamps: List[Stamp] = []
    end = t0 + seconds

    def client(i: int) -> None:
        time.sleep(i * stagger_s / clients)
        while True:
            now = time.perf_counter()
            if now >= end:
                return
            with lock:
                r = next(it, None)
                if r is None:
                    return
                s = Stamp(now, r["max_new"], r["prompt_len"], now >= t0)
                stamps.append(s)
            _issue(call, r, s, vocab)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, end - time.perf_counter()))
    deadline = time.perf_counter() + drain_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.perf_counter()))
    with lock:
        out = list(stamps)
    for s in out:
        if s.done is None and s.error is None:
            s.error = f"not done {drain_s:.0f}s after the window"
    return out


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated quantile of all the values (None if empty)."""
    if not values:
        return None
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def summarize(stamps: Sequence[Stamp], t0: float, seconds: float,
              limits: Optional[Dict] = None) -> Dict:
    """What the client saw of the measured requests. Tails are over every
    measured request that produced the stamp at all; a failed request has
    no first token and so no latency, and is counted in `failed`."""
    measured = [s for s in stamps if s.measured]
    ok = [s for s in measured if s.ok]
    ttft = [s.ttft_s * 1e3 for s in measured if s.ttft_s is not None]
    tpot = [s.tpot_s * 1e3 for s in ok if s.tpot_s is not None]
    late = [(s.sent - s.due) * 1e3 for s in measured]
    end = t0 + seconds
    # Output tokens that reached the client inside the window, whichever
    # request (ramp or measured) they belong to.
    in_window = sum(1 for s in stamps for t in s.token_t if t0 <= t < end)
    out = {
        "attempted": len(measured),
        "failed": len(measured) - len(ok),
        "errors": sorted({s.error for s in measured if s.error})[:5],
        "tokens_in_window": in_window,
        "tokens_per_s": in_window / seconds,
        "ttft_ms": {"n": len(ttft), "p50": quantile(ttft, 0.5),
                    "p95": quantile(ttft, 0.95)},
        "tpot_ms": {"n": len(tpot), "p50": quantile(tpot, 0.5),
                    "p95": quantile(tpot, 0.95)},
        "lateness_ms": {"mean": statistics.fmean(late) if late else None,
                        "max": max(late) if late else None},
        "prompt_tokens": sum(s.prompt_len for s in measured),
        "output_tokens": sum(len(s.tokens) for s in measured),
        "backlog_at_end": sum(1 for s in measured
                              if s.done is None or s.done > end),
    }
    if limits:
        inside = sum(
            1 for s in ok
            if s.ttft_s * 1e3 <= limits["ttft_ms"]
            and (s.tpot_s is None or s.tpot_s * 1e3 <= limits["tpot_ms"]))
        out["inside_both_limits_share"] = (
            inside / len(measured) if measured else None)
    return out
