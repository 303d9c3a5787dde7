"""The one general traffic generator: a traffic mix is a JSON file of
parameters under bench/traffic/, and this turns it and `--seed` into the
requests of a run. The program receives only the generated requests.

The arrival and length laws are those of `ray_tpu/loadgen` (`arrival.py`
exponential and Pareto gaps, `workload.py` `LengthMix` bounded lognormal
and `RateCurve`'s flash-crowd step), copied here so that no later PR can
change the yardstick.

Steadiness rule: every seed offers the SAME schedule. The request shapes
(prompt and output lengths), the arrival gaps and their order are all drawn
from the mix's own `pool_seed`; `--seed` draws the token ids (and, in the
cell, the weights). Permuting the same shapes and gaps by the seed was
tried first and is not enough: which long prompt meets which burst moved
the 95th percentile of time to first token by 21% between seeds, while two
runs of one seed differed by 1-2% (my chip runs, PR 23). A mix that wants
another schedule is another mix file with another `pool_seed`.

Mix file, serving (`"kind": "open"` or `"closed"`):

    arrival   {"process": "poisson" | "pareto", "rate_per_s": r,
               "pareto_alpha": a,
               "flash": {"start_share": 0..1, "length_s": s, "mult": m}}
              (open loop) `flash` is a flash crowd inside the window: from
              start_share of the window on, for length_s seconds, arrivals
              come at m times the rate outside the step, and `rate_per_s`
              stays the mean over the window (the ramp has no step)
    clients   n                                        (closed loop)
    stagger_s the callers' first requests spread over this long
    ramp_s    seconds of the same traffic before the window, not measured
    prompt, output   {"median", "sigma", "lo", "hi"}   bounded lognormal
    shared_prefix    {"share": 0..1, "tokens": n, "groups": g}
    sampling  {"temperature", "top_p"}
    deadline_s       per-request budget handed to the handle
    limits    {"ttft_ms", "tpot_ms"}   reported, judge nothing
    pool_seed, closed_pool   see above

Mix file, training (`"kind": "train"`): {"batch", "seq", "loss_every"}.
"""

from __future__ import annotations

import json
import math
import os
import random
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def _draw_length(rng: random.Random, spec: Dict) -> int:
    n = int(round(rng.lognormvariate(math.log(spec["median"]),
                                     spec["sigma"])))
    return min(max(n, spec["lo"]), spec["hi"])


def _gaps(rng: random.Random, n: int, arrival: Dict) -> List[float]:
    """n inter-arrival gaps of mean 1 (scaled to the rate by the caller)."""
    process = arrival.get("process", "poisson")
    if process == "poisson":
        return [rng.expovariate(1.0) for _ in range(n)]
    if process == "pareto":
        alpha = float(arrival.get("pareto_alpha", 1.5))
        if alpha <= 1.0:
            raise ValueError("pareto_alpha must be > 1 (finite mean)")
        mean = alpha / (alpha - 1.0)
        return [rng.paretovariate(alpha) / mean for _ in range(n)]
    raise ValueError(f"unknown arrival process {process!r}")


def _flash_warp(flash: Dict, span: float):
    """`RateCurve`'s flash-crowd step as a change of clock: inside
    [start, start + length) the rate is `mult` times the rate outside, and
    the mean over the span is unchanged. Returns the map from an arrival's
    time at the even rate to its time under the step; both run over
    [0, span). Applied to exponential gaps it gives a Poisson process of
    that rate, and to Pareto gaps the same bursts squeezed and stretched."""
    a = float(flash["start_share"]) * span
    b = min(a + float(flash["length_s"]), span)
    mult = float(flash["mult"])
    if not (0.0 <= a < b and mult > 0.0):
        raise ValueError(f"flash step {flash!r} is empty or outside the "
                         f"window of {span:g} s")
    outside = span / (span + (mult - 1.0) * (b - a))  # share of the mean
    ua, ub = a * outside, (a + (b - a) * mult) * outside

    def warp(u: float) -> float:
        if u < ua:
            return u / outside
        if u < ub:
            return a + (u - ua) / (outside * mult)
        return b + (u - ub) / outside

    return warp


def _shapes(pool: random.Random, n: int, mix: Dict) -> List[Dict]:
    return [{"prompt_len": _draw_length(pool, mix["prompt"]),
             "max_new": _draw_length(pool, mix["output"])}
            for _ in range(n)]


def _fill_tokens(rng: random.Random, shapes: List[Dict], mix: Dict,
                 vocab: int) -> None:
    """Token ids from the run's seed. Requests share a prefix only where
    the mix says so (`shared_prefix`), else every prompt is distinct."""
    sp = mix.get("shared_prefix") or {}
    share, groups = float(sp.get("share", 0.0)), int(sp.get("groups", 1))
    prefixes = [[rng.randrange(vocab) for _ in range(int(sp.get("tokens", 0)))]
                for _ in range(groups if share > 0 else 0)]
    for s in shapes:
        head: List[int] = []
        if prefixes and rng.random() < share:
            head = prefixes[rng.randrange(len(prefixes))][:s["prompt_len"] - 1]
        s["prompt"] = head + [rng.randrange(vocab)
                              for _ in range(s["prompt_len"] - len(head))]


def serve_requests(mix: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    """The requests of one serving run.

    Open loop: `ramp` requests due in [-ramp_s, 0) and `window` requests
    due in [0, seconds), each with its offset `t` from the window's start.
    Closed loop: one list `pool` the clients draw from in order; requests
    taken before the window's start are the ramp.
    """
    pool = random.Random(int(mix.get("pool_seed", 0)))
    rng = random.Random(seed)
    ramp_s = float(mix.get("ramp_s", 0.0))
    if mix["kind"] == "closed":
        shapes = _shapes(pool, int(mix["closed_pool"]), mix)
        _fill_tokens(rng, shapes, mix, vocab)
        return {"kind": "closed", "clients": int(mix["clients"]),
                "ramp_s": ramp_s, "pool": shapes}
    if mix["kind"] != "open":
        raise ValueError(f"not a serving mix: kind {mix['kind']!r}")
    rate = float(mix["arrival"]["rate_per_s"])
    out = {"kind": "open", "ramp_s": ramp_s}
    for part, span in (("window", seconds), ("ramp", ramp_s)):
        n = int(round(rate * span))
        shapes = _shapes(pool, n, mix)
        gaps = _gaps(pool, n, mix["arrival"])
        # Scaled so that the n arrivals fill the span exactly.
        scale = span / sum(gaps) if gaps else 0.0
        flash = mix["arrival"].get("flash") if part == "window" else None
        warp = _flash_warp(flash, span) if flash else (lambda u: u)
        t = 0.0
        for s, g in zip(shapes, gaps):
            s["t"] = warp(t) - (span if part == "ramp" else 0.0)
            t += g * scale
        _fill_tokens(rng, shapes, mix, vocab)
        out[part] = shapes
    return out
