"""A model with experts: `engine.stats()["moe"]` (the routing accumulator
the step programs keep on the device, fetched when stats() is read, so a
window is after - before) and, for the roofline share, the reduced trace.
spec["quantity"]:

  experts_hit_share     a layer's experts that received a row, a mean over
                        the window's calls and the layers, over all experts
  load_max_over_mean    the largest expert's rows over the mean expert's,
                        a mean over calls and layers (1.0 is even routing)
  expert_roofline_share the least time the chip's memory could take to
                        read the expert weights the traced calls had to
                        read, over the time their grouped products took:
                        `expert_bytes` (the configuration's `operations`
                        module) at the window's mean experts hit, times
                        layers, times the traced launches of the programs
                        that run a grouped product, over peak bytes/s
                        (bench/peaks.json), over the device time of the
                        operations matching spec["match"]

A dense model's stats() has no "moe" and a parent's program has none
either: every quantity then reads None and the metric is left out."""

import re

import flops
import spec as cells


def _window(sources):
    src = sources.get("stats")
    if not src or not src.get("before") or not src.get("after"):
        return None
    before, after = src["before"].get("moe"), src["after"].get("moe")
    if not before or not after:
        return None
    delta = {k: after[k] - before[k]
             for k in ("assignments", "calls", "experts_hit_sum",
                       "max_load_sum")}
    return delta if delta["calls"] > 0 and delta["assignments"] > 0 else None


def read(sources, spec):
    win = _window(sources)
    if win is None:
        return None
    dims = sources["model"]["dims"]
    layers, experts = dims["n_layers"], dims["num_experts"]
    hit = win["experts_hit_sum"] / (win["calls"] * layers)
    q = spec["quantity"]
    if q == "experts_hit_share":
        return 100.0 * hit / experts
    if q == "load_max_over_mean":
        return win["max_load_sum"] * experts / win["assignments"]
    if q != "expert_roofline_share":
        raise ValueError(f"unknown moe quantity {q!r}")
    tr, device = sources.get("trace"), sources["model"]["device"]
    if not tr or not tr.get("op_s") or device["platform"] != "tpu":
        return None
    pat = re.compile(spec["match"])
    seconds = sum(s for name, s in tr["op_s"].items() if pat.search(name))
    launches = sum(m["launches"] for m in tr["modules"].values()
                   if any(pat.search(op) for op in m["ops"]))
    if seconds <= 0 or not launches:
        return None
    needed = cells.named_module(sources["model"], "operations").expert_bytes(
        dims, hit, layers) * launches
    peak = flops.peaks(device["kind"])["hbm_bytes_per_s"]
    return 100.0 * needed / peak / seconds
