"""A model that holds one chip's share of each layer's routed experts:
`engine.stats()["moe"]` of such a model (the routing accumulator the step
programs keep on the device, with the share's own sizes beside it:
`experts_held` of `num_experts` in each of `expert_layers` layers, and
`held_assignments` of the `assignments` routed) and, for the roofline share,
the reduced trace. A window is after - before. spec["quantity"]:

  held_assignment_share  assignments to the experts held here over all the
                         router made (experts_held / num_experts is even:
                         6.25% for 16 of 256)
  held_experts_hit_share an expert layer's held experts that received a
                         row, a mean over the window's calls and the expert
                         layers, over the experts held
  held_load_max_over_mean  the largest held expert's rows over the mean
                         held expert's, a mean over calls and expert layers
  expert_roofline_share  `readers/moe.py`'s, for the share: `expert_bytes`
                         (the configuration's `operations` module) at the
                         window's mean HELD experts hit, times the EXPERT
                         layers (a leading dense layer has none), times the
                         traced launches of the programs that run a grouped
                         product, over peak bytes/s (bench/peaks.json), over
                         the device time of the operations matching
                         spec["match"]

A model whose stats() has no "moe", or one without the share's sizes in it
(a whole model's, a parent's program), reads None and the metric is left
out."""

import re

import flops
import spec as cells

COUNTED = ("assignments", "held_assignments", "calls", "experts_hit_sum",
           "max_load_sum")


def _window(sources):
    src = sources.get("stats")
    if not src or not src.get("before") or not src.get("after"):
        return None
    before, after = src["before"].get("moe"), src["after"].get("moe")
    if not before or not after or "held_assignments" not in after:
        return None
    win = {k: after[k] - before.get(k, 0) for k in COUNTED}
    win.update({k: after[k] for k in ("experts_held", "expert_layers")})
    ok = win["calls"] > 0 and win["assignments"] > 0 and win["expert_layers"]
    return win if ok else None


def read(sources, spec):
    win = _window(sources)
    if win is None:
        return None
    layers, held = win["expert_layers"], win["experts_held"]
    hit = win["experts_hit_sum"] / (win["calls"] * layers)
    q = spec["quantity"]
    if q == "held_assignment_share":
        return 100.0 * win["held_assignments"] / win["assignments"]
    if q == "held_experts_hit_share":
        return 100.0 * hit / held
    if q == "held_load_max_over_mean":
        if win["held_assignments"] <= 0:
            return None
        return win["max_load_sum"] * held / win["held_assignments"]
    if q != "expert_roofline_share":
        raise ValueError(f"unknown moe_share quantity {q!r}")
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
        sources["model"]["dims"], hit, layers) * launches
    peak = flops.peaks(device["kind"])["hbm_bytes_per_s"]
    return 100.0 * needed / peak / seconds
