"""A model with layers that attend over a window: the reduced trace and
`engine.stats()["attention"]`'s pair of counts of those layers (a host
mirror of the slots' lengths; a window is after - before). spec["quantity"]:

  decode_roofline_share  the least time the chip's memory could take to
                         read the rows the window layers of the traced
                         decode launches had to read, over the time the
                         matched operations (the ring's decode kernel) took:
                         spec["bytes"], a function of the configuration's
                         `operations` module, at the window's mean rows a
                         step (`attention.window_rows_read` over `steps`:
                         summed over slots and window layers, a slot's the
                         lesser of its length and the window), times the
                         traced launches of the programs that run a matched
                         operation, over peak bytes/s (bench/peaks.json)

A program without the counters (a model without a window layer, a parent's
program) or a trace without a matching operation reads None and the metric
is left out."""

import re

import flops
import spec as cells
from readers import dig


def read(sources, spec):
    if spec["quantity"] != "decode_roofline_share":
        raise ValueError(f"unknown window quantity {spec['quantity']!r}")
    tr, model = sources.get("trace"), sources["model"]
    src = sources.get("stats") or {}
    if (not tr or not tr.get("op_s") or model["device"]["platform"] != "tpu"
            or not src.get("before") or not src.get("after")):
        return None
    rows, steps = (
        (dig(src["after"], path) or 0) - (dig(src["before"], path) or 0)
        for path in ("attention.window_rows_read", "steps"))
    pat = re.compile(spec["match"])
    seconds = sum(s for name, s in tr["op_s"].items() if pat.search(name))
    launches = sum(m["launches"] for m in tr["modules"].values()
                   if any(pat.search(op) for op in m["ops"]))
    if not rows or not steps or seconds <= 0 or not launches:
        return None
    needed = getattr(cells.named_module(model, "operations"), spec["bytes"])(
        model["dims"], rows / steps) * launches
    peak = flops.peaks(model["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * needed / peak / seconds
