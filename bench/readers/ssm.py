"""A model with recurrent layers: `engine.stats()["ssm"]` (the accumulator
its step programs keep on the device, fetched when stats() is read, so a
window is after - before) and, for the roofline shares, the reduced trace.
spec["quantity"]:

  live_row_share        slot rows whose state a decode step advanced for a
                        live sequence, over the rows it computed (every
                        slot, every step)
  update_roofline_share the least time the chip's memory could take to
                        read and write the state of the rows the traced
                        decode launches computed (`state_update_bytes` of
                        the configuration's `operations` module, at
                        launches x slots rows, over peak bytes/s of
                        bench/peaks.json), over the device time of the
                        operations matching spec["match"]
  scan_roofline_share   the same for the chunked scan of the traced
                        prefill launches: the larger of `scan_flops` over
                        peak operations/s and `scan_bytes` over peak
                        bytes/s, at launches x spec["tokens_per_launch"]
                        tokens, over the matched operations' time

A trace's launches and operations are those of the programs that run an
operation matching spec["contains_op"] (decode and prefill are told apart
by their activations' shape, as `decode.device_ms_per_step` does), and only
those programs' operations are matched.

A model without recurrent layers has no "ssm" in stats() and a parent's
program has none either: every quantity then reads None and the metric is
left out."""

import re

import flops
import spec as cells


def _window(sources):
    src = sources.get("stats")
    if not src or not src.get("before") or not src.get("after"):
        return None
    before, after = src["before"].get("ssm"), src["after"].get("ssm")
    if not before or not after:
        return None
    return {k: after[k] - before[k]
            for k in ("decode_rows_live", "decode_rows_computed", "calls")}


def _traced(sources, spec):
    """(seconds in the matched operations, launches) of the programs that
    run an operation matching spec["contains_op"]; None off the chip or
    where nothing matches."""
    tr, device = sources.get("trace"), sources["model"]["device"]
    if not tr or not tr.get("op_s") or device["platform"] != "tpu":
        return None
    pat, program = re.compile(spec["match"]), re.compile(spec["contains_op"])
    chosen = [m for m in tr["modules"].values()
              if any(program.search(op) for op in m["ops"])]
    names = {op for m in chosen for op in m["ops"] if pat.search(op)}
    seconds = sum(tr["op_s"].get(name, 0.0) for name in names)
    launches = sum(m["launches"] for m in chosen)
    return (seconds, launches) if seconds > 0 and launches else None


def read(sources, spec):
    win = _window(sources)
    if win is None:
        return None
    q = spec["quantity"]
    if q == "live_row_share":
        if win["decode_rows_computed"] <= 0:
            return None
        return 100.0 * win["decode_rows_live"] / win["decode_rows_computed"]
    if q not in ("update_roofline_share", "scan_roofline_share"):
        raise ValueError(f"unknown ssm quantity {q!r}")
    traced = _traced(sources, spec)
    if traced is None:
        return None
    seconds, launches = traced
    model = sources["model"]
    ops = cells.named_module(model, "operations")
    peak = flops.peaks(model["device"]["kind"])
    if q == "update_roofline_share":
        least = ops.state_update_bytes(
            model["dims"], launches * model["num_slots"]
        ) / peak["hbm_bytes_per_s"]
    else:
        tokens = launches * spec["tokens_per_launch"]
        least = max(
            ops.scan_flops(model["dims"], tokens) / peak["bf16_flops_per_s"],
            ops.scan_bytes(model["dims"], tokens, launches)
            / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
