"""A model with latent attention: the reduced trace and, for the roofline
share, `engine.stats()["attention"]`. The decode program's attention over
a latent pool is a kernel (`%latent_decode_attention`, PR 53) and the
prefill program's a loop of plain operations; a trace names an operation
by its own name and its first result's shape, and both are found by those
shapes. spec["quantity"]:

  attn_time_share        device time of the operations matching
                         spec["match"] in the programs that run an operation
                         matching spec["contains_op"] (decode and prefill
                         are told apart by their activations' shape, as
                         `decode.device_ms_per_step` does), over those
                         programs' device time
  decode_attn_roofline_share  the least time the chip could take for the
                         absorbed attention of the traced decode launches,
                         the larger of `latent_attention_flops` over peak
                         operations/s and `latent_attention_bytes` over
                         peak bytes/s (the configuration's `operations`
                         module, bench/peaks.json), at the window's mean
                         LIVE cached rows a step (`decode_rows_live` over
                         `steps`: what the algorithm needs, which is fewer
                         than the rows in the whole pages the kernel
                         fetches, `decode_rows_read`), times the traced
                         launches, over the matched operations' time

spec["match"] and spec["contains_op"] name the run's own sizes as <slots>,
<heads>, <rank>, <rope>, <nope>, <v>, <d_model>, <latent> (rank + rope) and
<row> (the latent padded to whole lanes of 128), filled in here from
`sources["model"]`, so another slot count or width does not silence them.

A program without the counter, or a trace without a matching program,
reads None and the metric is left out."""

import re

import flops
import spec as cells
from readers import dig


def _sized(pattern, model):
    """`pattern` with the run's sizes in place of their <names>."""
    m = model["dims"]
    latent = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    sizes = {"slots": model["num_slots"], "heads": m["n_heads"],
             "rank": m["kv_lora_rank"], "rope": m["qk_rope_head_dim"],
             "nope": m["qk_nope_head_dim"], "v": m["v_head_dim"],
             "d_model": m["d_model"], "latent": latent,
             "row": -(-latent // 128) * 128}
    return re.sub(r"<(\w+)>", lambda g: str(sizes[g.group(1)]), pattern)


def _traced(sources, spec):
    """(seconds in the matched operations, seconds in the chosen programs,
    their launches); None off the chip or where nothing matches."""
    tr, device = sources.get("trace"), sources["model"]["device"]
    if not tr or not tr.get("op_s") or device["platform"] != "tpu":
        return None
    pat, program = (re.compile(_sized(spec[k], sources["model"]))
                    for k in ("match", "contains_op"))
    chosen = [m for m in tr["modules"].values()
              if any(program.search(op) for op in m["ops"])]
    names = {op for m in chosen for op in m["ops"] if pat.search(op)}
    seconds = sum(tr["op_s"].get(name, 0.0) for name in names)
    launches = sum(m["launches"] for m in chosen)
    whole = sum(m["total_s"] for m in chosen)
    return (seconds, whole, launches) if seconds > 0 and launches else None


def read(sources, spec):
    traced = _traced(sources, spec)
    if traced is None:
        return None
    seconds, whole, launches = traced
    q = spec["quantity"]
    if q == "attn_time_share":
        return 100.0 * seconds / whole
    if q != "decode_attn_roofline_share":
        raise ValueError(f"unknown mla quantity {q!r}")
    src = sources.get("stats") or {}
    rows, steps = (
        None if not src.get("before") or not src.get("after") else
        (dig(src["after"], path) or 0) - (dig(src["before"], path) or 0)
        for path in ("attention.decode_rows_live", "steps"))
    if not rows or not steps:
        return None
    model = sources["model"]
    ops = cells.named_module(model, "operations")
    peak = flops.peaks(model["device"]["kind"])
    layers = model["dims"]["n_layers"]
    per_launch = rows / steps
    least = max(
        ops.latent_attention_flops(model["dims"], per_launch, layers)
        / peak["bf16_flops_per_s"],
        ops.latent_attention_bytes(model["dims"], per_launch, layers)
        / peak["hbm_bytes_per_s"]) * launches
    return 100.0 * least / seconds
