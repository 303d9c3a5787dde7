"""What a cell is made of, found by name: BENCHMARK.json's entry, the
configuration's file, the traffic mix's file and the per-layer metrics'
files. Nothing here knows a cell, a configuration or a metric by name, so a
later PR adds one as files and one entry, and edits nothing."""

from __future__ import annotations

import json
import os
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
CACHE = os.path.join(REPO, ".cache", "bench")

# The model's public config.json key -> the program's TransformerConfig
# field. These are the sizes a configuration file states and the program's
# named config has to agree with (depth alone may be `reduced`).
PUBLISHED_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _with_preset(doc: Dict, platform: str) -> Dict:
    """On the CPU rehearsal a file's `cpu_preset` overrides its top level
    (one level deep for dict values); on the chip it is ignored."""
    out = {k: v for k, v in doc.items() if k != "cpu_preset"}
    if platform != "cpu":
        return out
    for k, v in (doc.get("cpu_preset") or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def load_cell(workload: str, platform: str) -> Dict:
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    from traffic.generate import load_mix

    def applies(metric: Dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "config_name": config["name"],
        "config": _with_preset(_load(os.path.join(REPO, config["file"])),
                               platform),
        "traffic": _with_preset(load_mix(cell["traffic"]), platform),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def layer_metric_spec(name: str) -> Dict:
    return _load(os.path.join(BENCH, "layer_metrics", f"{name}.json"))


def program_config(doc: Dict, platform: str):
    """The program's TransformerConfig for a configuration file: its named
    model at the file's depth. On the chip every published size in the file
    must be the program's, or the run is of another model."""
    from dataclasses import replace

    from ray_tpu.models import configs

    cfg = configs.get_config(doc["model"])
    if platform != "cpu":
        wrong: List[str] = []
        for key, field in PUBLISHED_KEYS.items():
            if key == "num_hidden_layers":
                continue
            if getattr(cfg, field) != doc[key]:
                wrong.append(f"{key}: file {doc[key]!r}, program "
                             f"{getattr(cfg, field)!r}")
        if wrong:
            raise SystemExit("bench: the configuration file and the "
                             f"program's {doc['model']!r} disagree: "
                             + "; ".join(wrong))
        cfg = replace(cfg, n_layers=doc["num_hidden_layers"])
    return cfg


def dims_of(cfg) -> Dict:
    """The sizes the reference and the FLOP arithmetic take, as plain
    numbers."""
    return {field: getattr(cfg, field) for field in PUBLISHED_KEYS.values()
            if field != "tie_embeddings"}
