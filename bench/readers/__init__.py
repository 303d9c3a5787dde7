"""Per-layer metric readers: one small function per source.

A metric is a data file `bench/layer_metrics/<name>.json` naming a reader
here and its arguments; `read(sources, spec)` returns the number, or None
where the source has nothing to read (the harness then leaves the metric
out of the line). A new metric over an existing source is data only; a new
source is one new file here.

`sources` is what a traced run gathered: `stats` (engine.stats() before and
after the window), `observatory` (finished-request phase records),
`recorder` (StepProfiler records), `trace` (bench/xplane/reduce.py's
reduction), `client` (bench/traffic/client.py's summary, or the train
loop's), `listener` (bench/listener.py) and `model` (sizes, chips, device).
"""

from __future__ import annotations

import importlib
from typing import Dict, Optional


def read(reader: str, sources: Dict, spec: Dict) -> Optional[float]:
    return importlib.import_module(f"readers.{reader}").read(sources, spec)


def dig(doc, path: str):
    """`a.b.c` into nested dicts; None where a step is missing."""
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc
