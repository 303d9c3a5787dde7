"""serve/observatory.py's finished-request records, a field at a time.
spec: {"num": "a.b", "den": "a.c" (optional), "stat": "mean" | "p95",
"scale": x}. `mean` is the ratio of sums over the window's records (the
sum of num over the sum of den, or over the number of records where no den
is given); `p95` is over the per-record values num / den, by the client's
own quantile. A record without the field (or with an empty den) is
skipped; none left returns None."""

from readers import dig
from traffic.client import quantile


def read(sources, spec):
    pairs = []
    for r in sources.get("observatory") or []:
        num = dig(r, spec["num"])
        den = dig(r, spec["den"]) if "den" in spec else 1
        if num is not None and den:
            pairs.append((num, den))
    if not pairs:
        return None
    if spec["stat"] == "mean":
        value = sum(n for n, _d in pairs) / sum(d for _n, d in pairs)
    elif spec["stat"] == "p95":
        value = quantile([n / d for n, d in pairs], 0.95)
    else:
        raise ValueError(f"unknown request stat {spec['stat']!r}")
    return spec.get("scale", 1.0) * value
