"""serve/observatory.py: the six-phase record of every finished request.
spec: {"phases": [names], "scale": x}: the mean, over the window's
requests, of the sum of those phases (seconds) times scale."""


def read(sources, spec):
    records = [r for r in sources.get("observatory") or []
               if all(p in r.get("phases", {}) for p in spec["phases"])]
    if not records:
        return None
    each = [sum(r["phases"][p] for p in spec["phases"]) for r in records]
    return spec.get("scale", 1.0) * sum(each) / len(each)
