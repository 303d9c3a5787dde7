"""train/flight_recorder.py StepProfiler: one record per span between two
syncs. spec: {"field": f, "per": g, "scale": x}: the median over records of
record[f] / record[g] (g optional) times scale."""

import statistics


def read(sources, spec):
    vals = []
    for r in sources.get("recorder") or []:
        v, per = r.get(spec["field"]), r.get(spec.get("per", ""), 1)
        if v is not None and per:
            vals.append(v / per)
    if not vals:
        return None
    return spec.get("scale", 1.0) * statistics.median(vals)
