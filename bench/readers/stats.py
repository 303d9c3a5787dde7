"""engine.stats(): host-side counts and host clocks inside the engine loop.
spec: {"num": [paths], "den": [paths], "scale": x}. Each path is a
cumulative field of stats() taken as after - before; `window_ms` is the
window's length. The value is scale * sum(num) / sum(den)."""

from readers import dig


def _delta(src, path):
    if path == "window_ms":
        return src["window_s"] * 1e3
    a, b = dig(src["after"], path), dig(src["before"], path)
    return None if a is None or b is None else a - b


def read(sources, spec):
    src = sources.get("stats")
    if not src:
        return None
    num = [_delta(src, p) for p in spec["num"]]
    den = [_delta(src, p) for p in spec["den"]]
    if None in num or None in den or sum(den) <= 0:
        return None
    return spec.get("scale", 1.0) * sum(num) / sum(den)
