"""What the load generator (or the train loop) saw. spec: {"path": "a.b",
"scale": x} into the client summary."""

from readers import dig


def read(sources, spec):
    v = dig(sources.get("client") or {}, spec["path"])
    return None if v is None else spec.get("scale", 1.0) * v
