"""bench/listener.py: backend compilations that ended inside the window,
in the process that holds the chip. spec: {}."""


def read(sources, spec):
    got = sources.get("listener")
    return None if not got else float(got["compiles_in_window"])
