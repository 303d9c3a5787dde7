"""Mean active slots over slots: every engine step emits one token for
each active slot, so the window's decode tokens (output tokens that
reached the client in the window, less the first token of each request,
which prefill emits) over steps x slots. spec: {}."""

from readers import dig


def read(sources, spec):
    src, client = sources.get("stats"), sources.get("client")
    if not src or not client:
        return None
    steps = dig(src["after"], "steps") - dig(src["before"], "steps")
    slots = sources["model"]["num_slots"]
    if steps <= 0:
        return None
    return 100.0 * client["decode_tokens_in_window"] / (steps * slots)
