"""The probe of an engine whose cache is pages of keys and values.

Next-token logits of a prompt from the program's own chunked prefill into
pages (`paged_kv.prefill_chunk_paged`, the function the engine jits), run
on a scratch page pool of one slot. The engine's `prefill_logits` probe
builds a scratch pool as large as the serving one, which a chip filled by
a real cache has no room for; the served path through the real pool is
held to the reference by the served tokens (`serve_cell.reference_check`).

A probe file may import the program (it drives the system under test, as
`serve_cell.py` does) and nothing of the reference. It compiles on its
first call, after the window, never at warm-up.
"""

from __future__ import annotations

from typing import List


def prefill_logits(engine, prompt: List[int]):
    """float32 [vocab]: the logits after `prompt`'s last token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve import paged_kv

    probe = getattr(engine, "_bench_probe", None)
    if probe is None:
        probe = engine._bench_probe = jax.jit(
            lambda p, t, n, o, k, v, ln, bt: paged_kv.prefill_chunk_paged(
                p, t, n, jnp.int32(0), o, k, v, ln, bt, engine.cfg,
                engine.max_len, engine.mesh),
            donate_argnums=(4, 5))
    ps, c = engine.page_size, engine.prefill_chunk
    pages = -(-engine.max_len // ps)
    cache = paged_kv.init_paged_cache(engine.cfg, 1, pages + 1, ps, pages,
                                      mesh=engine.mesh)
    k, v, lengths = cache["k"], cache["v"], cache["lengths"]
    table = jnp.asarray(np.arange(1, pages + 1, dtype=np.int32)[None])
    prompt = np.asarray(prompt, dtype=np.int32)
    for off in range(0, len(prompt), c):
        chunk = prompt[off:off + c]
        padded = np.zeros((1, c), dtype=np.int32)
        padded[0, :len(chunk)] = chunk
        logits, k, v, lengths = probe(
            engine.params, jnp.asarray(padded), jnp.int32(len(chunk)),
            jnp.int32(off), k, v, lengths, table)
    return np.asarray(logits[0], dtype=np.float32)
