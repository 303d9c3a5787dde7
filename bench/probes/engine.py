"""The probe of an engine whose cache is more than pages of keys and
values (a recurrent state beside them): the engine's own.

`engine.prefill_logits` runs the engine's jitted prefill over a scratch
cache of ONE slot made of whatever the engine's cache is made of, so this
file needs to know nothing of it. It compiles the one-slot shapes on its
first call, after the window, and keeps them on the engine.
"""


def prefill_logits(engine, prompt):
    """float32 [vocab]: the logits after `prompt`'s last token."""
    return engine.prefill_logits(prompt)
