"""The probe bench/tests/test_add_cell.py's throwaway architecture names: at
toy size the engine's own `prefill_logits` has room for its scratch pool.
The test copies it to `probes/engine_probe.py` of a temporary checkout."""


def prefill_logits(engine, prompt):
    return engine.prefill_logits(prompt)
