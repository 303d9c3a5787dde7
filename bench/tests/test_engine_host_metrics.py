"""The engine loop's host metrics (PR 63) are data files over
`readers/stats.py`: the loop's waits for a lock, the time between two
turns, three phases of the ledger that had no reader, and the two halves of
`engine.drained_share`. Each reads a value from a `stats` pair of the
program as it is; the two over keys this PR added read nothing (None, the
metric is left out of the line) from the parent's shape of `stats`. The
pair is recorded here from a tiny engine on the CPU: it checks paths and
plumbing, and is no device number."""

import copy
import json
import os
import time
from dataclasses import replace

import numpy as np
import pytest

import readers
import spec

# Over keys of `stats()["timing"]` that the parent lacks ...
NEW_KEYS = ("engine.lock_wait_ms_per_turn",
            "engine.between_turns_ms_per_turn")
# ... and over keys the ledger had and nothing read.
OLD_KEYS = ("engine.distribute_ms_per_turn", "engine.upload_ms_per_turn",
            "engine.decode_dispatch_ms_per_turn",
            "engine.drained_fetch_share", "engine.drained_late_share")
ADDED_KEYS = ("lock_wait", "between_turns")


@pytest.fixture(scope="module")
def stats_pair():
    import jax

    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    cfg = replace(configs.tiny, dtype=np.float32)
    eng = ContinuousBatchingEngine(init_params(jax.random.PRNGKey(0), cfg),
                                   cfg, num_slots=2, max_len=64)
    try:
        before, t0 = eng.stats(), time.time()
        for h in [eng.submit([3, 7, 11], max_new_tokens=8),
                  eng.submit([5, 2], max_new_tokens=8)]:
            h.result(timeout=120)
        time.sleep(0.6)  # the loop publishes its last turn and goes idle
        after, t1 = eng.stats(), time.time()
    finally:
        eng.shutdown()
    return {"before": before, "after": after, "window_s": t1 - t0}


def _read(name, pair):
    return readers.read("stats", {"stats": pair},
                        spec.layer_metric_spec(name))


def test_every_new_metric_is_in_the_benchmark_with_the_six_closed_loops():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    closed = [w["name"] for w in bench["workloads"]
              if w["name"].startswith("serve-")
              and w["name"].endswith("-closed")]
    assert len(closed) == 6
    for name in NEW_KEYS + OLD_KEYS:
        m = per_layer[name]
        assert m["layer"] == "engine loop" and m["better"] == "lower"
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == closed
        assert m["unit"] == ("%" if name.endswith("_share") else "ms")
        assert m["source"] == ("program_counter" if name.endswith("_share")
                               else "program_span")


@pytest.mark.parametrize("name", NEW_KEYS + OLD_KEYS)
def test_new_metric_reads_the_program(stats_pair, name):
    how = spec.layer_metric_spec(name)
    assert how["reader"] == "stats" and how["note"]
    value = _read(name, stats_pair)
    assert value is not None and value >= 0.0
    if name.endswith("_share"):
        assert value <= 100.0


@pytest.mark.parametrize("name", NEW_KEYS)
def test_new_metric_reads_nothing_from_the_parents_stats(stats_pair, name):
    parent = copy.deepcopy(stats_pair)
    for side in ("before", "after"):
        timing = parent[side]["timing"]
        for key in ADDED_KEYS:
            del timing[key]
    assert _read(name, parent) is None
    # What the ledger already had reads the same from the parent's shape.
    for old in OLD_KEYS:
        assert _read(old, parent) == _read(old, stats_pair)


def test_the_halves_add_up_to_the_entries_they_part(stats_pair):
    """Fetch-drained and late are `engine.drained_share`; the three
    phases are parts of `engine.host_work_ms_per_turn`."""
    work = _read("engine.host_work_ms_per_turn", stats_pair)
    assert (_read("engine.drained_fetch_share", stats_pair)
            + _read("engine.drained_late_share", stats_pair)
            == pytest.approx(_read("engine.drained_share", stats_pair),
                             rel=1e-9))
    parts = sum(_read(f"engine.{k}_ms_per_turn", stats_pair)
                for k in ("distribute", "upload", "decode_dispatch"))
    assert 0.0 < parts <= work * (1 + 1e-9)
