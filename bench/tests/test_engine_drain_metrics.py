"""The engine loop's drain, late-dispatch and stall metrics (PR 42) are
data files over `readers/stats.py`: each reads a value from a `stats`
pair of the program as it is, and nothing (None, the metric is left out
of the line) from the parent's shape of `stats`, which lacks the
counters. The pair is recorded here from a tiny engine on the CPU: it
checks paths and plumbing, and is no device number."""

import copy
import json
import os
import time
from dataclasses import replace

import numpy as np
import pytest

import readers
import spec

NEW = ("engine.drained_share", "engine.drained_share.steady",
       "engine.fetch_drain_ms", "engine.fetch_drain_ms.steady",
       "engine.late_dispatch_share",
       "engine.pass_drain_ms", "engine.pass_drain_ms.steady",
       "engine.stall_ms_per_s", "engine.stall_ms_per_s.steady")
ADDED_KEYS = ("drained", "pass_drain", "dispatches", "late_dispatches",
              "stalls")


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


def test_every_new_metric_is_in_the_benchmark_with_its_cells():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # One entry for every closed loop since PR 60, where the latent
    # model's and the conv hybrid's cells had `.mla` and `.lfm2` copies or,
    # at the cap of 128 entries, none. The closed loops are the cells that
    # report `serve_tokens_per_s` (PR 66): the next one appends its name to
    # these lists and edits no test.
    closed = next(m["workloads"] for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert len(closed) >= 6 and "serve-kda-reason-closed" in closed
    for name in NEW:
        m = per_layer[name]
        assert m["layer"] == "engine loop" and m["better"] == "lower"
        steady = name.endswith(".steady")
        assert m["workloads"] == (["serve-chat-steady"] if steady else closed)
        assert (m["moves"] == "serve_tokens_per_s") != steady


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_the_program_and_not_the_parent(stats_pair, name):
    how = spec.layer_metric_spec(name)
    assert how["reader"] == "stats" and how["note"]
    value = readers.read("stats", {"stats": stats_pair}, how)
    assert value is not None and value >= 0.0
    if "share" in name:
        assert value <= 100.0
    if "stall" in name:
        assert value == 0.0
    parent = copy.deepcopy(stats_pair)
    for side in ("before", "after"):
        for key in ADDED_KEYS:
            del parent[side]["timing"][key]
    assert readers.read("stats", {"stats": parent}, how) is None
