"""The window model's arithmetic (bench/smallthinker_flops.py), held to the
numbers ISSUE 70 worked out from the published config (21.51 B held, 3.33 B
used by a token; the cut's 3.967 B and what its cache takes with and without
a ring), and the readers the cell's own metrics go through (`window`, `moe`,
`stats`) on made-up sources and on the cell's data files."""

import json
import os

import pytest

import readers
import smallthinker_flops as ops
import spec

CELL = "serve-swa-longdoc"
FILE = "smallthinker-21b-a3b-serve.json"


def published_dims():
    with open(os.path.join(spec.BENCH, "configs", FILE)) as f:
        doc = json.load(f)
    dims = {field: doc[key] for key, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    return doc, dims


def test_the_parts_add_up_to_the_published_21_5_billion():
    doc, m = published_dims()
    assert ops.attention_params(m) == 2560 * 3584 * 2 + 2560 * 512 * 2 \
        == 20_971_520
    assert ops.router_params(m) == 163_840
    assert ops.expert_params(m) == 3 * 2560 * 768 == 5_898_240
    assert m["num_experts"] * ops.expert_params(m) == 377_487_360
    assert ops.layer_params_held(m) == 398_622_720
    assert ops.table_params(m) == 388_956_160
    whole = ops.params_held(m, doc["published"]["num_hidden_layers"])
    assert whole == 52 * 398_622_720 + 777_912_320
    assert round(whole / 1e9, 2) == 21.51
    assert round(ops.params_used_per_token(m, 52) / 1e9, 2) == 3.33
    # The stated intermediate_size is used by nothing here.
    assert m["d_ff"] == 768 and "intermediate_size" in doc["assumed"]
    assert ops.params_held(dict(m, d_ff=1), 52) == whole


def test_the_cut_is_eight_layers_and_7_93_gb():
    doc, m = published_dims()
    layers = doc["num_hidden_layers"]
    assert layers == 8 and doc["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout"]
    assert doc["sliding_window_layout"] == doc["rope_layout"] == [0, 1, 1, 1] * 2
    held = ops.params_held(m, layers)
    assert held == 8 * 398_622_720 + 777_912_320 == 3_966_894_080
    assert round(held * 2 / 1e9, 3) == 7.934
    assert round(ops.params_held(m, 12) * 2 / 1e9, 1) == 11.1
    engine = doc["engine"]
    cache = ops.cache_bytes(m, layers, engine["num_slots"], engine["max_len"],
                            4096 + engine["prefill_chunk"])
    assert cache["full"] == 2 * 32 * 16384 * 2048
    assert cache["ring"] == 6 * 32 * 4608 * 2048
    assert [round(cache[k] / 1e9, 3) for k in (
        "full", "ring", "one_table_for_every_layer")] == [2.147, 1.812, 8.59]
    assert round((held * 2 + cache["full"] + cache["ring"]) / 1e9, 2) == 11.89
    # What the compiler counts as arguments: the weights (the norms' scales
    # beside the matrices), both pools with their NULL pages, and under
    # 0.3 MB of tables, lengths and counters.
    norms = 8 * 2 * 2560 + 2560
    pools = 2 * 16 * 512 * 2 * (2 * (32 * 1024 + 1) + 6 * (32 * 288 + 1))
    stated = doc["compiled"]["decode"]["arguments_bytes"]
    assert abs(stated - ((held + norms) * 2 + pools)) < 300_000


def test_operations_follow_the_used_parameters_and_the_window():
    _, m = published_dims()
    assert ops.window_share(m) == 0.75
    assert ops.keys_seen(m, 1000) == 1000
    assert ops.keys_seen(m, 8192) == 0.25 * 8192 + 0.75 * 4096
    matrix = 2.0 * ops.params_used_per_token(m, 8)
    assert ops.forward_flops_per_token(m, 8, 8192) == \
        matrix + 8 * 4.0 * 5120 * 28 * 128
    assert ops.train_flops_per_token(m, 8, 1024) == \
        3.0 * ops.forward_flops_per_token(m, 8, 512.0)


def test_a_decode_step_moves_under_ten_gigabytes():
    _, m = published_dims()
    assert ops.kv_row_bytes(m) == 2048
    assert ops.expert_bytes(m, 1) == 11_796_480
    assert round(ops.expert_bytes(m, 64, 8) / 1e9, 2) == 6.04
    assert ops.window_attention_bytes(m, 6 * 32 * 4096) == 6 * 32 * 4096 * 2048
    assert ops.window_attention_flops(m, 10) == 4.0 * 10 * 28 * 128
    parts = ops.step_bytes(m, 8, 32, 7000, 64)
    assert round(parts["attention_weights"] / 1e9, 2) == 0.34
    # The head is 0.389 B parameters, 0.78 GB (the issue's 0.39 "of the
    # head" counted parameters for bytes), so a step is 9.7 GB, not 9.2.
    assert round(parts["head"] / 1e9, 2) == 0.78
    assert parts["rows_read"] == 8 * 32 * (0.25 * 7000 + 0.75 * 4096) * 2048
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    assert 9.5e9 < parts["total"] < 9.9e9   # 11.6 to 12.1 ms at 819 GB/s


def sources(before, after, trace=None):
    _, m = published_dims()
    return {
        "stats": {"before": before, "after": after, "window_s": 1.0},
        "trace": trace,
        "model": {"dims": m, "operations": "smallthinker_flops",
                  "num_slots": 32,
                  "device": {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}},
    }


def stats(steps, rows=7000, hit=60):
    """`steps` decode steps of 32 slots at `rows` positions each."""
    seen = min(rows, 4096)
    return {
        "steps": steps,
        "moe": {"assignments": steps * 8 * 32 * 6, "calls": steps,
                "experts_hit_sum": steps * 8 * hit,
                "max_load_sum": steps * 8 * 9},
        "attention": {
            "decode_rows_read": steps * 32 * (2 * rows + 6 * seen),
            "decode_rows_held": steps * 32 * (2 * 16384 + 6 * 4608),
            "decode_rows_live": steps * 32 * (2 * rows + 6 * seen),
            "window_rows_read": steps * 32 * 6 * seen,
            "window_rows_unwindowed": steps * 32 * 6 * rows},
    }


def test_the_window_counters_read_through_the_stats_reader():
    how = spec.layer_metric_spec("attn.window_rows_read_share")
    assert how["reader"] == "stats" and how["note"]
    src = sources(stats(10), stats(110))
    assert readers.read("stats", src, how) == pytest.approx(
        100 * 4096 / 7000)
    short = sources(stats(10, rows=900), stats(110, rows=900))
    assert readers.read("stats", short, how) == pytest.approx(100.0)
    # A program without window layers has no such counters: nothing to read.
    bare = sources({"steps": 0, "attention": {"decode_rows_read": 0}},
                   {"steps": 300, "attention": {"decode_rows_read": 9}})
    assert readers.read("stats", bare, how) is None


TRACE = {
    "op_s": {"%window_decode_attention.36 f32[32,4,8,128]": 0.20,
             "%window_decode_attention.37 f32[32,4,8,128]": 0.21,
             "%window_decode_attention.38 f32[32,4,8,128]": 0.19,
             "%paged_decode_attention.12 f32[32,4,8,128]": 0.3,
             "%gmm.1 f32[256,768]": 0.8, "%gmm.2 f32[256,2560]": 0.8,
             "%gmm.3 f32[3072,768]": 0.4},
    "modules": {
        "jit__lambda(1)": {"launches": 180, "ops": [
            "%window_decode_attention.36 f32[32,4,8,128]",
            "%gmm.1 f32[256,768]"]},
        "jit__lambda(2)": {"launches": 20, "ops": ["%gmm.3 f32[3072,768]"]},
        "jit__pick(3)": {"launches": 20, "ops": ["%select f32[32]"]}},
}


def test_the_ring_kernels_roofline_reads_through_the_window_reader():
    how = spec.layer_metric_spec("attn.window_decode_roofline_share")
    assert how["reader"] == "window" and how["bytes"] == \
        "window_attention_bytes"
    src = sources(stats(10), stats(110), TRACE)
    want = 100.0 * (32 * 6 * 4096 * 2048 * 180) / 819e9 / 0.60
    assert readers.read("window", src, how) == pytest.approx(want)
    assert 0 < want < 100
    # The parent's program, and a model without a window layer: no counter,
    # nothing read, nothing raised; nor off the chip, nor without the kernel.
    bare = sources({"steps": 0, "attention": {}}, {"steps": 9,
                                                   "attention": {}}, TRACE)
    assert readers.read("window", bare, how) is None
    assert readers.read("window", sources(stats(10), stats(110)), how) is None
    other = dict(TRACE, op_s={"%gmm.1 f32[256,768]": 0.8})
    assert readers.read("window", sources(stats(10), stats(110), other),
                        how) is None
    cpu = sources(stats(10), stats(110), TRACE)
    cpu["model"]["device"] = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert readers.read("window", cpu, how) is None


def test_the_expert_roofline_reads_through_the_moe_reader():
    how = spec.layer_metric_spec("moe.expert_roofline_share.swa")
    assert how["reader"] == "moe"
    src = sources(stats(10), stats(110, hit=59.5), TRACE)
    hit = (110 * 59.5 - 10 * 60) / 100
    want = 100.0 * (8 * hit * 11_796_480 * 200) / 819e9 / 2.0
    assert readers.read("moe", src, how) == pytest.approx(want)
    assert 0 < want < 100


def test_the_two_decode_kernels_are_told_apart_by_name():
    window = spec.layer_metric_spec("attn.window_decode_time_share")["match"]
    full = spec.layer_metric_spec("attn.full_decode_time_share.swa")["match"]
    import re

    names = list(TRACE["op_s"])
    assert [n for n in names if re.search(window, n)] == names[:3]
    assert [n for n in names if re.search(full, n)] == names[3:4]


def test_the_cell_is_on_the_lists_it_can_read():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    own = {m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]}
    assert len(own) == 11 and own <= listed
    assert {"moe.experts_hit_share", "moe.load_max_over_mean",
            "attn.rows_read_share"} <= listed
    assert all(m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"]
               if m["name"] in listed)
    assert len(bench["per_layer"]) <= 128
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
