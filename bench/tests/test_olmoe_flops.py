"""The sparse model's arithmetic (bench/olmoe_flops.py) and the reader of
its routing counters (bench/readers/moe.py), on made-up sources."""

import json
import os

import pytest

import olmoe_flops
import spec
from readers import moe as reader


def published_dims():
    with open(os.path.join(spec.BENCH, "configs",
                           "olmoe-1b-7b-serve.json")) as f:
        doc = json.load(f)
    dims = {field: doc[key] for key, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    return doc, dims


def test_a_token_uses_1_28_billion_parameters_both_tables_counted():
    doc, m = published_dims()
    layers = doc["num_hidden_layers"]
    assert olmoe_flops.attention_params(m) == 4 * 2048 * 2048
    assert olmoe_flops.expert_params(m) == 3 * 2048 * 1024
    used = olmoe_flops.params_used_per_token(m, layers)
    assert used == 16 * (4 * 2048 * 2048 + 2048 * 64
                         + 8 * 3 * 2048 * 1024) + 2 * 50304 * 2048
    assert round(used / 1e9, 2) == 1.28
    held = olmoe_flops.params_held(m, layers)
    assert round(held / 1e9, 2) == 6.92  # 13.84 GB in bf16
    experts = layers * m["num_experts"] * olmoe_flops.expert_params(m)
    assert round(experts / 1e9, 2) == 6.44


def test_operations_follow_the_used_parameters():
    _, m = published_dims()
    matrix = 2.0 * (16 * olmoe_flops.layer_params_used(m)
                    + olmoe_flops.table_params(m))
    attention = 16 * 4.0 * 512 * 16 * 128
    assert olmoe_flops.forward_flops_per_token(m, 16, 512) == \
        matrix + attention
    assert olmoe_flops.train_flops_per_token(m, 16, 1024) == \
        3.0 * (matrix + attention)


def test_expert_bytes_are_the_hit_experts_three_matrices():
    _, m = published_dims()
    assert olmoe_flops.expert_bytes(m, 1) == 12_582_912
    assert olmoe_flops.expert_bytes(m, 64, 16) == 16 * 64 * 12_582_912
    # A decode step of 16 slots hits about 56 of 64: 11.3 GB.
    assert round(olmoe_flops.expert_bytes(m, 56, 16) / 1e9, 1) == 11.3
    assert olmoe_flops.grouped_flops(m, 128) == 2.0 * 128 * 3 * 2048 * 1024


def sources(before, after, trace=None, platform="tpu"):
    _, m = published_dims()
    return {
        "stats": {"before": {"moe": before, "steps": 0},
                  "after": {"moe": after, "steps": 10}, "window_s": 1.0},
        "trace": trace,
        "model": {"dims": m, "operations": "olmoe_flops", "num_slots": 16,
                  "device": {"platform": platform, "kind": "TPU v5 lite",
                             "count": 1}},
    }


def counters(calls, hit_per_layer, max_per_layer, rows):
    return {"assignments": calls * 16 * rows * 8, "calls": calls,
            "experts_hit_sum": calls * 16 * hit_per_layer,
            "max_load_sum": calls * 16 * max_per_layer,
            "per_expert": [0] * 64}


def test_reader_on_made_up_counters_and_trace():
    before, after = counters(5, 64, 9, 16), counters(105, 56, 6, 16)
    # The window's 100 calls alone: (105 * 56 - 5 * 64) / 100 experts hit.
    hit = (105 * 56 - 5 * 64) / 100
    src = sources(before, after)
    assert reader.read(src, {"quantity": "experts_hit_share"}) == \
        pytest.approx(100.0 * hit / 64)
    load = (105 * 6 - 5 * 9) / 100  # the largest expert's mean rows
    assert reader.read(src, {"quantity": "load_max_over_mean"}) == \
        pytest.approx(load / (16 * 8 / 64))
    # Ten traced launches whose grouped products took 0.2 s: the bytes of
    # `hit` experts in 16 layers, ten times, at 819 GB/s, over 0.2 s.
    trace = {
        "op_s": {"%gmm.13 f32[128,1024]": 0.07, "%gmm.14 f32[128,1024]": 0.07,
                 "%gmm.15 f32[128,2048]": 0.06, "%fusion.2 f32[16,64]": 0.5},
        "modules": {
            "jit__lambda(1)": {"launches": 7, "ops": ["%gmm.13 f32[128,1024]"]},
            "jit__lambda(2)": {"launches": 3, "ops": ["%gmm.15 f32[512,2048]"]},
            "jit__pick(3)": {"launches": 50, "ops": ["%sort.1 f32[1,50304]"]}},
    }
    spec_ = {"quantity": "expert_roofline_share", "match": r"^%gmm[.\d]* f32\["}
    want = 100.0 * (16 * hit * 12_582_912 * 10) / 819e9 / 0.2
    assert reader.read(sources(before, after, trace), spec_) == \
        pytest.approx(want)
    assert 0 < want < 100


def test_reader_finds_nothing_to_read_and_says_so():
    moe = counters(10, 60, 5, 16)
    spec_ = {"quantity": "expert_roofline_share", "match": "gmm"}
    hit = {"quantity": "experts_hit_share"}
    # A dense model, or the parent's program: stats() has no "moe".
    dense = {"stats": {"before": {"steps": 0}, "after": {"steps": 9},
                       "window_s": 1.0}, "model": sources(moe, moe)["model"]}
    assert reader.read(dense, hit) is None
    assert reader.read(dense, spec_) is None
    assert reader.read({"model": dense["model"]}, hit) is None
    # No call in the window, no trace, a CPU rehearsal's trace.
    assert reader.read(sources(moe, moe), hit) is None
    later = counters(20, 60, 5, 16)
    assert reader.read(sources(moe, later), spec_) is None
    empty = {"op_s": {}, "modules": {}}
    assert reader.read(sources(moe, later, empty), spec_) is None
    assert reader.read(sources(moe, later, {"op_s": {"%gmm.1 f32[8,8]": 1.0},
                                            "modules": {}}, "cpu"),
                       spec_) is None
    with pytest.raises(ValueError):
        reader.read(sources(moe, later), {"quantity": "nope"})
