"""LFM2-24B-A2B's arithmetic (bench/lfm2_flops.py) held to the table of
ISSUE 56 / PERF.md section 4, and the readers of its counters
(bench/readers/moe_share.py for a model that holds every expert,
bench/readers/ssm.py over the conv rows) on made-up sources."""

import json
import os

import lfm2_flops
import spec
from readers import moe_share, ssm


def published_dims():
    with open(os.path.join(spec.BENCH, "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        doc = json.load(f)
    dims = {field: doc[key] for key, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    dims["layer_pattern"] = tuple(dims["layer_pattern"])
    return doc, dims


def test_the_parts_are_the_issues_table():
    _, m = published_dims()
    assert lfm2_flops.table_params(m) == 134_217_728
    assert lfm2_flops.conv_mixer_params(m) == 16_783_360
    assert lfm2_flops.attention_params(m) == 10_485_760
    assert lfm2_flops.dense_mlp_params(m) == 72_351_744
    assert lfm2_flops.expert_params(m) == 9_437_184
    assert lfm2_flops.expert_layer_params(m) == 604_110_848


def test_the_cut_holds_5_401_billion_parameters_10_80_gb():
    doc, m = published_dims()
    assert lfm2_flops.kinds(m, 10) == (8, 2, 2, 8)
    held = lfm2_flops.params_held(m, doc["num_hidden_layers"])
    assert held == (8 * 16_783_360 + 2 * 10_485_760 + 2 * 72_351_744
                    + 8 * 604_110_848 + 2 * 134_217_728)
    assert round(held / 1e9, 3) == 5.401
    assert round(held * 2 / 1e9, 2) == 10.80               # bf16
    # With the table tied (the family's smaller models): 5.267 B, 10.53 GB.
    assert round((held - 134_217_728) / 1e9, 3) == 5.267
    # The whole model by the same rule, on the published 40 layers.
    whole = dict(m, layer_pattern=tuple(doc["published"]["layer_types"]))
    assert lfm2_flops.kinds(whole, 40) == (30, 10, 2, 38)
    assert round(lfm2_flops.params_held(whole, 40) / 1e9, 2) == 23.98
    assert round((lfm2_flops.params_held(whole, 40) - 134_217_728) / 1e9,
                 2) == 23.84                               # tied
    assert round(lfm2_flops.params_used_per_token(whole, 40) / 1e9, 1) == 2.3


def test_a_decode_step_reads_the_experts_it_hit_and_no_dense_layer():
    _, m = published_dims()
    one = 3 * 2048 * 1536 * 2
    assert lfm2_flops.expert_bytes(m, 64, 8) == 8 * 64 * one
    assert lfm2_flops.expert_bytes(m, 63.8, 8) == 8 * 63.8 * one
    # 92% of the 10.5 GB of weights a step reads (the embedding table is
    # looked up, not read), 11.8 ms at 819 GB/s.
    assert round(lfm2_flops.expert_bytes(m, 64, 8) / (
        2 * (lfm2_flops.params_held(m, 10) - 134_217_728)), 2) == 0.92
    assert lfm2_flops.grouped_flops(m, 384, 8) == 8 * 2.0 * 384 * (one // 2)
    assert lfm2_flops.train_flops_per_token(m, 10, 1024) == \
        3.0 * lfm2_flops.forward_flops_per_token(m, 10, 512)
    # Two attention layers attend: 4 x context x 32 x 64 each.
    assert (lfm2_flops.forward_flops_per_token(m, 10, 100)
            - lfm2_flops.forward_flops_per_token(m, 10, 0)
            ) == 2 * 4.0 * 100 * 2048


def test_the_conv_mixers_operations_and_bytes():
    _, m = published_dims()
    d = 2048
    assert lfm2_flops.conv_mixer_flops(m, 1) == (
        2.0 * 4 * d * d + 2.0 * 3 * d + 2 * d)
    assert lfm2_flops.conv_mixer_flops(m, 96, 8) == \
        8 * 96 * lfm2_flops.conv_mixer_flops(m, 1)
    # The weights once, and a sequence's two carried rows read and written.
    assert lfm2_flops.conv_mixer_bytes(m, 96, 8) == 8 * 2 * (
        16_783_360 + 2 * 96 * 2 * d)
    # The conv pool of 96 slots: 6.3 MB.
    assert 8 * 96 * 2 * d * 2 == 6_291_456


def sources(before, after, trace=None, platform="tpu"):
    _, m = published_dims()
    return {
        "stats": {"before": before, "after": after, "window_s": 1.0},
        "trace": trace,
        "model": {"dims": m, "operations": "lfm2_flops", "num_slots": 96,
                  "device": {"platform": platform, "kind": "TPU v5 lite",
                             "count": 1}},
    }


def counted(calls, hit, largest, rows=96):
    """`calls` decode calls of `rows` slots: 4 assignments a row in each of
    the 8 expert layers, every expert held here."""
    routed = calls * 8 * rows * 4
    return {"moe": {"assignments": routed, "held_assignments": routed,
                    "calls": calls, "experts_hit_sum": calls * 8 * hit,
                    "max_load_sum": calls * 8 * largest, "experts_held": 64,
                    "num_experts": 64, "expert_layers": 8,
                    "per_expert": [0] * 64},
            "ssm": {"decode_rows_live": calls * rows * 9 // 10,
                    "decode_rows_computed": calls * rows, "calls": calls}}


def test_the_readers_count_over_the_eight_expert_layers():
    """`moe_share` reads the count of expert layers from stats()["moe"]
    (`moe.py` would divide by all ten layers), so the hit share of a model
    that holds every expert is over its 64 experts and 8 layers."""
    src = sources(counted(5, 64, 9), counted(105, 63, 12))
    hit = (105 * 63 - 5 * 64) / 100
    assert moe_share.read(src, {"quantity": "held_experts_hit_share"}) == \
        100.0 * hit / 64
    load = moe_share.read(src, {"quantity": "held_load_max_over_mean"})
    assert abs(load - (105 * 12 - 5 * 9) / 100 * 64 / 384) < 1e-12
    assert ssm.read(src, {"quantity": "live_row_share"}) == 90.0
    # The roofline share: bytes of the experts hit x 8 x launches over
    # 819 GB/s, over the gmm seconds; nothing off the chip or untraced.
    spec_ = {"quantity": "expert_roofline_share",
             "match": "^%gmm[.\\d]* f32\\["}
    assert moe_share.read(src, spec_) is None
    trace = {"op_s": {"%gmm.3 f32[384,1536]": 0.6, "%gmm.4 f32[384,2048]": 0.4,
                      "%fusion.1 bf16[96,1,2048]": 0.5},
             "modules": {"jit__lambda(1)": {
                 "launches": 100, "total_s": 1.6,
                 "ops": ["%gmm.3 f32[384,1536]", "%gmm.4 f32[384,2048]"]}}}
    src = sources(counted(5, 64, 9), counted(105, 63, 12), trace)
    want = 100.0 * lfm2_flops.expert_bytes(src["model"]["dims"], hit, 8) \
        * 100 / 819e9 / 1.0
    got = moe_share.read(src, spec_)
    assert abs(got - want) < 1e-9 and 100 < got < 120   # made-up seconds
    assert moe_share.read(sources(counted(5, 64, 9), counted(105, 63, 12),
                                  trace, platform="cpu"), spec_) is None
    # A parent's program, or a dense model's, has no such counters.
    assert moe_share.read(sources({}, {}), spec_) is None
    assert ssm.read(sources({}, {}), {"quantity": "live_row_share"}) is None
