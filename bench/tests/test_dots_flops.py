"""The latent-attention model's arithmetic for one chip's share
(bench/dots_flops.py) and the readers of its counters (bench/readers/mla.py,
bench/readers/moe_share.py), on made-up sources."""

import json
import os

import pytest

import dots_flops
import spec
from readers import mla, moe_share


def published_dims():
    with open(os.path.join(spec.BENCH, "configs",
                           "dots-vlm1-ep16-serve.json")) as f:
        doc = json.load(f)
    dims = {field: doc[key] for key, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    return doc, dims


def test_the_share_holds_5_5_billion_parameters_11_gb():
    doc, m = published_dims()
    assert dots_flops.attention_params(m) == (
        7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
        + 16384 * 7168)
    assert round(dots_flops.attention_params(m) / 1e6, 1) == 187.1
    assert dots_flops.expert_params(m) == 3 * 7168 * 2048
    assert round(dots_flops.layer_params_held(m, True) / 1e6, 1) == 583.5
    assert round(dots_flops.layer_params_held(m, False) / 1e6, 1) == 937.6
    held = dots_flops.params_held(m, doc["num_hidden_layers"])
    assert round(held / 1e9, 2) == 5.50
    assert round(held * 2 / 1e9, 2) == 11.01  # bf16
    # A token uses half an expert here on a mean (8 x 16 / 256).
    assert dots_flops.layer_params_used(m, False) == (
        dots_flops.attention_params(m) + 7168 * 256
        + 1.5 * dots_flops.expert_params(m))


def test_a_cached_row_is_1152_bytes_and_278528_operations_a_layer():
    _, m = published_dims()
    assert dots_flops.latent_row_bytes(m) == 1152
    assert dots_flops.latent_attention_flops(m, 1) == 278_528
    assert dots_flops.latent_attention_bytes(m, 1000, 6) == 6 * 1000 * 1152
    # At the v5e's ridge: 242 operations a byte against 197e12 / 819e9.
    assert round(278_528 / 1152) == 242
    assert dots_flops.expert_bytes(m, 16, 5) == 5 * 16 * 3 * 7168 * 2048 * 2
    assert dots_flops.train_flops_per_token(m, 6, 1024) == \
        3.0 * dots_flops.forward_flops_per_token(m, 6, 512)


def sources(before, after, trace=None, platform="tpu", steps=(0, 10)):
    _, m = published_dims()
    return {
        "stats": {"before": dict(before, steps=steps[0]),
                  "after": dict(after, steps=steps[1]), "window_s": 1.0},
        "trace": trace,
        "model": {"dims": m, "operations": "dots_flops", "num_slots": 64,
                  "device": {"platform": platform, "kind": "TPU v5 lite",
                             "count": 1}},
    }


def moe(calls, hit, largest, rows):
    """`calls` calls of `rows` rows: 8 assignments a row in 5 expert
    layers, a sixteenth of them to the 16 held."""
    routed = calls * 5 * rows * 8
    return {"moe": {"assignments": routed, "held_assignments": routed // 16,
                    "calls": calls, "experts_hit_sum": calls * 5 * hit,
                    "max_load_sum": calls * 5 * largest, "experts_held": 16,
                    "num_experts": 256, "expert_layers": 5,
                    "per_expert": [0] * 256}}


def test_the_shares_reader_on_made_up_counters_and_trace():
    before, after = moe(5, 16, 9, 64), moe(105, 12, 6, 64)
    src = sources(before, after)
    hit = (105 * 12 - 5 * 16) / 100
    assert moe_share.read(src, {"quantity": "held_assignment_share"}) == \
        pytest.approx(6.25)
    assert moe_share.read(src, {"quantity": "held_experts_hit_share"}) == \
        pytest.approx(100.0 * hit / 16)
    load = (105 * 6 - 5 * 9) / 100
    assert moe_share.read(src, {"quantity": "held_load_max_over_mean"}) == \
        pytest.approx(load / (64 * 8 / 16 / 16))
    trace = {
        "op_s": {"%gmm.13 f32[512,2048]": 0.06, "%gmm.15 f32[512,7168]": 0.03,
                 "%fusion.2 f32[64,256]": 0.5},
        "modules": {
            "jit__lambda(1)": {"launches": 8, "ops": ["%gmm.13 f32[512,2048]"]},
            "jit__lambda(2)": {"launches": 2, "ops": ["%gmm.15 f32[512,7168]"]},
            "jit__pick(3)": {"launches": 50, "ops": ["%sort.1 f32[1,16160]"]}},
    }
    spec_ = {"quantity": "expert_roofline_share", "match": r"^%gmm[.\d]* f32\["}
    want = 100.0 * (5 * hit * 3 * 7168 * 2048 * 2 * 10) / 819e9 / 0.09
    assert moe_share.read(sources(before, after, trace), spec_) == \
        pytest.approx(want)
    assert 0 < want < 100


def test_the_shares_reader_finds_nothing_to_read_and_says_so():
    hit = {"quantity": "held_experts_hit_share"}
    # A whole model's counters (OLMoE's, or a parent's program): no share.
    whole = {"moe": {k: v for k, v in moe(9, 16, 5, 64)["moe"].items()
                     if k in ("assignments", "calls", "experts_hit_sum",
                              "max_load_sum", "per_expert")}}
    later = {"moe": dict(whole["moe"], calls=19)}
    assert moe_share.read(sources(whole, later), hit) is None
    assert moe_share.read(sources({}, {}), hit) is None
    assert moe_share.read({"model": sources({}, {})["model"]}, hit) is None
    same = moe(9, 16, 5, 64)
    assert moe_share.read(sources(same, same), hit) is None  # no call
    spec_ = {"quantity": "expert_roofline_share", "match": "gmm"}
    assert moe_share.read(sources(same, moe(19, 16, 5, 64)), spec_) is None
    assert moe_share.read(
        sources(same, moe(19, 16, 5, 64),
                {"op_s": {"%gmm.1 f32[8,8]": 1.0}, "modules": {}}, "cpu"),
        spec_) is None
    with pytest.raises(ValueError):
        moe_share.read(sources(same, moe(19, 16, 5, 64)), {"quantity": "no"})


def attention(live, read):
    return {"attention": {"decode_rows_live": live, "decode_rows_read": read,
                          "decode_rows_held": 0}}


def test_the_latent_attention_reader_on_a_made_up_trace():
    decode, prefill = r"bf16\[64,1,7168\]", r"bf16\[2,256,7168\]"
    trace = {
        "op_s": {"%fusion.7 f32[64,128,1,512]": 0.03,
                 "%gather.2 bf16[64,32,16,640]": 0.01,
                 "%fusion.9 bf16[64,1,7168]": 0.2,
                 "%fusion.7 f32[2,128,256,512]": 0.05},
        "modules": {
            "jit__lambda(1)": {"launches": 20, "total_s": 0.4, "ops": [
                "%fusion.7 f32[64,128,1,512]", "%gather.2 bf16[64,32,16,640]",
                "%fusion.9 bf16[64,1,7168]"]},
            "jit__lambda(2)": {"launches": 5, "total_s": 0.2, "ops": [
                "%fusion.7 f32[2,128,256,512]", "%x.1 bf16[2,256,7168]"]}},
    }
    match = r"(f32\[64,128,1,512\]|bf16\[64,32,16,640\])$"
    src = sources(attention(1000, 4000), attention(1000 + 50 * 60000, 4000),
                  trace, steps=(0, 50))
    share = {"quantity": "attn_time_share", "contains_op": decode,
             "match": match}
    assert mla.read(src, share) == pytest.approx(100.0 * 0.04 / 0.4)
    assert mla.read(src, dict(share, contains_op=prefill,
                              match=r"f32\[2,128,256,512\]$")) == \
        pytest.approx(100.0 * 0.05 / 0.2)
    # 60,000 live rows a step in 6 layers, 20 traced launches, bound by
    # operations (242 a byte against the chip's 240.5): over 0.04 s.
    roof = dict(share, quantity="decode_attn_roofline_share")
    least = max(6 * 60000 * 278_528 / 197e12, 6 * 60000 * 1152 / 819e9) * 20
    assert mla.read(src, roof) == pytest.approx(100.0 * least / 0.04)
    assert 0 < 100.0 * least / 0.04 < 100
    # Nothing to read: no trace, a CPU's, no matching program, no counter.
    assert mla.read(sources({}, {}), share) is None
    assert mla.read(sources({}, {}, trace, "cpu"), share) is None
    assert mla.read(src, dict(share, contains_op="bf16\\[9,9\\]")) is None
    assert mla.read(sources({}, {}, trace, steps=(0, 50)), roof) is None
    with pytest.raises(ValueError):
        mla.read(src, dict(share, quantity="no"))
