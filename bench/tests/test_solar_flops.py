"""Solar-Open2-250B's arithmetic (bench/solar_flops.py) held to the table of
ISSUE 61 / PERF.md section 4, and the readers of its counters
(bench/readers/ssm.py over a delta-rule pool, bench/readers/moe_share.py
over a held eighth of the experts) on made-up sources."""

import json
import os

import solar_flops
import spec
from readers import moe_share, ssm

FILE = "solar-open2-250b-ep8-serve.json"


def published_dims():
    with open(os.path.join(spec.BENCH, "configs", FILE)) as f:
        doc = json.load(f)
    dims = {field: doc[key] for key, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    dims["layer_pattern"] = tuple(dims["layer_pattern"])
    return doc, dims


def test_the_parts_are_the_issues_table():
    _, m = published_dims()
    assert solar_flops.expert_params(m) == 15_728_640
    assert solar_flops.kda_mixer_params(m) == 137_723_904
    assert solar_flops.attention_params(m) == 109_051_904      # with its gate
    assert solar_flops.expert_layer_params(m) == 646_184_960   # 40 + 1, router
    assert solar_flops.table_params(m) == 100_663_296          # an eighth
    assert solar_flops.state_bytes_per_row(m) == 4_194_304     # 64 x 128 x 128


def test_the_cut_holds_3_308_billion_parameters_6_62_gb():
    doc, m = published_dims()
    assert solar_flops.kinds(m, 4) == (3, 1)
    held = solar_flops.params_held(m, doc["num_hidden_layers"])
    assert held == (3 * 137_723_904 + 109_051_904 + 4 * 646_184_960
                    + 2 * 100_663_296)
    assert round(held / 1e9, 3) == 3.308
    assert round(held * 2 / 1e9, 2) == 6.62                    # bf16
    # The stated arguments: weights, pages, states and convolution rows at
    # the engine's 128 slots x 2,560 positions, within 2% of the compiler's.
    slots, max_len = doc["engine"]["num_slots"], doc["engine"]["max_len"]
    pages = (slots * max_len // 16 + 1) * 16 * 2 * 8 * 128 * 2
    states = 3 * slots * solar_flops.state_bytes_per_row(m)
    rows = 3 * slots * 3 * 3 * 8192 * 2
    total = 2 * held + pages + states + rows
    assert abs(total / doc["compiled"]["decode"]["arguments_bytes"] - 1) < 0.02
    assert total / 16.9e9 > 0.25
    # The whole model by the same rule, on the published sizes: 250 B.
    published = doc["published"]
    whole = dict(m, experts_held=0, vocab_size=published["vocab_size"],
                 layer_pattern=tuple(
                     "full_attention" if i in published["gqa_layers"]
                     else "kda" for i in range(48)))
    assert whole["layer_pattern"][:4] == m["layer_pattern"]
    assert solar_flops.kinds(whole, 48) == (36, 12)
    assert abs(solar_flops.params_held(whole, 48) / 250e9 - 1) < 0.02


def test_a_decode_step_moves_the_state_and_the_experts_it_hit():
    _, m = published_dims()
    one = 3 * 4096 * 1280 * 2
    assert solar_flops.expert_bytes(m, 40, 4) == 4 * 40 * one
    assert solar_flops.expert_bytes(m, 38.4, 4) == 4 * 38.4 * one
    # 128 slots, 3 delta-rule layers, once each way: 3.22 GB, 3.93 ms.
    assert solar_flops.state_update_bytes(dict(m, n_layers=4), 128) == \
        128 * 3 * 2 * 4_194_304
    assert solar_flops.state_update_bytes(m, 128, 4) == 3_221_225_472
    assert solar_flops.grouped_flops(m, 1024, 4) == 4 * 2.0 * 1024 * (one // 2)
    assert solar_flops.train_flops_per_token(m, 4, 1024) == \
        3.0 * solar_flops.forward_flops_per_token(m, 4, 512)
    # One attention layer attends: 4 x context x 64 x 128.
    assert (solar_flops.forward_flops_per_token(m, 4, 100)
            - solar_flops.forward_flops_per_token(m, 4, 0)
            ) == 4.0 * 100 * 8192
    # The chunked form a token: bytes outweigh operations on this chip.
    chunk = dict(m, n_layers=4)
    assert solar_flops.scan_bytes(chunk, 256, 1) == 3 * (
        256 * (5 * 8192 + 64) * 4 + 2 * 4_194_304)
    assert (solar_flops.scan_flops(chunk, 256) / 197e12
            < solar_flops.scan_bytes(chunk, 256, 1) / 819e9)


def sources(before, after, trace=None, platform="tpu"):
    doc, m = published_dims()
    return {
        "stats": {"before": before, "after": after, "window_s": 1.0},
        "trace": trace,
        "model": {"dims": dict(m, n_layers=doc["num_hidden_layers"]),
                  "operations": "solar_flops", "num_slots": 128,
                  "device": {"platform": platform, "kind": "TPU v5 lite",
                             "count": 1}},
    }


def counted(calls, hit, largest, rows=128):
    """`calls` decode calls of `rows` slots: 8 assignments a row in each of
    the 4 expert layers, an eighth of them to the 40 experts held."""
    routed = calls * 4 * rows * 8
    return {"moe": {"assignments": routed, "held_assignments": routed // 8,
                    "calls": calls, "experts_hit_sum": calls * 4 * hit,
                    "max_load_sum": calls * 4 * largest, "experts_held": 40,
                    "num_experts": 320, "expert_layers": 4,
                    "per_expert": [0] * 320},
            "ssm": {"decode_rows_live": calls * rows * 3 // 4,
                    "decode_rows_computed": calls * rows, "calls": calls}}


def test_the_readers_read_a_delta_rule_pool_and_a_held_eighth():
    src = sources(counted(5, 38, 7), counted(105, 38, 7))
    assert moe_share.read(src, {"quantity": "held_assignment_share"}) == 12.5
    assert moe_share.read(src, {"quantity": "held_experts_hit_share"}) == 95.0
    assert ssm.read(src, {"quantity": "live_row_share"}) == 75.0
    update = spec.layer_metric_spec("kda.update_roofline_share")
    scan = spec.layer_metric_spec("kda.scan_roofline_share")
    assert ssm.read(src, update) is None                       # untraced
    decode_ops = ["%kda_update.14 f32[3,128,64,128,128]",
                  "%multiply_reduce_fusion.8 f32[128,64,128]",
                  "%bitcast_add_fusion.2 bf16[128,1,4096]",
                  "%fusion.482 f32[128,64,128]"]
    prefill_ops = ["%fusion.583 f32[3,128,64,128,128]",
                   "%multiply_reduce_fusion.7 f32[4,64,64,64]",
                   "%fusion.555 f32[4,64,64,256]",
                   "%bitcast_add_fusion.5 bf16[1,256,4096]"]
    trace = {"op_s": {decode_ops[0]: 0.5, decode_ops[1]: 0.25,
                      decode_ops[2]: 0.1, decode_ops[3]: 0.3,
                      prefill_ops[0]: 0.01, prefill_ops[1]: 0.02,
                      prefill_ops[2]: 0.01, prefill_ops[3]: 0.2},
             "modules": {
                 "jit__lambda(1)": {"launches": 100, "total_s": 2.0,
                                    "ops": decode_ops},
                 "jit__lambda(2)": {"launches": 10, "total_s": 0.2,
                                    "ops": prefill_ops}}}
    src = sources(counted(5, 38, 7), counted(105, 38, 7), trace)
    # The update: 100 launches x 128 rows x 3 layers once each way over
    # 819 GB/s, over the decode program's one kernel, found by the pool's
    # shape (the gates' fusions, PR 61's sums over the decayed state among
    # them since PR 66, and the prefill's write stay out).
    want = 100.0 * (100 * 3_221_225_472 / 819e9) / 0.5
    assert abs(ssm.read(src, update) - want) < 1e-9 and 75 < want < 82
    # The scan: 10 launches x 256 tokens, bytes-bound, over the prefill
    # program's matched operations, its write into the pool among them.
    m = src["model"]["dims"]
    least = solar_flops.scan_bytes(m, 2560, 10) / 819e9
    assert abs(ssm.read(src, scan) - 100.0 * least / 0.04) < 1e-9
    assert ssm.read(sources(counted(5, 38, 7), counted(105, 38, 7), trace,
                            platform="cpu"), update) is None
    # A parent's program has no such counters: nothing is read, none raised.
    assert ssm.read(sources({}, {}, trace), update) is None
    assert moe_share.read(sources({}, {}, trace), spec.layer_metric_spec(
        "moe.expert_roofline_share.solar")) is None
