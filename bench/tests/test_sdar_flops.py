"""The block-diffusion sparse model's arithmetic (bench/sdar_flops.py), held
to the numbers ISSUE 68 worked out from the published config, and the two
readers the cell's own metrics go through (`moe`, `stats`) on made-up
sources and on the cell's data files."""

import json
import os

import pytest

import readers
import sdar_flops
import spec

CELL = "serve-sdar-blockgen"


def published_dims():
    with open(os.path.join(spec.BENCH, "configs",
                           "sdar-30b-a3b-serve.json")) as f:
        doc = json.load(f)
    dims = {field: doc[key] for key, field in spec._published(doc).items()
            if field != "tie_embeddings"}
    return doc, dims


def test_the_parts_add_up_to_the_published_30_5_billion():
    doc, m = published_dims()
    assert sdar_flops.attention_params(m) == 18_874_368
    assert sdar_flops.router_params(m) == 262_144
    assert sdar_flops.expert_params(m) == 3 * 2048 * 768 == 4_718_592
    assert m["num_experts"] * sdar_flops.expert_params(m) == 603_979_776
    assert sdar_flops.layer_params_held(m) == 623_116_288
    assert sdar_flops.table_params(m) == 311_164_928
    whole = sdar_flops.params_held(m, doc["published"]["num_hidden_layers"])
    assert round(whole / 1e9, 2) == 30.53
    used = sdar_flops.params_used_per_token(m, 48)
    assert round(used / 1e9, 2) == 3.35           # both tables counted
    assert round((used - sdar_flops.table_params(m)) / 1e9, 2) == 3.04
    # The published intermediate_size is stated and used by nothing here.
    assert m["d_ff"] == 6144
    assert sdar_flops.params_held(dict(m, d_ff=1), 48) == whole


def test_the_cut_is_six_layers_and_8_72_gb():
    doc, m = published_dims()
    layers = doc["num_hidden_layers"]
    assert layers == 6 and doc["reduced"] == ["num_hidden_layers"]
    held = sdar_flops.params_held(m, layers)
    assert held == 6 * 623_116_288 + 2 * 311_164_928 == 4_361_027_584
    assert round(held * 2 / 1e9, 3) == 8.722
    assert round(sdar_flops.params_held(m, 7) * 2 / 1e9, 2) == 9.97
    # What the compiler counts as arguments: the weights (the norms'
    # scales beside the matrices), the pages of 96 slots x 2,560, and under
    # 0.2 MB of tables, lengths and counters.
    norms = 6 * (2 * 2048 + 2 * 128) + 2048
    pages = 2 * 6 * (96 * 160 + 1) * 16 * 512 * 2
    assert round(pages / 1e9, 3) == 3.020
    stated = doc["compiled"]["block_pass"]["arguments_bytes"]
    assert abs(stated - ((held + norms) * 2 + pages)) < 200_000


def test_operations_follow_the_used_parameters():
    _, m = published_dims()
    matrix = 2.0 * (6 * sdar_flops.layer_params_used(m)
                    + sdar_flops.table_params(m))
    assert sdar_flops.forward_flops_per_token(m, 6, 512) == \
        matrix + 6 * 4.0 * 512 * 32 * 128
    assert sdar_flops.train_flops_per_token(m, 6, 1024) == \
        3.0 * sdar_flops.forward_flops_per_token(m, 6, 514.0)


def test_a_pass_moves_about_nine_gigabytes():
    _, m = published_dims()
    assert sdar_flops.expert_bytes(m, 1) == 9_437_184
    assert round(sdar_flops.expert_bytes(m, 128, 6) / 1e9, 2) == 7.25
    parts = sdar_flops.pass_bytes(m, 6, 96, 600)
    assert parts["layers"] == 6 * 623_116_288 * 2
    assert parts["pages_read"] == 6 * 96 * 600 * 2048
    assert parts["pages_written"] == 6 * 96 * 4 * 2048
    assert parts["head"] == 622_329_856
    assert parts["logits"] == 2 * 96 * 2 * 151_936 * 4
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    assert 8.8e9 < parts["total"] < 9.2e9   # 10.7 to 11.2 ms at 819 GB/s


def sources(before, after, trace=None):
    _, m = published_dims()
    return {
        "stats": {"before": before, "after": after, "window_s": 1.0},
        "trace": trace,
        "model": {"dims": m, "operations": "sdar_flops", "num_slots": 96,
                  "device": {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}},
    }


def stats(passes, live, hit=128):
    commits = passes * live // 3
    return {
        "steps": passes,
        "moe": {"assignments": passes * 6 * 384 * 8, "calls": passes,
                "experts_hit_sum": passes * 6 * hit,
                "max_load_sum": passes * 6 * 40},
        "diffusion": {"passes": passes, "slot_passes_offered": passes * 96,
                      "slot_passes": passes * live,
                      "denoise_slot_passes": passes * live - commits,
                      "commit_slot_passes": commits,
                      "tokens_committed": commits * 4,
                      "blocks_committed": commits,
                      "head_rows": passes * 192,
                      "head_rows_used": 2 * (passes * live - commits)},
    }


def test_the_cells_own_counters_read_through_the_stats_reader():
    src = sources(stats(30, 90), stats(330, 90))
    want = {"diffusion.tokens_per_pass": 4 / 3,
            "diffusion.commit_pass_share": 100 / 3,
            "diffusion.live_slot_share": 100 * 90 / 96,
            "diffusion.head_rows_used_share": 100 * 2 / 3 * 90 / 96}
    for name, value in want.items():
        how = spec.layer_metric_spec(name)
        assert how["reader"] == "stats" and how["note"]
        assert readers.read("stats", src, how) == pytest.approx(value)
        # The parent's program has no such counters: nothing to read.
        bare = sources({"steps": 0}, {"steps": 300})
        assert readers.read("stats", bare, how) is None


def test_the_expert_roofline_reads_through_the_moe_reader():
    how = spec.layer_metric_spec("moe.expert_roofline_share.sdar")
    assert how["reader"] == "moe"
    trace = {
        "op_s": {"%gmm.14 f32[3072,768]": 0.34, "%gmm.15 f32[3072,768]": 0.34,
                 "%gmm.16 f32[3072,2048]": 0.34, "%fusion.137 f32[192,64]": 0.5},
        "modules": {
            "jit__lambda(1)": {"launches": 98,
                               "ops": ["%gmm.14 f32[3072,768]"]},
            "jit__lambda(2)": {"launches": 7,
                               "ops": ["%gmm.13 f32[4096,768]"]},
            "jit_start_blocks(3)": {"launches": 22, "ops": ["%select f32[96]"]}},
    }
    src = sources(stats(30, 90), stats(330, 90, hit=127.5), trace)
    hit = (330 * 127.5 - 30 * 128) / 300
    want = 100.0 * (6 * hit * 9_437_184 * 105) / 819e9 / 1.02
    assert readers.read("moe", src, how) == pytest.approx(want)
    assert 0 < want < 100
    assert readers.read("moe", sources({"steps": 0}, {"steps": 9}, trace),
                        how) is None


def test_the_cell_is_on_the_lists_it_can_read_and_off_three():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    own = {name for name in listed
           if name.endswith(".sdar") or name.startswith("diffusion.")}
    assert len(own) == 11 and len(listed) == 26
    assert {"moe.experts_hit_share", "moe.load_max_over_mean"} <= listed
    # One token a live slot a step is not this engine's: its own count is
    # `diffusion.live_slot_share`. And no token comes out of its prefill,
    # so nothing is fetched behind a pass: the two means over such fetches
    # have no event to be a mean of, and `readers/stats.py` reads nothing.
    assert not listed & {"engine.occupancy", "engine.pass_drain_ms",
                         "engine.fetch_drain_ms"}
    assert all(m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"]
               if m["name"] in listed)
