"""The arithmetic of the hybrid's cell (`granite_flops.py`) against the
numbers its configuration file and PERF.md state, and the `ssm` reader on
made-up counters and a made-up trace."""

import json
import os

import pytest

import granite_flops as ops
import spec
from readers import ssm as reader


def dims():
    with open(os.path.join(spec.BENCH, "configs",
                           "granite-4.0-h-micro-serve.json")) as f:
        doc = json.load(f)
    return spec.dims_of(spec.program_config(doc, "tpu"), doc)


def test_the_published_model_by_its_shapes():
    m = dims()
    assert ops.kinds(m, 40) == (36, 4)
    assert ops.mamba_params(m) == 25_847_232           # 25.85 M
    assert ops.mlp_params(m) == 50_331_648             # 50.33 M
    assert ops.attention_params(m) == 10_485_760       # 10.49 M
    assert ops.table_params(m) == 205_520_896          # 205.5 M, tied
    held = ops.params_held(m, 40)
    assert round(held / 1e9, 2) == 3.19
    # The program's tree has these and the norm scales: two a layer and
    # the last one, 2048 wide each.
    assert held + (2 * 40 + 1) * 2048 == 3_191_396_096
    assert round(held * 2 / 1e9, 2) == 6.38            # bf16
    # A slot's recurrent row: 36 layers of float32 state and of the
    # convolution's last 3 inputs in bf16.
    assert ops.state_bytes_per_row(m) == 2_097_152
    row = 36 * (2_097_152 + 3 * 4352 * 2)
    assert round(row / 1e6, 1) == 76.4
    assert round(48 * row / 1e9, 2) == 3.67
    # A decode step of 48 slots reads and writes every row's state.
    assert ops.state_update_bytes(m, 48) == 48 * 36 * 2 * 2_097_152
    assert round(ops.state_update_bytes(m, 48) / 1e9, 2) == 7.25
    assert ops.state_update_bytes(m, 48, n_layers=6) == 48 * 5 * 2 * 2_097_152
    # A chunk of 256 tokens in one block: 1.09 GFLOP a layer.
    assert round(ops.scan_flops(m, 256) / 36 / 1e9, 2) == 1.09
    assert ops.scan_bytes(m, 256, 1) == 36 * (
        256 * (4096 + 4352 + 64) * 4 + 2 * 2_097_152)
    # Training arithmetic, for `train.mfu` should a cell train it: three
    # forward passes, a forward pass two operations a matrix parameter.
    per_token = ops.train_flops_per_token(m, 40, 1024)
    assert 6.0 * 3.19e9 < per_token < 6.0 * 3.19e9 * 1.25


def sources(before, after, trace=None, platform="tpu"):
    return {
        "stats": {"before": {"ssm": before}, "after": {"ssm": after},
                  "window_s": 1.0},
        "trace": trace,
        "model": {"dims": dims(), "operations": "granite_flops",
                  "num_slots": 48,
                  "device": {"platform": platform, "kind": "TPU v5 lite",
                             "count": 1}},
    }


def counters(calls, live, computed):
    return {"calls": calls, "decode_rows_live": live,
            "decode_rows_computed": computed, "prefill_tokens_valid": 0,
            "prefill_tokens_computed": 0, "state_resets": 0,
            "prefix_reuse_skipped": 0, "pool_bytes": 1, "bytes_per_slot": 1}


UPDATE = {"quantity": "update_roofline_share",
          "match": r"f32\[(?:36,)?48,64,64(?:,128)?\]$",
          "contains_op": r"bf16\[48,1,2048\]"}
SCAN = {"quantity": "scan_roofline_share", "match": r"f32\[64,256,256\]$",
        "contains_op": r"bf16\[1,256,2048\]", "tokens_per_launch": 256}


def test_reader_on_made_up_counters_and_trace():
    before, after = counters(5, 100, 240), counters(105, 4_420, 5_040)
    assert reader.read(sources(before, after),
                       {"quantity": "live_row_share"}) == pytest.approx(90.0)
    trace = {
        "op_s": {"%fusion.9 f32[36,48,64,64,128]": 0.05,
                 "%fusion.8 f32[48,64,64]": 0.04,
                 "%fusion.7 bf16[48,1,2048]": 0.3,
                 "%fusion.3 f32[64,256,256]": 0.02,
                 "%fusion.5 f32[36,48,64,64,128]": 0.001},
        "modules": {
            "jit__lambda(1)": {"launches": 10, "ops": [
                "%fusion.9 f32[36,48,64,64,128]", "%fusion.8 f32[48,64,64]",
                "%fusion.7 bf16[48,1,2048]"]},
            "jit__lambda(2)": {"launches": 4, "ops": [
                "%fusion.3 f32[64,256,256]", "%fusion.2 bf16[1,256,2048]",
                "%fusion.5 f32[36,48,64,64,128]"]}},
    }
    src = sources(before, after, trace)
    # Ten decode launches of 48 rows, 0.09 s in the two matched operations
    # of the decode program (the chunk's write of one row is not its).
    want = 100.0 * (10 * 48 * 36 * 2 * 2_097_152) / 819e9 / 0.09
    assert reader.read(src, UPDATE) == pytest.approx(want)
    assert 0 < want < 100
    # Four chunks of 256 tokens: bound by bytes, not by operations.
    m = dims()
    least = max(ops.scan_flops(m, 1024) / 197e12,
                ops.scan_bytes(m, 1024, 4) / 819e9)
    assert least == ops.scan_bytes(m, 1024, 4) / 819e9
    assert reader.read(src, SCAN) == pytest.approx(100.0 * least / 0.02)


def test_reader_finds_nothing_to_read_and_says_so():
    ssm = counters(10, 400, 480)
    live = {"quantity": "live_row_share"}
    # A model without recurrent layers, or the parent's program.
    plain = {"stats": {"before": {"steps": 0}, "after": {"steps": 9},
                       "window_s": 1.0}, "model": sources(ssm, ssm)["model"]}
    assert reader.read(plain, live) is None
    assert reader.read(plain, UPDATE) is None
    assert reader.read({"model": plain["model"]}, live) is None
    # No decode step in the window, no trace, a CPU rehearsal's trace.
    assert reader.read(sources(ssm, ssm), live) is None
    later = counters(20, 800, 960)
    assert reader.read(sources(ssm, later), UPDATE) is None
    empty = {"op_s": {}, "modules": {}}
    assert reader.read(sources(ssm, later, empty), UPDATE) is None
    one = {"op_s": {"%f.1 f32[48,64,64]": 1.0},
           "modules": {"m": {"launches": 1, "ops": [
               "%f.1 f32[48,64,64]", "%f.2 bf16[48,1,2048]"]}}}
    assert reader.read(sources(ssm, later, one, "cpu"), UPDATE) is None
    assert reader.read(sources(ssm, later, one), UPDATE) is not None
    with pytest.raises(ValueError):
        reader.read(sources(ssm, later), {"quantity": "nope"})
