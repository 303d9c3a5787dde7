"""The trace reduction: interval arithmetic on a made-up trace, and the
whole reduction on the small trace recorded on the chip and kept beside
the reducer (bench/xplane/sample.xplane.pb; bench/xplane/sample.json says
how it was recorded and what the reduction has to find in it)."""

import json
import os

import pytest

from xplane import reduce as xr

HERE = os.path.dirname(os.path.abspath(xr.__file__))


def test_union_and_subtract():
    assert xr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert xr.subtract([(0, 1), (4, 6)], [(0.5, 5)]) == [(0, 0.5), (5, 6)]


def test_reduce_a_made_up_trace():
    # Operations are named by their whole HLO text; a `while` spans its
    # body, and an operand called %all-reduce.2 makes nothing a collective.
    fusion = "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %all-reduce.2)"
    reduce_ = "%all-reduce.2 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} all-reduce(bf16[8,128]{1,0} %p)"
    kernel = '%custom-call.3 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(bf16[8,128]{1,0} %x), custom_call_target="tpu_custom_call"'
    loop = "%while.4 = (s32[], bf16[24,1,2560]{2,0,1}) while(%tuple)"
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_step(123)", 0.0, 4.0), ("jit_step(123)", 5.0, 4.0)]},
            {"name": "XLA Ops", "events": [
                (loop, 0.0, 4.0), (fusion, 0.0, 2.0), (reduce_, 2.0, 1.0),
                (kernel, 3.0, 1.0), (loop, 5.0, 4.0), (fusion, 5.0, 2.0),
                (reduce_, 7.0, 1.0), (kernel, 8.0, 1.0)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("bench.fetch_loss", 3.5, 2.0)]}]},
    ]
    out = xr.reduce(planes, window_s=10.0)
    dev = out["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(8.0)
    assert dev["idle_share"] == pytest.approx(0.2)
    assert dev["collective_exposed_s"] == pytest.approx(2.0)
    assert dev["custom_call_s"] == pytest.approx(2.0)
    assert dev["ops"] == 6  # the two `while` events are not leaves
    step = out["modules"]["jit_step(123)"]
    assert (step["launches"], step["total_s"]) == (2, 8.0)
    assert "%while.4 s32[]" in step["ops"] and "%fusion.1 bf16[8,128]" in step["ops"]
    assert out["idle_gaps"] == [["bench.fetch_loss", pytest.approx(1.0)]]
    assert out["device_ops"][0] == ["%fusion.1 bf16[8,128]", pytest.approx(4.0)]
    from readers import trace as reader
    src = {"trace": out}
    assert reader.read(src, {"quantity": "module_ms_per_launch", "match": "jit_step",
                             "contains_op": r"bf16\[24,1,2560\]"}) is None
    assert reader.read(src, {"quantity": "module_ms_per_launch", "match": "jit_step",
                             "contains_op": r"bf16\[8,128\]"}) == pytest.approx(4000.0)
    assert reader.read(src, {"quantity": "collective_exposed_share"}) == pytest.approx(20.0)
    assert reader.read(src, {"quantity": "custom_call_share"}) == pytest.approx(25.0)
    # An operand called %all-reduce.2 does not make the fusion a match.
    for match, share in ((r"^%fusion", 50.0), ("all-reduce", 25.0),
                         (r"bf16\[8,128\]", 100.0), ("while", 0.0)):
        assert reader.read(src, {"quantity": "op_time_share",
                                 "match": match}) == pytest.approx(share)


def test_reduce_the_recorded_trace():
    sample = os.path.join(HERE, "sample.xplane.pb")
    if not os.path.exists(sample):
        pytest.skip("no recorded trace beside the reducer")
    with open(os.path.join(HERE, "sample.json")) as f:
        want = json.load(f)
    out = xr.reduce(xr.load(sample), want["window_s"])
    assert out["n_devices"] == want["n_devices"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    for name, m in want["modules"].items():
        assert out["modules"][name]["launches"] == m["launches"]
        assert out["modules"][name]["total_s"] == pytest.approx(m["total_s"], rel=1e-6)
    assert 0.0 < out["busy_s"] < want["window_s"]
    assert out["devices"]["/device:TPU:0"]["ops"] == want["leaf_ops"]
    assert [n for n, _ in out["by_opcode"]] == [n for n, _ in want["by_opcode"]]
    # The scan's `while` spans its body and is not counted as work itself.
    assert "while" not in dict(out["by_opcode"])
    # Every operation's time is kept by name, the printed breakdown its
    # first ten; a share over a pattern sums whatever matches.
    assert len(out["op_s"]) == 14 and len(out["device_ops"]) == 10
    assert out["device_ops"] == [[n, s] for n, s in out["op_s"].items()][:10]
    from readers import trace as reader
    fusions = dict(out["by_opcode"])["fusion"]  # all of them named *fusion*
    assert reader.read({"trace": out}, {
        "quantity": "op_time_share", "match": "fusion"}) == pytest.approx(
            100.0 * fusions / out["busy_s"])
    assert reader.read({"trace": out}, {
        "quantity": "op_time_share", "match": r"^%slice-(start|done)"}
    ) == pytest.approx(100.0 * 5.2e-08 / out["busy_s"])  # past the tenth
