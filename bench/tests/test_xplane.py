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


# --- The marked window (PR 66): made-up planes whose operations overhang it.

FUSION = "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p)"
OTHER = "%fusion.2 = f32[8]{0} fusion(bf16[8,128]{1,0} %q)"
LOOP = "%while.4 = (s32[], bf16[24,1,2560]{2,0,1}) while(%tuple)"
GATHER = "%all-gather.7 = bf16[16,128]{1,0} all-gather(bf16[8,128]{1,0} %p)"


def marks(span, one=True, thread="worker"):
    """The harness's marks on a host plane: one `bench.window` span, or a
    short mark at each end on a thread of its own."""
    lo, hi = span
    if one:
        return {"name": "/host:CPU", "lines": [
            {"name": thread, "events": [(xr.WINDOW, lo, hi - lo)]}]}
    return {"name": "/host:CPU", "lines": [
        {"name": "rpc-1", "events": [(xr.WINDOW_START, lo - 0.25, 0.25)]},
        {"name": "rpc-2", "events": [(xr.WINDOW_END, hi, 0.5)]}]}


def device(n, ops, launches=()):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Modules", "events": list(launches)},
        {"name": "XLA Ops", "events": list(ops)}]}


@pytest.mark.parametrize("one", [True, False], ids=["one-span", "two-marks"])
def test_operations_that_overhang_the_marked_window_are_clipped(one):
    # Recorded from 0 to 12, marked from 2 to 10: an operation over each
    # end, one inside, gaps of 1 and 2 inside; the handed window is ignored.
    planes = [device(0, [(FUSION, 0.0, 3.0), (OTHER, 4.0, 3.0),
                         (FUSION, 9.0, 3.0)]), marks((2.0, 10.0), one)]
    out = xr.reduce(planes, window_s=99.0)
    dev = out["devices"]["/device:TPU:0"]
    assert out["window_marked"] and out["window_s"] == pytest.approx(8.0)
    assert dev["busy_s"] == pytest.approx(1.0 + 3.0 + 1.0)
    assert out["busy_s"] == dev["busy_s"] <= out["window_s"]
    assert dev["outside_s"] == [pytest.approx(2.0), pytest.approx(2.0)]
    assert dev["edge_idle_s"] == [0.0, 0.0]
    assert dev["busy_unclipped_s"] == pytest.approx(9.0)
    assert out["busy_unclipped_s"] == dev["busy_unclipped_s"]
    assert dev["idle_share"] == pytest.approx(1.0 - 5.0 / 8.0)
    # Numerators are of the same window: the names' times and the gaps.
    assert dict(out["device_ops"]) == {
        "%fusion.1 bf16[8,128]": pytest.approx(2.0),
        "%fusion.2 f32[8]": pytest.approx(3.0)}
    # The gaps are the window's, and the harness's own marks name none.
    assert out["idle_gaps"] == [["(no host event)", pytest.approx(3.0)]]
    assert sum(s for _n, s in out["by_opcode"]) == pytest.approx(5.0)
    from readers import trace as reader
    assert reader.read({"trace": out}, {"quantity": "idle_share"}
                       ) == pytest.approx(37.5)
    assert reader.read({"trace": out}, {
        "quantity": "op_time_share", "match": r"^%fusion\.1"}
    ) == pytest.approx(40.0)


def test_a_window_inside_one_busy_interval_reads_no_idle_and_no_more():
    # The never-idle case (PRs 50 and 65 met it): the device runs from
    # before the window to after it. Busy is the window to the last bit,
    # and idle 0.0 by arithmetic, not by a clamp.
    span = (1.0000001, 3.9999997)
    planes = [device(0, [(FUSION, 0.0, 2.5), (OTHER, 2.5, 2.5)]),
              marks(span)]
    out = xr.reduce(planes)
    dev = out["devices"]["/device:TPU:0"]
    assert out["busy_s"] == out["window_s"] == span[1] - span[0]
    assert dev["idle_share"] == 0.0
    assert dev["outside_s"] == [pytest.approx(1.0000001),
                                pytest.approx(5.0 - 3.9999997)]
    assert out["idle_gaps"] == []
    # The parent's arithmetic on the same planes: everything recorded over
    # the window's length, and a clamp to hide it.
    assert dev["busy_unclipped_s"] / out["window_s"] > 1.6


def test_a_while_across_an_end_is_no_leaf_on_either_side():
    # The scan's `while` spans 1 to 9 with its body's operations on both
    # sides of the window's end at 6; cut there it is still no work of
    # its own, and the leaf the end cuts counts for its part inside.
    planes = [device(0, [(LOOP, 1.0, 8.0), (FUSION, 1.0, 2.0),
                         (OTHER, 3.0, 2.0), (FUSION, 5.5, 1.5),
                         (OTHER, 7.0, 2.0)]), marks((0.0, 6.0))]
    out = xr.reduce(planes)
    dev = out["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(5.0)  # the `while`, 1 to 6
    assert dev["ops"] == 3
    assert "while" not in dict(out["by_opcode"])
    assert dict(out["device_ops"]) == {
        "%fusion.1 bf16[8,128]": pytest.approx(2.5),
        "%fusion.2 f32[8]": pytest.approx(2.0)}
    assert dev["outside_s"] == [0.0, pytest.approx(3.0)]
    assert dev["edge_idle_s"] == [pytest.approx(1.0), 0.0]
    # A `while` whose body lies wholly past the end: inside the window it
    # has no operation under it and is still not a leaf.
    planes = [device(0, [(LOOP, 4.0, 6.0), (FUSION, 7.0, 3.0)]),
              marks((0.0, 6.0))]
    out = xr.reduce(planes)
    assert out["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(2.0)
    assert out["device_ops"] == [] and out["devices"]["/device:TPU:0"]["ops"] == 0


def test_a_launch_an_end_cuts_is_left_out_of_the_mean():
    # Four launches of 2 s; the window cuts the first and the last. The
    # mean is over the two whole ones; what ran in a cut one still names
    # the program (`contains_op`).
    step = "jit_step(77)"
    launches = [(step, 0.0, 2.0), (step, 3.0, 2.0), (step, 6.0, 2.5),
                (step, 9.0, 2.0)]
    ops = [(FUSION, 0.0, 2.0), (FUSION, 3.0, 2.0), (FUSION, 6.0, 2.5),
           (OTHER, 9.0, 2.0)]
    out = xr.reduce([device(0, ops, launches), marks((1.0, 10.0))])
    m = out["modules"][step]
    assert (m["launches"], m["total_s"]) == (2, pytest.approx(4.5))
    assert "%fusion.2 f32[8]" in m["ops"]  # seen in the cut launch alone
    from readers import trace as reader
    assert reader.read({"trace": out}, {
        "quantity": "module_ms_per_launch", "match": "jit_step"}
    ) == pytest.approx(2250.0)
    # Busy takes the cut launches' parts inside the window.
    assert out["busy_s"] == pytest.approx(1.0 + 2.0 + 2.5 + 1.0)
    # A program whose every launch is cut reads no mean at all.
    out = xr.reduce([device(0, ops[:1], launches[:1]), marks((1.0, 10.0))])
    assert out["modules"][step]["launches"] == 0
    assert reader.read({"trace": out}, {
        "quantity": "module_ms_per_launch", "match": "jit_step"}) is None


def test_four_device_planes_share_the_one_window():
    # A step across four chips: each plane is clipped to the same span,
    # the line's busy is the chips' mean, the idle share the worst chip's,
    # a collective is exposed where no other operation covers it.
    step = "jit_step(9)"
    planes = [device(n, [(FUSION, 0.0, 4.0 + n), (GATHER, 4.0 + n, 2.0),
                         (FUSION, 9.0, 2.0)],
                     [(step, 0.0, 6.0 + n), (step, 9.0, 2.0)])
              for n in range(4)] + [marks((1.0, 10.0))]
    out = xr.reduce(planes)
    assert out["n_devices"] == 4 and out["window_s"] == pytest.approx(9.0)
    busy = [out["devices"][f"/device:TPU:{n}"]["busy_s"] for n in range(4)]
    assert busy == [pytest.approx(3.0 + n + 2.0 + 1.0) for n in range(4)]
    assert out["busy_s"] == pytest.approx(sum(busy) / 4)
    assert all(b <= out["window_s"] for b in busy)
    assert out["outside_s"] == [pytest.approx(1.0), pytest.approx(1.0)]
    assert out["modules"][step]["launches"] == 0  # every launch is cut
    from readers import trace as reader
    src = {"trace": out}
    assert reader.read(src, {"quantity": "idle_share"}) == pytest.approx(
        100.0 * (1.0 - 6.0 / 9.0))
    assert reader.read(src, {"quantity": "collective_exposed_share"}
                       ) == pytest.approx(100.0 * 2.0 / 9.0)
    assert reader.read(src, {"quantity": "op_time_share",
                             "match": "all-gather"}
                       ) == pytest.approx(100.0 * 2.0 / (sum(busy) / 4))


def test_a_trace_with_no_span_is_reduced_whole_or_not_at_all():
    # No mark: today's numbers against the window handed in (the recorded
    # sample's case, `test_reduce_the_recorded_trace`), a share under
    # nought where that window is too short for what was recorded (no
    # clamp), and with no window either, a reason and not a number.
    planes = [device(0, [(FUSION, 0.0, 3.0), (OTHER, 4.0, 3.0)]),
              {"name": "/host:CPU", "lines": [{"name": "python", "events": [
                  ("bench.fetch_loss", 2.5, 2.0)]}]}]
    out = xr.reduce(planes, window_s=8.0)
    dev = out["devices"]["/device:TPU:0"]
    assert not out["window_marked"] and out["window_s"] == 8.0
    assert dev["busy_s"] == dev["busy_unclipped_s"] == pytest.approx(6.0)
    assert dev["outside_s"] == [0.0, 0.0]
    assert dev["idle_share"] == pytest.approx(0.25)
    assert out["idle_gaps"] == [["bench.fetch_loss", pytest.approx(1.0)]]
    short = xr.reduce(planes, window_s=5.0)
    assert short["devices"]["/device:TPU:0"]["idle_share"] == pytest.approx(-0.2)
    with pytest.raises(ValueError, match="bench.window"):
        xr.reduce(planes)
    # One mark of the two is no window.
    half = planes + [{"name": "/host:CPU", "lines": [{"name": "rpc", "events": [
        (xr.WINDOW_START, 1.0, 0.1)]}]}]
    with pytest.raises(ValueError, match="bench.window"):
        xr.reduce(half)


def test_reduce_dir_gives_the_reason_where_there_is_no_trace(tmp_path):
    out = xr.reduce_dir(str(tmp_path / "trace"))
    assert set(out) == {"error"} and "no .xplane.pb" in out["error"]


# --- A traced run with no reduced trace has no result (bench/run.py).

def traced_result(trace):
    return {"correct": True, "attempted": 5, "failed": 0, "values": {},
            "compared": {"x": {"value": 0.0, "limit": 1.0, "holds": True}},
            "sources": {"trace": trace},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1}}


@pytest.mark.parametrize("trace, reason", [
    ({"error": "no .xplane.pb under /x/trace"}, "no .xplane.pb under /x/trace"),
    ({"error": "the tracer thread had not returned 120 s after the callers' "
               "end"}, "had not returned 120 s"),
    ({}, "gathered no trace"),
    (None, "gathered no trace"),
    ({"window_s": 3.0}, "no busy_s"),
    ({"busy_s": 0.0, "window_s": 3.0, "n_devices": 0}, "no operation ran"),
    ({"busy_s": 3.1, "window_s": 3.0, "n_devices": 1}, "busy_s 3.1"),
])
def test_a_traced_run_without_a_reduced_trace_is_no_result(
        tmp_path, capsys, trace, reason):
    import argparse

    import run as harness

    args = argparse.Namespace(trace=1, platform="tpu")
    cell = {"per_layer": [], "end_to_end": []}
    rc = harness.finish(args, cell, traced_result(trace), str(tmp_path), 40.0)
    said = capsys.readouterr()
    assert rc == 1
    last = said.out.strip().splitlines()[-1]
    assert "no result:" in last and reason in last
    assert not any(row.startswith("{") for row in said.out.splitlines())
    assert said.err == "" and not os.listdir(tmp_path)


def test_a_traced_run_with_its_trace_prints_the_line_from_it(tmp_path, capsys):
    import argparse

    import run as harness

    planes = [device(0, [(FUSION, 0.0, 3.0), (OTHER, 4.0, 3.0)]),
              marks((2.0, 6.0), one=False)]
    args = argparse.Namespace(trace=1, platform="tpu")
    cell = {"per_layer": [], "end_to_end": []}
    rc = harness.finish(args, cell, traced_result(xr.reduce(planes)),
                        str(tmp_path), 40.0)
    said = capsys.readouterr()
    assert rc == 0
    line = json.loads(said.out.strip().splitlines()[-1])
    assert line["device"]["busy_s"] == pytest.approx(3.0)
    assert line["device"]["window_s"] == pytest.approx(4.0)
    assert list(line)[-1] == "compared"
    assert "outside_s [2.0, 1.0]" in said.out
    # The untraced line takes nothing from a trace and needs none.
    args = argparse.Namespace(trace=0, platform="tpu")
    assert harness.finish(args, cell, traced_result(None), str(tmp_path),
                          40.0) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "busy_s" not in line["device"] and "breakdown" not in line


# --- `offer`'s call for the reduction, after the `stats` window has closed.

class FakeHandle:
    """`handle.options(method_name=m).remote().result(timeout=t)`."""

    def __init__(self, answer):
        self.answer, self.called = answer, []

    def options(self, method_name):
        self.called.append(method_name)
        return self

    def remote(self, *args):
        return self

    def result(self, timeout):
        if isinstance(self.answer, Exception):
            raise self.answer
        return self.answer


def test_the_reduction_is_asked_for_once_the_tracer_is_back_or_says_why():
    import threading

    import serve_cell

    done = threading.Thread(target=lambda: None)
    done.start()
    done.join()
    ok = FakeHandle({"busy_s": 2.0, "window_s": 3.0})
    assert serve_cell.reduced_trace(ok, {"stopped": True}, done) == ok.answer
    assert ok.called == ["trace_reduce"]
    boom = FakeHandle(RuntimeError("Remote task failed:\n  File x\nOSError: gone\n"))
    assert serve_cell.reduced_trace(boom, {"stopped": True}, done) == {
        "error": "the trace could not be reduced: RuntimeError: OSError: gone"}
    # The profiler never stopped: its reason, and no call to the replica.
    for traced, reason in (({"error": "the profiler could not be run: x"},
                            "the profiler could not be run: x"),
                           ({}, "the profiler was not stopped")):
        idle = FakeHandle({})
        assert serve_cell.reduced_trace(idle, traced, done) == {"error": reason}
        assert idle.called == []
    # The tracer thread still out: the join's limit is named.
    gate = threading.Event()
    out = threading.Thread(target=gate.wait, daemon=True)
    out.start()
    try:
        got = serve_cell.reduced_trace(ok, {"stopped": True}, out)
    finally:
        gate.set()
        out.join()
    assert got == {"error": "the tracer thread had not returned 120 s after "
                            "the callers' end"}
