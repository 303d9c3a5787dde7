"""From a profiler trace (.xplane.pb) to numbers: device busy and idle,
time per program and per operation, collectives, and the longest idle gaps
named by what the host was doing. The reduction is the benchmark's own, so
every PR computes the same number in the same way; it is checked against
the small recorded trace kept beside it (bench/tests/test_xplane.py).

What a TPU trace looks like today (JAX 0.9, v5e; `describe()` prints it):
one plane per chip named `/device:TPU:<n>`, with a line `XLA Modules` (one
event per launch of a compiled program, named `jit_<fn>(<fingerprint>)`;
the engine's decode and prefill programs are both `jit__lambda` and differ
only in the fingerprint) and a line `XLA Ops` (one event per HLO operation
as it runs on the core, named by its whole HLO text, nested where a `while`
spans its body; a Pallas kernel is a `custom-call` whose target is
`tpu_custom_call`, and nothing in the event tells one kernel from another). Host threads are lines of the plane
`/host:CPU`; `jax.profiler.TraceAnnotation` spans land there under their
own names. All planes share one clock.

The window is the harness's own span in that trace (`bench.window`, or the
two short marks `bench.window.start` and `bench.window.end` where the two
ends are made on different threads): the profiler records from some way
into `start_trace` to some way into `stop_trace`, the program dispatches
through both, and what ran outside the marked span is clipped off every
interval before any union or sum, so that busy time and the window it is
divided by are of one span on one clock.

Programs and kernels have no stable names yet: matching is by regular
expression on the names the compiler gives today, and PERF.md lists the
`jax.named_scope`s a tracing PR should add.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?$")
# A Pallas (Mosaic) kernel is a custom call with this target; XLA's own
# custom calls (AllocateBuffer and the like) are not kernels.
KERNEL_TARGET = "tpu_custom_call"
# The harness's marks on the `/host:` planes (serve_cell.trace_start and
# trace_stop, train_cell's traced steps): one span, or a mark at each end.
MARKS = WINDOW, WINDOW_START, WINDOW_END = (
    "bench.window", "bench.window.start", "bench.window.end")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> List[Dict]:
    """The trace as plain data: planes -> lines -> events (name, start and
    duration in seconds on the trace's own clock)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals `a` that no interval of the
    (merged) `b` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def marked_window(planes: List[Dict]) -> Optional[Interval]:
    """The span the harness marked in the trace: its `bench.window`
    annotation, or from the end of `bench.window.start` to the start of
    `bench.window.end`; None where the trace has neither."""
    found: Dict[str, Interval] = {}
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            for name, s, d in line["events"]:
                if name in MARKS:
                    found.setdefault(name, (s, s + d))
    if WINDOW in found:
        return found[WINDOW]
    if WINDOW_START in found and WINDOW_END in found:
        span = (found[WINDOW_START][1], found[WINDOW_END][0])
        return span if span[1] > span[0] else None
    return None


def within(intervals: Sequence[Interval], lo: float, hi: float
           ) -> List[Interval]:
    """The parts of the intervals that lie between `lo` and `hi`."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _clip(events, lo: float, hi: float):
    """The part of each event between `lo` and `hi`; an event outside goes
    (one of no length stays where it starts inside)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a or (d == 0 and lo <= s < hi):
            out.append((name, a, max(b - a, 0.0)))
    return out


def _line(plane: Dict, name: str) -> List[Tuple[str, float, float]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _short(name: str) -> str:
    """A program `jit_step(1234567)` -> `jit_step`. An operation's event
    is named by its whole HLO text, `%fusion.3 = bf16[8,128]{...}
    fusion(...operands...)`: keep its own name and result shape, never the
    operands (an operand called %all-reduce.5 does not make a fusion a
    collective)."""
    if " = " in name:
        own, rest = name.split(" = ", 1)
        shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
        return own + (" " + shape.group(0).lstrip("(") if shape else "")
    return re.sub(r"\(\d+\)$", "", name)


def _opcode(name: str) -> str:
    """The HLO opcode of an operation's event (`fusion`, `custom-call`,
    `all-reduce-start`, ...): the first lower-case word followed by `(`
    after the result shape (layout annotations are upper-case)."""
    if " = " not in name:
        return ""
    m = _OPCODE.search(name.split(" = ", 1)[1])
    return m.group(1) if m else ""


def _is_kernel(name: str) -> bool:
    return _opcode(name) == "custom-call" and KERNEL_TARGET in name


def _leaves(events):
    """Events of one line that contain no other event of it: a `while` or
    a call spans its body's operations, which are the work."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= ev[1]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    out.extend(top for top, has_child in stack if not has_child)
    return out


def describe(planes: List[Dict], top: int = 12) -> str:
    """The shape of a trace, for a first look and for the run's log."""
    rows = []
    for p in planes:
        rows.append(f"plane {p['name']}")
        for line in p["lines"]:
            by = defaultdict(float)
            for name, _s, d in line["events"]:
                by[_short(name)] += d
            heads = sorted(by.items(), key=lambda kv: -kv[1])[:top]
            rows.append(f"  line {line['name']!r}: {len(line['events'])} "
                        "events; " + ", ".join(
                            f"{n} {d * 1e3:.2f}ms" for n, d in heads))
    return "\n".join(rows)


def reduce(planes: List[Dict], window_s: Optional[float] = None,
           top: int = 10) -> Dict:
    """Everything the per-layer readers take from a trace.

    The window is the span the harness marked in the trace
    (`marked_window`), and every operation, launch and collective interval
    is clipped to it before any union or sum: busy time is the union of
    the clipped operation intervals on each device's `XLA Ops` line, the
    idle share 1 - busy / window per device, and busy cannot pass the
    window. A program's `launches` and `total_s` count the launches that
    lie wholly inside the window, so a launch an end cuts biases no mean.
    `outside_s` is what the clip took, before and after the window, and
    `busy_unclipped_s` the union of everything the profiler recorded.

    A trace with no marked span (the recorded sample) is reduced whole
    against the `window_s` it is handed, the window's length by the
    host's clock; with neither there is no window and this raises.
    """
    span = marked_window(planes)
    if span is not None:
        window_s = span[1] - span[0]
    elif window_s is None:
        raise ValueError(
            f"the trace has no {WINDOW!r} span (nor {WINDOW_START!r} and "
            f"{WINDOW_END!r}) on a /host: plane, and no window was given")
    marked = span is not None
    lo, hi = span if marked else (float("-inf"), float("inf"))
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    host_lines = [ln for p in planes if p["name"].startswith("/host:")
                  for ln in p["lines"]]
    per_device = {}
    ops_total: Dict[str, float] = defaultdict(float)
    by_opcode: Dict[str, float] = defaultdict(float)
    shorts: Dict[str, str] = {}
    modules: Dict[str, Dict] = {}
    gaps: List[Interval] = []
    for p in devices:
        ops = _line(p, OPS_LINE)
        if not ops:  # an unfamiliar layout: every line counts as work
            ops = [ev for ln in p["lines"] for ev in ln["events"]]
        recorded_iv = union((s, s + d) for _n, s, d in ops)
        busy_iv = within(recorded_iv, lo, hi)
        # What is a leaf is decided over the whole trace (a `while` an end
        # cuts keeps its body on the other side), then clipped.
        leaves = _clip(_leaves(ops), lo, hi)
        coll_iv = union((s, s + d) for n, s, d in leaves
                        if COLLECTIVE.match(_opcode(n)))
        work_iv = union((s, s + d) for n, s, d in leaves
                        if not COLLECTIVE.match(_opcode(n)))
        custom = sum(d for n, _s, d in leaves if _is_kernel(n))
        busy = total(busy_iv)
        # Marked, busy cannot pass the window; unmarked, a clocked window
        # too short for what was recorded reads a share under nought.
        assert not marked or busy <= window_s * (1.0 + 1e-9), (
            f"{p['name']}: busy {busy!r} s over the marked window "
            f"{window_s!r} s after the clip")
        per_device[p["name"]] = {
            "busy_s": busy,
            "idle_share": 1.0 - busy / window_s,
            "busy_unclipped_s": total(recorded_iv),
            "outside_s": [total(within(recorded_iv, float("-inf"), lo)),
                          total(within(recorded_iv, hi, float("inf")))],
            # Idle at the window's two ends: a device tracer that recorded
            # less than the window would show as a long stretch here.
            "edge_idle_s": ([busy_iv[0][0] - lo, hi - busy_iv[-1][1]]
                            if marked and busy_iv else [0.0, 0.0]),
            "collective_s": total(coll_iv),
            "collective_exposed_s": total(subtract(coll_iv, work_iv)),
            "custom_call_s": custom,
            "ops": len(leaves),
        }
        for n, _s, d in leaves:
            ops_total[_short(n)] += d
            by_opcode[_opcode(n) + ("/kernel" if _is_kernel(n) else "")] += d
        by_start = sorted(ops, key=lambda e: e[1])
        starts = [e[1] for e in by_start]
        for n, s0, d in _line(p, MODULES_LINE):
            m = modules.setdefault(n, {"name": _short(n), "launches": 0,
                                       "total_s": 0.0, "ops": set()})
            if lo <= s0 and s0 + d <= hi:
                m["launches"] += 1
                m["total_s"] += d
            # Every launch adds what ran in it, a cut one too: the first
            # one in a trace may be cut off at the start.
            i = bisect.bisect_left(starts, s0)
            j = bisect.bisect_left(starts, s0 + d)
            m["ops"].update(shorts.setdefault(e[0], _short(e[0]))
                            for e in by_start[i:j])
        if p is devices[0] and busy_iv:
            # Marked: the whole window less what ran, its two ends too.
            # Unmarked: what lies between the first and the last operation.
            whole = (lo, hi) if marked else (busy_iv[0][0], busy_iv[-1][1])
            gaps = subtract([whole], busy_iv)
    n_dev = max(len(devices), 1)
    for m in modules.values():  # a program across chips launches on each
        m["launches"] //= n_dev
        m["total_s"] /= n_dev
        m["ops"] = sorted(m["ops"])
    devs = list(per_device.values())
    # Every operation's time by name, a chip's mean, longest first; the
    # printed breakdown keeps the first `top`.
    op_s = {n: s / n_dev for n, s in sorted(
        ops_total.items(), key=lambda kv: -kv[1])}
    return {
        "window_s": window_s,
        "window_marked": marked,
        "n_devices": len(devices),
        "busy_s": sum(d["busy_s"] for d in devs) / n_dev,
        "busy_unclipped_s": sum(d["busy_unclipped_s"] for d in devs) / n_dev,
        "outside_s": [sum(d["outside_s"][i] for d in devs) / n_dev
                      for i in (0, 1)],
        "devices": per_device,
        "modules": modules,
        "op_s": op_s,
        "device_ops": [[n, s] for n, s in list(op_s.items())[:top]],
        "by_opcode": [[n, s / n_dev] for n, s in sorted(
            by_opcode.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": _name_gaps(gaps, host_lines, top),
    }


def _name_gaps(gaps: Sequence[Interval], host_lines: Sequence[Dict],
               top: int) -> List[List]:
    """The longest idle gaps of the first device, each named by the host
    event that covers most of it (innermost where several nest), summed by
    name. A gap no host event overlaps is `(no host event)`; the harness's
    own marks, one of which spans every gap, are not the host's doing."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:200]
    by_name: Dict[str, float] = defaultdict(float)
    for gs, ge in longest:
        best, best_cover, best_len = "(no host event)", 0.0, float("inf")
        for line in host_lines:
            for name, s, d in line["events"]:
                cover = min(ge, s + d) - max(gs, s)
                if cover <= 0 or name in MARKS:
                    continue
                # Most of the gap covered; among equals the shorter
                # (more specific) span names it.
                if (cover > best_cover * 1.05
                        or (cover >= best_cover * 0.95 and d < best_len)):
                    best, best_cover, best_len = name, cover, d
        by_name[_short(best)] += ge - gs
    return [[n, s] for n, s in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:top]]


def reduce_dir(trace_dir: str, window_s: Optional[float] = None,
               keep: bool = False) -> Dict:
    """Reduce the newest trace under `trace_dir`; `{"error": reason}` where
    there is none or it holds no window (the run then has no result:
    `run.trace_fault`)."""
    path = find_xplane(trace_dir)
    if path is None:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    planes = load(path)
    try:
        out = reduce(planes, window_s)
    except ValueError as e:
        return {"error": f"{e} ({path})"}
    out["describe"] = describe(planes)
    out["xplane_bytes"] = os.path.getsize(path)
    if not keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out
