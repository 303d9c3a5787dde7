"""Doc-vs-artifact claim checker.

Perf numbers quoted in README.md / COMPONENTS.md drift from the
committed JSON artifacts as rounds iterate (flagged in two consecutive
verdicts) — and one stale number means a reader can trust none of them.
This tool pins every quoted number to its artifact: each CLAIM names a
doc file, a regex whose group(1) captures the quoted value, a getter
into the artifact JSON, and a tolerance. The test suite runs it
(test_bench_harness.py), so a doc edit that outruns its artifact — or a
regenerated artifact that outruns the docs — fails CI.

Run directly for a report:  python tools/check_claims.py
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Callable, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name: str):
    with open(os.path.join(REPO, name)) as f:
        return json.load(f)


def _bench_core(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_CORE.json"):
            if metric_sub in e.get("benchmark", ""):
                return e[field]
        raise KeyError(f"no BENCH_CORE entry matching {metric_sub!r}")
    return get


def _bench_scale_broadcast(nodes: int, field: str):
    def get():
        for e in _load("BENCH_SCALE.json"):
            if e.get("probe", "").endswith(f"broadcast to {nodes} nodes"):
                return e[field]
        raise KeyError(f"no broadcast-to-{nodes} probe in BENCH_SCALE.json")
    return get


def _bench_scale_tasks(n: int, field: str):
    def get():
        for e in _load("BENCH_SCALE.json"):
            if e.get("probe") == "cost_curves":
                for pt in e["tasks"]:
                    if pt["n"] == n:
                        return pt[field]
        raise KeyError(f"no tasks curve point n={n} in BENCH_SCALE.json")
    return get


def _bench_scale_probe(probe: str, field: str):
    def get():
        for e in _load("BENCH_SCALE.json"):
            if e.get("probe") == probe:
                return e[field]
        raise KeyError(f"no probe {probe!r} in BENCH_SCALE.json")
    return get


def _bench_scale_lifecycle(n: int, field: str, phase: str = None):
    """Lifecycle decomposition point n=<n>: a top-level field, or one
    phase's mean µs when ``phase`` is given."""
    def get():
        for e in _load("BENCH_SCALE.json"):
            if e.get("probe") == "lifecycle phase decomposition":
                for pt in e["points"]:
                    if pt["n"] == n:
                        return pt["phases_us"][phase] if phase else pt[field]
        raise KeyError(
            f"no lifecycle decomposition point n={n} in BENCH_SCALE.json"
        )
    return get


def _bench_infer(metric_sub: str, field: str, **where):
    def get():
        for e in _load("BENCH_INFER.json"):
            if metric_sub in e.get("metric", "") and all(
                e.get(k) == v for k, v in where.items()
            ):
                return e[field]
        raise KeyError(
            f"no BENCH_INFER entry matching {metric_sub!r} {where}"
        )
    return get


def _bench_infer_r5_implied_step_ms():
    """The r5 TPU continuous-batching probe ran 4 slots; its implied
    steady-state engine step is slots / throughput."""
    def get():
        tps = _bench_infer("continuous batching tokens/s/chip",
                           "continuous_tokens_per_s")()
        return 4.0 / tps * 1e3
    return get


def _bench_data(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_DATA.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_DATA entry matching {metric_sub!r}")
    return get


def _bench_obs(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_OBS.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_OBS entry matching {metric_sub!r}")
    return get


def _bench_serve_obs(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_SERVE_OBS.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_SERVE_OBS entry matching {metric_sub!r}")
    return get


def _bench_ft(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_FT.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_FT entry matching {metric_sub!r}")
    return get


def _bench_serve_ft(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_SERVE_FT.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_SERVE_FT entry matching {metric_sub!r}")
    return get


def _bench_multitenant(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_MULTITENANT.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(
            f"no BENCH_MULTITENANT entry matching {metric_sub!r}"
        )
    return get


def _bench_collective(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_COLLECTIVE.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_COLLECTIVE entry matching {metric_sub!r}")
    return get


def _bench_serve_macro(metric_sub: str, field: str):
    def get():
        for e in _load("BENCH_SERVE_MACRO.json"):
            if metric_sub in e.get("metric", ""):
                return e[field]
        raise KeyError(f"no BENCH_SERVE_MACRO entry matching {metric_sub!r}")
    return get


def _bench_r(field: str, sub: str = None):
    def get():
        d = _load("BENCH_TPU_LIVE.json")
        if sub:
            d = d[sub]
        return d[field]
    return get


def _rtlint_rule_count():
    def get():
        if REPO not in sys.path:  # direct `python tools/check_claims.py`
            sys.path.insert(0, REPO)
        from tools.rtlint.rules import ALL_RULES

        return len(ALL_RULES)
    return get


def _rtlint_baseline_size():
    def get():
        data = _load(os.path.join("tools", "rtlint", "baseline.json"))
        return sum(data["findings"].values())
    return get


_RTLINT_RUN = {}


def _rtlint_run():
    """One live engine run over the default targets, shared by every
    suppression-count claim (MIGRATION.md's triage table must track
    the code, not a hand-maintained tally)."""
    if not _RTLINT_RUN:
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from tools.rtlint import DEFAULT_TARGETS, analyze_paths

        targets = [os.path.join(REPO, t) for t in DEFAULT_TARGETS
                   if "*" not in t]
        targets += glob.glob(os.path.join(REPO, "bench_*.py"))
        _RTLINT_RUN["result"] = analyze_paths(targets, root=REPO)
    return _RTLINT_RUN["result"]


def _rtlint_suppressed(rule: str = None):
    def get():
        res = _rtlint_run()
        if rule is None:
            return sum(res.suppressed.values())
        return res.suppressed.get(rule, 0)
    return get


def _rtlint_found(rule: str):
    def get():
        return sum(1 for f in _rtlint_run().findings if f.rule == rule)
    return get


class Claim:
    def __init__(self, doc: str, pattern: str, getter: Callable,
                 rel_tol: float = 0.15, scale: float = 1.0,
                 note: str = ""):
        self.doc = doc
        self.pattern = pattern
        self.getter = getter
        self.rel_tol = rel_tol
        self.scale = scale  # doc units -> artifact units (k -> 1000)
        self.note = note

    def check(self) -> List[str]:
        """Returns a list of problem strings (empty = ok)."""
        path = os.path.join(REPO, self.doc)
        text = open(path).read()
        matches = re.findall(self.pattern, text)
        if not matches:
            return [f"{self.doc}: pattern {self.pattern!r} not found "
                    f"(doc rewritten? update tools/check_claims.py)"]
        try:
            actual = float(self.getter())
        except (KeyError, FileNotFoundError) as e:
            return [f"{self.doc}: artifact lookup failed for "
                    f"{self.pattern!r}: {e}"]
        problems = []
        for m in matches:
            quoted = float(m) * self.scale
            if actual == 0:
                ok = quoted == 0
            else:
                ok = abs(quoted - actual) / abs(actual) <= self.rel_tol
            if not ok:
                problems.append(
                    f"{self.doc}: quoted {quoted:g} vs artifact "
                    f"{actual:g} (pattern {self.pattern!r}"
                    f"{'; ' + self.note if self.note else ''})"
                )
        return problems


CLAIMS = [
    # README headline flagship numbers <- live TPU artifact.
    Claim("README.md", r"MFU (0\.\d+)", _bench_r("mfu"), rel_tol=0.08),
    Claim("README.md", r"(\d+\.\d+)k tokens/s/chip", _bench_r("value"),
          scale=1000.0, rel_tol=0.08),
    # README pipelined throughput <- BENCH_CORE.
    Claim("README.md", r"~(\d+\.?\d*)k pipelined tasks/s",
          _bench_core("tasks async", "ops_per_s"), scale=1000.0,
          rel_tol=0.2),
    Claim("README.md", r"~(\d+\.?\d*)k pipelined actor calls",
          _bench_core("actor calls async", "ops_per_s"), scale=1000.0,
          rel_tol=0.2),
    Claim("README.md", r"actor register\+ready\+call ~(\d+)/s",
          _bench_core("register+ready", "ops_per_s"), rel_tol=0.35),
    # COMPONENTS direct-transport tasks/s <- BENCH_CORE.
    Claim("COMPONENTS.md", r"~(\d+\.?\d*)k pipelined tasks/s",
          _bench_core("tasks async", "ops_per_s"), scale=1000.0,
          rel_tol=0.2),
    # COMPONENTS broadcast wall clock <- BENCH_SCALE steady-state.
    Claim("COMPONENTS.md", r"256MB->4 nodes (\d+\.?\d*)s",
          _bench_scale_broadcast(4, "wall_s"), rel_tol=0.5,
          note="steady-state broadcast wall"),
    Claim("README.md", r"\*\*(0\.\d+)s to 4\s*\n?\s*nodes",
          _bench_scale_broadcast(4, "wall_s"), rel_tol=0.5),
    Claim("README.md", r"(\d+)µs/task on one core",
          _bench_scale_tasks(1_000_000, "us_per_task"), rel_tol=0.3),
    # COMPONENTS flagship MFU <- live TPU artifact.
    Claim("COMPONENTS.md", r"MFU (0\.\d+)", _bench_r("mfu"), rel_tol=0.08),
    # Serving-engine hot-loop numbers <- BENCH_INFER stepwise probe.
    # Quoted in MIGRATION.md; tight tolerance — docs and artifact are
    # committed together.
    Claim("MIGRATION.md", r"engine step (\d+\.\d+) ms",
          _bench_infer("engine step breakdown", "engine_step_ms"),
          rel_tol=0.02),
    Claim("MIGRATION.md", r"raw decode floor (\d+\.\d+) ms",
          _bench_infer("engine step breakdown", "raw_decode_step_ms"),
          rel_tol=0.02),
    Claim("MIGRATION.md", r"throughput ratio (\d+\.\d+)",
          _bench_infer("engine vs raw decode throughput",
                       "engine_vs_raw_throughput_ratio"),
          rel_tol=0.02),
    Claim("MIGRATION.md", r"pins (\d+) compiles",
          _bench_infer("engine step breakdown", "compiles_in_window")),
    Claim("MIGRATION.md", r"and (\d+) param uploads",
          _bench_infer("engine step breakdown",
                       "param_uploads_in_window")),
    Claim("MIGRATION.md", r"implied (\d+\.\d+) ms/step",
          _bench_infer_r5_implied_step_ms(), rel_tol=0.02,
          note="r5 engine step implied by 4 slots / continuous tok/s"),
    Claim("MIGRATION.md", r"a (\d+\.\d+) ms raw batch-8 decode",
          _bench_infer("llama2(0.8B) decode", "ms_per_decode_step",
                       batch=8),
          rel_tol=0.02),
    # Input-pipeline feed numbers <- BENCH_DATA.json (bench_data.py).
    # Tight tolerance: docs and artifact are committed together.
    Claim("MIGRATION.md", r"serial feed (\d+\.\d+) batches/s",
          _bench_data("feed throughput", "serial_batches_per_s"),
          rel_tol=0.02),
    Claim("MIGRATION.md", r"pipelined (\d+\.\d+) batches/s",
          _bench_data("feed throughput", "pipelined_batches_per_s"),
          rel_tol=0.02),
    Claim("MIGRATION.md", r"feed speedup (\d+\.\d+)x",
          _bench_data("feed throughput", "speedup"), rel_tol=0.02),
    Claim("MIGRATION.md", r"overlap ratio (0\.\d+)",
          _bench_data("feed throughput", "overlap_ratio"), rel_tol=0.02),
    Claim("MIGRATION.md", r"resolves in\s*\n?\s*(\d+\.\d+) probe rounds",
          _bench_data("multi-ref get", "parallel_probe_rounds"),
          rel_tol=0.1),
    Claim("MIGRATION.md", r"vs (\d+\.\d+) serially",
          _bench_data("multi-ref get", "serial_probe_rounds"),
          rel_tol=0.1),
    Claim("MIGRATION.md", r"multi-ref speedup (\d+\.\d+)x",
          _bench_data("multi-ref get", "speedup"), rel_tol=0.02),
    # Fault-tolerance latencies <- BENCH_FT.json (bench_ft.py). Loose
    # tolerances: these are wall-clock timings of control-plane paths on
    # a shared CI box (detection additionally quantizes to the 50ms poll
    # cadence).
    Claim("MIGRATION.md", r"kill-to-detection ~(\d+\.?\d*) ms",
          _bench_ft("kill-to-detection", "detect_ms"), rel_tol=0.5),
    Claim("MIGRATION.md", r"gang rebuild ~(\d+\.?\d*) ms",
          _bench_ft("gang rebuild", "rebuild_s"), scale=0.001,
          rel_tol=1.0, note="pipelined actor respawn; noisy at ~20ms"),
    Claim("MIGRATION.md", r"deadline trips in (\d+\.\d+) s",
          _bench_ft("collective timeout trip", "trip_s"), rel_tol=0.1),
    # Flight-recorder overhead <- BENCH_OBS.json (bench_obs.py). Loose
    # tolerances: sub-% overhead measured on a shared CI box; the CLAIM
    # is "well under 2%", the exact digits wobble run to run.
    Claim("MIGRATION.md", r"emission\) adds (\d+\.\d+)%",
          _bench_obs("step recorder overhead", "overhead_pct"),
          rel_tol=2.0, note="paired-median overhead, noisy at sub-%"),
    Claim("MIGRATION.md", r"recorder adds (\d+\.\d+) µs/step",
          _bench_obs("step recorder overhead", "recorder_cost_us_per_step"),
          rel_tol=1.0),
    Claim("MIGRATION.md", r"empty-step floor of (\d+\.\d+) µs",
          _bench_obs("recorder cost, empty steps", "cost_us_per_step"),
          rel_tol=1.0),
    Claim("MIGRATION.md", r"(\d+\.\d+) ms at 256 live arrays",
          _bench_obs("memory accountant sample", "sample_ms"),
          rel_tol=1.0),
    # Cluster black box <- BENCH_OBS.json journal probes. The step-wall
    # delta hovers around zero on a shared box, so the doc quotes the
    # gate, not the digit; these pin the stable numbers.
    Claim("MIGRATION.md", r"one `emit\(\)` costs (\d+\.\d+) µs",
          _bench_obs("journal emit cost", "emit_us"),
          rel_tol=1.0, note="µs micro-bench, noisy on a shared box"),
    Claim("MIGRATION.md", r"\((\d+) steps per arm, interleaved",
          _bench_obs("journal overhead", "steps_per_arm"), rel_tol=0.0),
    Claim("MIGRATION.md", r"(\d+)-emit probe",
          _bench_obs("journal emit cost", "emits"), rel_tol=0.0),
    Claim("MIGRATION.md", r"`RT_JOURNAL_RING` \(default (\d+)\)",
          _bench_obs("journal emit cost", "ring"), rel_tol=0.0),
    # Request observatory <- BENCH_SERVE_OBS.json (bench_serve_obs.py).
    # The decode-overhead median hovers around zero on a shared box, so
    # the doc quotes the gate, not the digit; these pin the stable
    # numbers.
    Claim("MIGRATION.md", r"(\d+\.\d+) µs of\s*\n?\s*bookkeeping per request",
          _bench_serve_obs("observatory cost, synthetic",
                           "cost_us_per_request"),
          rel_tol=1.0, note="µs micro-bench, noisy on a shared box"),
    Claim("MIGRATION.md", r"median of (\d+)\s*\n?\s*paired",
          _bench_serve_obs("steady-state decode overhead", "pairs"),
          rel_tol=0.0),
    Claim("MIGRATION.md", r"explains (\d+\.\d+) of\s*\n?\s*each request",
          _bench_serve_obs("phase-sum fraction", "mean_fraction"),
          rel_tol=0.02),
    Claim("MIGRATION.md", r"a (\d+\.\d+) s\s*\n?\s*chaos-injected prefill",
          _bench_serve_obs("HOL watchdog", "injected_prefill_s"),
          rel_tol=0.0),
    Claim("MIGRATION.md", r"as (\d+\.\d+) blocked slot-seconds",
          _bench_serve_obs("HOL watchdog", "blocked_slot_seconds"),
          rel_tol=0.25, note="injected 0.2s + one real prefill pass"),
    # Serve survival plane <- BENCH_SERVE_FT.json (bench_serve_ft.py).
    # Wall-clock probes on a shared box get loose tolerances; the zero
    # lost-request pins are exact — any loss must fail the doc check.
    Claim("MIGRATION.md", r"shed decision costs (\d+\.\d+) µs",
          _bench_serve_ft("shed decision latency", "shed_p50_us"),
          rel_tol=1.0, note="µs micro-bench, noisy on a shared box"),
    Claim("MIGRATION.md", r"sheds every request\s*\n?\s*with a "
                          r"(\d+\.\d+) ms p99",
          _bench_serve_ft("shed decision latency", "shed_p99_ms"),
          rel_tol=1.0, note="p99 of a µs-scale decision"),
    Claim("MIGRATION.md", r"p99 TTFT at (\d+\.\d+)× the",
          _bench_serve_ft("replica chaos", "chaos_over_baseline_p99"),
          rel_tol=1.0, note="ratio hovers just above 1 on a quiet box"),
    Claim("MIGRATION.md", r"drains in (\d+\.\d+) s median",
          _bench_serve_ft("graceful drain", "drain_p50_s"), rel_tol=0.5),
    Claim("MIGRATION.md", r"answers again in\s*\n?\s*(\d+\.\d+) s",
          _bench_serve_ft("controller kill+restart",
                          "controller_recovery_s"),
          rel_tol=2.0, note="named-actor restart + checkpoint restore"),
    Claim("MIGRATION.md", r"traffic loses (\d+) requests",
          _bench_serve_ft("controller kill+restart",
                          "requests_failed"), rel_tol=0.0),
    Claim("MIGRATION.md", r"with (\d+) lost non-shed requests",
          _bench_serve_ft("survival plane summary",
                          "lost_requests_total"), rel_tol=0.0),
    # Multi-tenancy / preemption <- BENCH_MULTITENANT.json
    # (bench_multitenant.py). Wall-clock probes get loose tolerances;
    # the zero-lost pin is exact, and the hard-kill latency is grace-
    # dominated so it stays fairly tight.
    Claim("MIGRATION.md", r"spike is\s*\n?\s*answering in (\d+\.\d+) s",
          _bench_multitenant("graceful reclamation",
                             "spike_deploy_to_first_response_s"),
          rel_tol=1.0, note="drain+checkpoint+respawn wall clock"),
    Claim("MIGRATION.md", r"places (\d+\.\d+) s after the\s*\n?\s*claim",
          _bench_multitenant("hard-kill deadline",
                             "spike_wait_to_placed_s"),
          rel_tol=0.4, note="grace deadline (3 s) + kill/force-remove"),
    Claim("MIGRATION.md", r"saw\s*\n?\s*(\d+) lost non-shed",
          _bench_multitenant("three-tenant SLO accounting",
                             "lost_non_shed"), rel_tol=0.0),
    # Elastic training <- the elastic-vs-evict probe of the same
    # artifact. Steps lost and the step target are exact pins; the
    # goodput ratio is wall-clock so it gets a loose tolerance.
    Claim("MIGRATION.md", r"holds them for (\d+) s",
          _bench_multitenant("elastic resize", "chips_held_s"),
          rel_tol=0.0),
    Claim("MIGRATION.md", r"finished all (\d+)\s*\n?\s*steps",
          _bench_multitenant("elastic resize", "steps"), rel_tol=0.0),
    Claim("MIGRATION.md", r"losing (\d+) steps",
          lambda: _bench_multitenant("elastic resize", "elastic")()
          ["steps_lost"], rel_tol=0.0),
    Claim("MIGRATION.md", r"delivered (\d+\.\d+)× the goodput",
          _bench_multitenant("elastic resize", "goodput_ratio"),
          rel_tol=0.5, note="wall-clock dependent; gate is > 1.0"),
    # Static-analysis section <- rtlint itself. Exact pins (rel_tol=0):
    # adding a rule or regenerating the baseline must update the doc.
    Claim("MIGRATION.md", r"lint pass\s*\n?\s*with (\d+) rules",
          _rtlint_rule_count(), rel_tol=0.0),
    Claim("MIGRATION.md", r"holds (\d+) known findings",
          _rtlint_baseline_size(), rel_tol=0.0),
    # v2 dogfood triage table <- a live engine run over the default
    # targets (exact pins: drift means a suppression was added or
    # removed without updating the doc).
    Claim("MIGRATION.md", r"RT008: (\d+) suppressed",
          _rtlint_suppressed("RT008"), rel_tol=0.0),
    Claim("MIGRATION.md", r"RT009: (\d+) suppressed",
          _rtlint_suppressed("RT009"), rel_tol=0.0),
    Claim("MIGRATION.md", r"RT010: (\d+) suppressed",
          _rtlint_suppressed("RT010"), rel_tol=0.0),
    Claim("MIGRATION.md", r"RT011: (\d+) suppressed",
          _rtlint_suppressed("RT011"), rel_tol=0.0),
    Claim("MIGRATION.md", r"RT012: (\d+) findings",
          _rtlint_found("RT012"), rel_tol=0.0),
    Claim("MIGRATION.md", r"RT013: (\d+) suppressed",
          _rtlint_suppressed("RT013"), rel_tol=0.0),
    Claim("MIGRATION.md", r"suppresses (\d+) findings across",
          _rtlint_suppressed(), rel_tol=0.0),
    Claim("MIGRATION.md", r"carries (\d+) baselined findings",
          _rtlint_baseline_size(), rel_tol=0.0),
    # Control-plane profiler <- BENCH_SCALE.json lifecycle probes.
    # Loose tolerances on the absolute µs (wall timings on a shared
    # 1-core box); tight on the coverage fraction, which is the claim.
    Claim("MIGRATION.md", r"explain (0\.\d+)\s*\n?\s*of the mean",
          _bench_scale_lifecycle(1000, "phase_sum_fraction_of_e2e"),
          rel_tol=0.05),
    Claim("MIGRATION.md", r"transport at ~(\d+) µs",
          _bench_scale_lifecycle(1000, None, phase="transport"),
          rel_tol=0.5),
    Claim("MIGRATION.md", r"of a (\d+) µs\s*\n?\s*mean submit",
          _bench_scale_lifecycle(1000, "us_per_task"), rel_tol=0.5),
    Claim("MIGRATION.md", r"costs (\d+\.?\d*) GCS round-trips",
          _bench_scale_probe("gcs rpcs per actor create",
                             "gcs_rpcs_per_actor_create"),
          rel_tol=0.3),
    Claim("MIGRATION.md", r"guard ops cost (\d+\.\d+) µs",
          _bench_scale_probe("lifecycle off-path overhead",
                             "fastpath_ops_us_per_task"),
          rel_tol=1.5, note="sub-µs micro-bench, noisy on a shared box"),
    # Topology-native collectives <- BENCH_COLLECTIVE.json
    # (bench_collective.py). Byte counts and the cost-model crossover
    # are deterministic (tight pins); the latency speedup is wall clock
    # on a shared box (loose).
    Claim("MIGRATION.md", r"crossover at (\d+) KiB",
          _bench_collective("algorithm selection", "crossover_KiB"),
          rel_tol=0.0),
    Claim("MIGRATION.md", r"moves (0\.\d+) of the flat ring's DCN bytes",
          _bench_collective("sharded-hier DCN bytes", "ratio"),
          rel_tol=0.05),
    Claim("MIGRATION.md", r"cuts DCN wire bytes (\d+\.\d+)×",
          _bench_collective("int8 quantized DCN allreduce",
                            "wire_reduction"), rel_tol=0.02),
    Claim("MIGRATION.md", r"max relative error (0\.\d+)",
          _bench_collective("int8 quantized DCN allreduce",
                            "max_rel_error"), rel_tol=0.1),
    Claim("MIGRATION.md", r"to (0\.\d+) over 20 error-feedback steps",
          _bench_collective("int8 quantized DCN allreduce",
                            "ef_mean_error_20_steps"), rel_tol=0.25),
    Claim("MIGRATION.md", r"recursive doubling beats it (\d+\.\d+)×",
          _bench_collective("rd vs ring latency", "speedup"),
          rel_tol=0.5, note="wall-clock ratio under injected latency"),
    # -- serve macro (cluster witness) claims -> BENCH_SERVE_MACRO.json
    Claim("MIGRATION.md", r"sustains (\d+\.\d+) QPS achieved",
          _bench_serve_macro("sustained macro", "achieved_qps"),
          rel_tol=0.25),
    Claim("MIGRATION.md", r"against (\d+\.\d+)\s*\n?\s*offered",
          _bench_serve_macro("sustained macro", "offered_qps"),
          rel_tol=0.25),
    Claim("MIGRATION.md", r"unattributed gap p99 (\d+\.\d+) ms",
          _bench_serve_macro("sustained macro", "gap_p99_ms"),
          rel_tol=3.0, note="ms-scale dispatch jitter run to run"),
    Claim("MIGRATION.md", r"gap fraction p99 (0\.\d+) against",
          _bench_serve_macro("sustained macro", "gap_fraction_p99"),
          rel_tol=3.0, note="ms-scale dispatch jitter run to run"),
    Claim("MIGRATION.md", r"(\d+) lost non-shed\s*\n?\s*requests",
          _bench_serve_macro("chaos macro", "lost_non_shed"),
          rel_tol=0.0),
    Claim("MIGRATION.md", r"out of (\d+), client TTFB",
          _bench_serve_macro("chaos macro", "issued"), rel_tol=0.0),
    Claim("MIGRATION.md", r"client TTFB p99 held at (\d+) ms",
          _bench_serve_macro("chaos macro", "client_ttfb_p99_ms"),
          rel_tol=1.0),
    Claim("MIGRATION.md", r"after the kill was (\d+\.\d+) s",
          _bench_serve_macro("chaos macro", "recovery_s"),
          rel_tol=3.0, note="respawn timing varies run to run"),
    Claim("MIGRATION.md", r"tracked the ramp to (\d+) replicas",
          _bench_serve_macro("chaos macro", "autoscaler_max_target"),
          rel_tol=0.34, note="2-4 replica band is healthy"),
    Claim("MIGRATION.md", r"regenerates the (\d+)-request",
          _bench_serve_macro("record/replay", "requests"),
          rel_tol=0.0, note="pure function of the committed seed"),
]


def check_all() -> List[str]:
    problems: List[str] = []
    for claim in CLAIMS:
        problems.extend(claim.check())
    return problems


def main() -> int:
    problems = check_all()
    if problems:
        for p in problems:
            print(f"STALE: {p}")
        return 1
    print(f"all {len(CLAIMS)} doc claims match their artifacts")
    return 0


if __name__ == "__main__":
    sys.exit(main())
