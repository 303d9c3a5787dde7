"""Continuous batching for LLM serving.

The reference's dynamic batcher (python/ray/serve/batching.py) coalesces
requests that ARRIVE together; a static batch then decodes in lockstep
until every member finishes, so at mixed arrival times most of the chip
sits idle (a 1-token straggler pins the whole batch). This module goes
past it: a decode loop over a fixed number of SLOTS, backed by the paged
KV cache (`ray_tpu.serve.paged_kv`, which also holds the step programs
this module jits), where requests join at any step boundary (prefill
interleaved between decode steps), emit tokens as they are produced, and
free their slot the moment they finish — the vLLM-style iteration-level
scheduling, built TPU-first:

  * A model with recurrent layers (a hybrid: Mamba-2 layers, gated short
    convolutions or channel-gated delta-rule layers among attention
    layers, its MLPs dense or routed, all of a layer's experts or one
    chip's share of them) gives every slot a row of fixed-size state
    beside its pages, in a second pool that the same step programs carry
    and update in place;
    where it has experts, `stats()` reports their routing counters
    (`"moe"`) beside the recurrent ones (`"ssm"`). What follows from the
    model and is no option: such a model serves without a prefix cache
    (a prompt cannot resume below a shared prefix without the state at
    that boundary, and no snapshots are kept) and without tensor
    parallelism (the mixer is not partitioned).
  * A block-diffusion model (`cfg.block_length`: SDAR's generation by
    diffusion over blocks) fills in a block of B positions a slot at once:
    a step of the loop is then a PASS of every slot's block
    (`paged_kv.block_pass_paged`), which hands a slot nothing (a denoising
    pass filled some of its block's masked positions) or B tokens (the
    block's commit pass). A slot's block lives on the device and its phase
    is counted on the host; prefill covers a prompt's whole blocks and
    emits no token. `stats()["diffusion"]` counts the passes.
  * A model with window layers (`cfg.sliding_window_layout`: SmallThinker's
    three layers in four) keeps those layers' keys and values in a RING a
    slot (`paged_kv.init_ring_pool`: the window and one prefill chunk,
    written at `position mod ring`), beside the full layers' pages and
    tables. A slot owns its ring: admission reserves the full layers' pages
    by the request's footprint and nothing for the ring. No prefix cache
    (what lay in a ring at a prefix's end is gone once the slot runs on),
    one row of a pass a prompt, one chip.
  * Static shapes everywhere: the decode step is jitted ONCE for the
    slot count and prompts prefill in fixed-size CHUNKS, one PASS of
    them between decode steps: a pass is one program over one row of a
    chunk or over up to `PASS_ROWS` of them (at most `PASS_TOKENS`
    tokens), of several slots or of one long prompt, so that they share
    one read of the weights, and a long prompt never stalls other slots'
    decoding for more than a pass.
    Compilation count is bounded and none happens mid-traffic after
    warmup.
  * Per-slot sequence lengths live in device memory; attention masks by
    each slot's own length, so one batched decode serves slots whose
    sequences started at different times.
  * Cache buffers are donated to the step and ride whole in the layer
    scan's carry (`paged_kv._scan_layers`), each layer writing and
    reading them at its own index, so decode and prefill update the KV
    cache in place. Donation alone does not do that: scanned over as
    the scan's inputs and stacked back as its outputs, a cache is two
    buffers and every step copies all of it twice.
  * The steady-state hot loop does ZERO avoidable host<->device traffic
    per step: sampling params and the active mask are device-resident
    (re-uploaded only on slot admission/eviction), step outputs come
    back through an async double-buffered copy (dispatch step k+1,
    drain step k's already-landed buffer), both decode variants compile
    at engine construction (greedy<->sampled traffic flips never
    compile mid-serving), and stats() exposes the loop's phase ledger
    (work/wait/other ms per turn, compile and upload counters) that
    proves it — the T3-style overlap discipline (arXiv:2401.16677)
    applied to decode, with EQuARX-style step decomposition
    (arXiv:2506.17615).

Reference provenance: serve/batching.py (the mechanism surpassed);
BASELINE.json configs[4] (the serving north-star).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import chaos
from ray_tpu._private.config import get_config
from ray_tpu.exceptions import (
    PromptTooLongError,
    RequestCancelledError,
    ServeOverloadedError,
)
from ray_tpu.serve import context as request_context
from ray_tpu.serve import observatory
from ray_tpu.serve import paged_kv
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.util.compile_cache import compile_events
from ray_tpu.util.device_peaks import device_report

# The most rows a prefill pass holds. A pass is compiled at two widths,
# one row and the widest, and takes the smaller that holds the rows it
# owes, padded with inert rows (`n_valid` 0): a lone short prompt pays for
# one row, not for the widest pass. No width between them: every program
# is a second of every start of the engine (read from the compile cache
# and loaded), and at 64 tokens a row two rows in a four-row pass cost a
# quarter more than in a pass of their own (20.9 ms against 16.4 on a
# v5e at Qwen3-4B's widths).
PASS_ROWS = 4
# The most tokens a pass may hold, whatever the chunk: a pass stands
# between two decode steps, and rows share a read of the weights only
# while the products wait for it. On a v5e a dense model's products catch
# up with the read at about 250 tokens (four rows of 64 cost 1.46 of one,
# two of 256 cost 2.04 of one at the hybrid's widths) and a sparse
# model's later (two rows of 256 cost 1.35 of one at OLMoE's, four 1.99),
# while an inert row of a pass past that point costs what a real one does.
PASS_TOKENS = 512

logger = logging.getLogger("ray_tpu.serve.llm")

_metrics_lock = threading.Lock()
_metrics: Optional[Dict] = None


def _engine_metrics() -> Dict:
    """Module-level serving metrics (ray_tpu.util.metrics): one set per
    process, shared by every engine, flushed to GCS/Prometheus by the
    metrics flusher. Created lazily so importing llm.py never spins up
    the flusher thread."""
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util import metrics as _mx
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            _metrics = {
                # Request-level latency (flight recorder): TTFT is
                # submit->first token (queue wait + prefill), TPOT the
                # mean inter-token interval after the first. Seconds,
                # sub-ms-resolving boundaries.
                "ttft_s": Histogram(
                    "serve_llm_ttft_seconds",
                    "Time to first token: submit() to the first pushed "
                    "token, per request",
                    # Wide tail: queue wait under macro load pushes TTFT
                    # p99 multi-second; don't clamp it into +Inf.
                    boundaries=_mx.LATENCY_BOUNDARIES_WIDE,
                ),
                "tpot_s": Histogram(
                    "serve_llm_tpot_seconds",
                    "Time per output token after the first (decode-rate "
                    "inverse), per finished request",
                    boundaries=_mx.LATENCY_BOUNDARIES,
                ),
                "occupancy": Gauge(
                    "serve_llm_batch_occupancy",
                    "Decoding slots in use / total slots, sampled every "
                    "engine step (how full the continuous batch runs)",
                ),
                "tokens_per_pass": Gauge(
                    "serve_llm_tokens_per_pass",
                    "Generated tokens a block-diffusion engine handed out "
                    "over the live slot-passes it ran, cumulative (a block "
                    "of B tokens costs denoise_steps + 1 passes)",
                ),
                "waiting": Gauge(
                    "serve_llm_waiting_requests",
                    "Requests enqueued but not yet granted a decode slot "
                    "(admission queue depth; the backlog half of the "
                    "autoscaling signal next to occupancy)",
                ),
                "admission_wait_s": Histogram(
                    "serve_llm_admission_wait_seconds",
                    "submit() enqueue to decode-slot grant, per request "
                    "(pure queueing: saturation shows here before TTFT)",
                    boundaries=_mx.LATENCY_BOUNDARIES,
                ),
                "hol_s": Counter(
                    "serve_hol_blocked_seconds_total",
                    "Decode-slot-seconds stalled behind prefill passes "
                    "crossing serve_hol_threshold_s (head-of-line "
                    "blocking attributed to the long prefill causing it)",
                ),
                # Paged KV memory plane (ray_tpu/serve/paged_kv).
                "kv_pages": Gauge(
                    "serve_kv_pages_in_use",
                    "KV page-pool pages currently referenced (request "
                    "block tables + prefix-cache entries), sampled every "
                    "engine step",
                ),
                "prefix_hits": Counter(
                    "serve_prefix_cache_hits_total",
                    "Admissions whose prompt prefix was resident in the "
                    "page-level prefix cache (>= 1 full page shared)",
                ),
                "prefix_misses": Counter(
                    "serve_prefix_cache_misses_total",
                    "Admissions that found no resident prompt prefix "
                    "(every prefill chunk recomputed)",
                ),
                "prefill_skipped": Counter(
                    "serve_prefill_tokens_skipped_total",
                    "Prompt tokens NOT re-prefilled because their pages "
                    "were shared from the prefix cache",
                ),
            }
        return _metrics


def _acquire_timed(lock, t0: Optional[float] = None) -> float:  # rtlint: disable=RT016 — returns holding `lock`, as acquire() does
    """`lock.acquire()` that says what it waited, in seconds: 0.0, and no
    clock read, where the lock was free; else from `t0` (a `perf_counter`
    stamp the caller has taken already, or one taken here) to having it."""
    if lock.acquire(False):
        return 0.0
    if t0 is None:
        t0 = time.perf_counter()
    lock.acquire()
    return time.perf_counter() - t0


class GenerationHandle:
    """Per-request stream: tokens arrive as the engine produces them."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._tokens: deque = deque()
        self._done = False
        self._error: Optional[BaseException] = None
        self._cond = threading.Condition()
        # Engine bookkeeping (set at admission).
        self.prompt: Optional[np.ndarray] = None
        self.max_new_tokens = 0
        self.produced = 0
        self.admitted_at_step = -1
        # Sampling params (0 temperature = greedy).
        self.temperature = 0.0
        self.top_k = 0
        self.top_p = 1.0
        # Latency bookkeeping (engine thread only): submit stamps
        # submitted_at; the first/terminal pushes yield TTFT/TPOT.
        self.submitted_at: Optional[float] = None
        self._first_token_t: Optional[float] = None
        # Observatory stamp card (set by submit() from the request
        # thread's context; engine thread writes marks into it).
        self.obs = None
        # Survival plane (set by submit() from the request-scoped
        # serving context): absolute deadline (0 = none), tenant label
        # for the WFQ admission queue, and the caller-side cancel flag
        # the engine loop polls at step boundaries.
        self.deadline_ts = 0.0
        self.tenant = "default"
        self.cancelled = False

    def cancel(self, reason: str = "client"):
        """Caller-side cancellation: the consumer stops waiting NOW
        (``_fail`` wakes it with RequestCancelledError) and the engine
        loop evicts the slot at the next step boundary — the slot is
        reclaimed without waiting for the sequence to finish."""
        self.cancelled = True
        self._fail(RequestCancelledError(
            f"request {self.request_id} cancelled ({reason})",
            reason=reason, rid=str(self.request_id),
        ))

    # -- engine side --
    def _push(self, token: int, done: bool) -> float:
        """Hand the consumer a token. Returns the seconds the caller
        waited for the condition (a consumer holds it while it takes a
        token): 0.0, and no second stamp, where it was free."""
        now = time.perf_counter()
        first = self._first_token_t is None
        if first:
            self._first_token_t = now
        obs = self.obs
        if obs is not None:
            # The card is written before the token can be taken: a
            # streamed chunk's delivery lag is measured from its push
            # stamp, and the record closes when a poll has the last one
            # (replica.next_chunks).
            obs.push_t.append(now)
            if first:
                obs.marks["first_token"] = now
            if done:
                obs.marks["engine_done"] = now
                obs.tokens_out = self.produced
        waited = _acquire_timed(self._cond, now)
        try:
            self._tokens.append(int(token))
            self._done = self._done or done
            self._cond.notify_all()
        finally:
            self._cond.release()
        # Observe outside the condition: a blocked consumer wakes without
        # waiting on the metrics registry lock.
        m = _engine_metrics()
        if first and self.submitted_at is not None:
            m["ttft_s"].observe(now - self.submitted_at)
        if done and self.produced > 1 and not first:
            m["tpot_s"].observe(
                (now - self._first_token_t) / (self.produced - 1)
            )
        return waited

    def _fail(self, err: BaseException) -> float:
        """Fail the request. Returns the seconds waited for the condition,
        as `_push` does."""
        waited = _acquire_timed(self._cond)
        try:
            if self._done and self._error is None:
                return waited  # finished cleanly first; late cancel/fail is moot
            self._error = err
            self._done = True
            self._cond.notify_all()
        finally:
            self._cond.release()
        return waited

    # -- caller side --
    def __iter__(self):
        taken = 0
        while True:
            with self._cond:
                while not self._tokens and not self._done:
                    self._cond.wait(timeout=60.0)
                if self._error is not None:
                    raise self._error
                if self._tokens:
                    tok = self._tokens.popleft()
                    taken += 1
                    if self.obs is not None:
                        self.obs.taken = taken
                    yield tok
                    continue
                if self._done:
                    return

    def result(self, timeout: float = 120.0) -> list:
        deadline = time.monotonic() + timeout
        out = []
        with self._cond:
            while not self._done:
                rest = deadline - time.monotonic()
                if rest <= 0:
                    raise TimeoutError("generation timed out")
                self._cond.wait(timeout=rest)
            if self._error is not None:
                raise self._error
            out.extend(self._tokens)
            self._tokens.clear()
        return out


# The children of an engine turn, by class: `work` is the loop thread
# doing host work, `wait` is the loop thread blocked on the device.
_TURN_PHASES = {
    "admit": "work",
    "prefill_dispatch": "work",
    "prefill_first_token_wait": "wait",
    "prefill_publish": "work",
    "upload": "work",
    "decode_dispatch": "work",
    "decode_fetch_wait": "wait",
    "distribute": "work",
}


# What a late dry stretch or a stall is told apart by: the phase it was
# seen in, `other` between two phases.
_BY_PHASE = (*_TURN_PHASES, "other")
_DISPATCHES = ("prefill_dispatch", "decode_dispatch")
# A span of a phase past this is a stall: five times the longest healthy
# phase in any cell (56 ms on a v5e: the hybrid's decode step and a
# two-row pass behind one first-token fetch).
STALL_S = 0.25


class _PhaseLedger:
    """Where the engine loop's time goes, on two clocks at once:
    `with ledger(key):` opens `jax.profiler.TraceAnnotation(
    "engine.<key>")` (a host span on the profiler's clock while a session
    is on, about 0.4 us when none is) and adds the elapsed `perf_counter`
    time and a count to the key's totals. Every loop iteration with work
    is one `turn` whose children are `_TURN_PHASES`; what a turn spends
    outside them is `other` = turn - children, so work + wait + other is
    the turn total by construction. `wait_for_work` (idle) lies beside
    the turns and in no total.

    What the loop's thread waits for a lock is counted, and only when it
    waits: `_Held` takes the engine's lock for the loop and, when
    somebody else holds it, stamps the wait into `lock_wait_engine` under
    an `engine.lock_wait` span (so a gap the lock made is named so in a
    trace and not by its phase); a handle says what its `_push` or
    `_fail` waited for its condition and the loop adds it (`lock_waited`).
    `between_turns` is a turn's exit to the next turn's entry with no
    `wait_for_work` between: the publication of the snapshot, in no other
    total. (No `time.thread_time()` beside the stamps: it is a system
    call a read on a sandboxed host, and the loop stalled more often with
    it; `PERF.md` section 6, PR 63, has what it found.)

    The time the loop KNOWS the device dry, by cause. The loop dispatches
    every program in order and hands the ledger a result of the newest
    (`newest`: one no later program takes as a donated argument); the
    device is known drained from the moment the loop sees that result
    complete until its next dispatch returns, inside turns only. Seen
    complete by a blocking fetch that returned (the exit of a `wait`
    phase finds `newest` ready: the loop was made to wait and nothing is
    queued behind what it waited for) the stretch is `drained_fetch`; seen
    by `is_ready()` at any other phase boundary (polled at every entry and
    exit while something is in flight) it is `drained_late`: the host was
    at work when the device finished, somewhere inside the phase at whose
    exit it was seen (`late@<phase>`; `late@other` when seen at an entry,
    between two phases). Both are lower bounds: completion is seen after
    it happened. A dispatch at whose entry the device was drained `late` is
    a `late_dispatch`. In a trace the fetch's stretch is `engine.pass_drain`,
    opened at `prefill_first_token_wait`'s entry (that fetch always drains:
    the pass and its pick are the last things queued) and closed with the
    next dispatch or the turn, and a late one `engine.drained_late` from
    where it is seen to the next dispatch. A span of a phase, or a turn's
    `other`, past `STALL_S` is a stall (`stalls`, `stall@<phase>`) and one
    line of the log.

    Loop-thread-only: stats() reads the copy the loop publishes after
    each turn."""

    def __init__(self):
        keys = ("turn", "wait_for_work", *_TURN_PHASES)
        self.names = {k: f"engine.{k}" for k in keys}
        counted = (*keys, "pass_drain", "drained_fetch", "drained_late",
                   "stalls", *(f"{kind}@{k}" for kind in ("late", "stall")
                               for k in _BY_PHASE))
        counted += ("lock_wait_engine", "lock_wait_handle", "between_turns")
        self.n = dict.fromkeys(counted, 0)
        self.s = dict.fromkeys(counted, 0.0)
        self.prefill_passes = 0  # turns in which _advance_prefills ran
        self.prefill_rows = 0    # real rows (chunks) its passes dispatched
        self.dispatches = 0      # decode steps and prefill passes
        self.late_dispatches = 0
        self.t = 0.0             # the newest stamp any phase read
        # A result of the newest program dispatched (the loop sets it
        # inside the dispatch's phase), None once it was seen complete.
        self.newest = None
        self._dry = ()           # the totals the open dry stretch adds to
        self._dry_t = 0.0        # ... and the stamp it has added up to
        self._children = 0.0     # this turn's children, for its `other`
        self._pass_span = None   # the open `engine.pass_drain`
        self._pass_t0 = 0.0
        self._late_span = None   # the open `engine.drained_late`
        self._turn_end = 0.0     # the last turn's exit, 0.0 once idle

    def __call__(self, key: str) -> "_Phase":
        return _Phase(self, key)

    def lock_waited(self, kind: str, seconds: float):
        """What the loop's thread waited for a lock (`_acquire_timed`):
        one contended acquisition of `lock_wait_<kind>`, or 0.0 and
        nothing to count."""
        if seconds:
            key = f"lock_wait_{kind}"
            self.s[key] += seconds
            self.n[key] += 1

    def snapshot(self) -> Dict:
        return {"n": dict(self.n), "s": dict(self.s),
                "prefill_passes": self.prefill_passes,
                "prefill_rows": self.prefill_rows,
                "dispatches": self.dispatches,
                "late_dispatches": self.late_dispatches}

    def _entered(self, key: str, now: float):
        if key == "prefill_first_token_wait":
            self._pass_t0 = now  # `_Phase` opened the span around its own
            return
        if key == "wait_for_work":
            # Dry for want of requests from here on: in neither cause
            # (the turn's exit added the stretch up; what lies between
            # two turns is in no total).
            self._dry = ()
            self._dispatched(now)
            self.newest = None
            self._turn_end = 0.0
            return
        if key == "turn":
            self._children = 0.0
            self._dry_t = now
            if self._turn_end:
                self.s["between_turns"] += now - self._turn_end
                self.n["between_turns"] += 1
        if self.newest is not None and self.newest.is_ready():
            self._seen(now, "late", "other")

    def _exited(self, key: str, now: float, elapsed: float, failed: bool):
        self.s[key] += elapsed
        self.n[key] += 1
        if key == "wait_for_work":
            return
        if key == "turn":
            # A failed turn is followed by the loop's recovery and a sleep.
            self._turn_end = 0.0 if failed else now
            by, over = "other", elapsed - self._children
            self._accrue(now)
            self._close_pass(now)
        else:
            by, over = key, elapsed
            self._children += elapsed
        if over > STALL_S:
            self.n["stalls"] += 1
            self.s["stalls"] += over
            self.n[f"stall@{by}"] += 1
            self.s[f"stall@{by}"] += over
            logger.warning("engine loop stalled %.0f ms in %s (a healthy "
                           "phase is under %.0f ms)", over * 1e3, by,
                           STALL_S * 1e3)
        if key in _DISPATCHES:
            # Late if entered so: nothing polls inside a dispatch.
            self.dispatches += 1
            self.late_dispatches += "drained_late" in self._dry
            self._dispatched(now)
        elif (self.newest is not None and not failed
              and self.newest.is_ready()):
            if _TURN_PHASES.get(key) == "wait":
                self._seen(now, "fetch", key)
            else:
                self._seen(now, "late", by)

    def _seen(self, now: float, cause: str, by: str):
        """`newest` was found complete: a dry stretch opens."""
        self.newest = None
        self._dry_t = now
        self.n[f"drained_{cause}"] += 1
        if cause == "fetch":
            self._dry = ("drained_fetch",)
            return
        self._dry = ("drained_late", f"late@{by}")
        self.n[f"late@{by}"] += 1
        self._late_span = jax.profiler.TraceAnnotation("engine.drained_late")
        self._late_span.__enter__()

    def _accrue(self, now: float):
        for key in self._dry:
            self.s[key] += now - self._dry_t
        self._dry_t = now

    def _close_pass(self, now: float):
        if self._pass_span is not None:
            self._pass_span.__exit__(None, None, None)
            self._pass_span = None
            self.s["pass_drain"] += now - self._pass_t0
            self.n["pass_drain"] += 1

    def _dispatched(self, now: float):
        """A dispatch returned (or the loop goes idle): the dry stretch
        and its spans end."""
        self._accrue(now)
        self._dry = ()
        self._close_pass(now)
        if self._late_span is not None:
            self._late_span.__exit__(None, None, None)
            self._late_span = None


class _Phase:
    """One span of the ledger (class-based: this runs a dozen times a
    turn)."""

    __slots__ = ("_ledger", "_key", "_span", "_t0")

    def __init__(self, ledger: _PhaseLedger, key: str):
        self._ledger = ledger
        self._key = key

    def __enter__(self):
        ledger, key = self._ledger, self._key
        if key == "prefill_first_token_wait":
            # Around the fetch's own span, not inside it.
            ledger._pass_span = jax.profiler.TraceAnnotation(
                "engine.pass_drain")
            ledger._pass_span.__enter__()
        self._span = jax.profiler.TraceAnnotation(ledger.names[key])
        self._span.__enter__()
        self._t0 = ledger.t = time.perf_counter()
        ledger._entered(key, self._t0)
        return self

    def __exit__(self, *exc):
        ledger = self._ledger
        ledger.t = now = time.perf_counter()
        self._span.__exit__(*exc)
        ledger._exited(self._key, now, now - self._t0, exc[0] is not None)


class _Held:
    """`with _Held(ledger, lock):` is `with lock:` for the loop's thread
    and the engine's lock. Free, it costs the one failed branch; held by
    another thread, the wait is stamped, counted (`lock_wait_engine`) and
    spanned `engine.lock_wait`. Holds no state of its own: one is kept
    and entered again."""

    __slots__ = ("_ledger", "_lock")

    def __init__(self, ledger: _PhaseLedger, lock):
        self._ledger = ledger
        self._lock = lock

    def __enter__(self):  # rtlint: disable=RT016 — a context manager: __exit__ releases
        if not self._lock.acquire(False):
            with jax.profiler.TraceAnnotation("engine.lock_wait"):
                waited = _acquire_timed(self._lock)
            self._ledger.lock_waited("engine", waited)

    def __exit__(self, *exc):
        self._lock.release()


def _timing_of(ledger: Dict) -> Dict:
    """stats()["timing"]'s view of a published ledger: cumulative, so two
    snapshots give a window. Keys carry no dot (readers walk a.b.c)."""
    n, s = ledger["n"], ledger["s"]
    by_class = {"work": 0.0, "wait": 0.0}
    for key, cls in _TURN_PHASES.items():
        by_class[cls] += s[key]

    def of(key):
        return {"n": n[key], "ms_total": s[key] * 1e3}

    return {
        "turns": n["turn"],
        "turn_ms_total": s["turn"] * 1e3,
        "prefill_passes": ledger["prefill_passes"],
        # Dispatches of the prefill program, one a pass, and the real
        # rows (chunks of a prompt) they held: rows over chunks is how
        # many chunks shared a read of the weights.
        "prefill_chunks": n["prefill_dispatch"],
        "prefill_rows": ledger["prefill_rows"],
        "work_ms_total": by_class["work"] * 1e3,
        "wait_ms_total": by_class["wait"] * 1e3,
        "other_ms_total": (s["turn"] - by_class["work"]
                           - by_class["wait"]) * 1e3,
        "phases": {k: of(k) for k in (*_TURN_PHASES, "wait_for_work")},
        # Acquisitions by the loop's thread that found the lock taken
        # (the engine's, a handle's condition), and what they waited.
        "lock_wait": {"engine": of("lock_wait_engine"),
                      "handle": of("lock_wait_handle")},
        # A turn's exit to the next turn's entry, no idle wait between.
        "between_turns": of("between_turns"),
        # The time the loop knew the device dry inside its turns, by
        # cause (a lower bound of the device's idle time beside
        # `wait_for_work`, see `_PhaseLedger`), and the span that names
        # the fetch's stretch in a trace (no child of the turn: it
        # overlaps four of them).
        "drained": {
            "fetch": of("drained_fetch"),
            "late": of("drained_late"),
            "late_by_phase": {k: of(f"late@{k}") for k in _BY_PHASE},
        },
        "pass_drain": of("pass_drain"),
        "dispatches": ledger["dispatches"],
        "late_dispatches": ledger["late_dispatches"],
        "stalls": {**of("stalls"),
                   "by_phase": {k: of(f"stall@{k}") for k in _BY_PHASE}},
    }


def _pick_first_tokens(logits, tokens, slots, temps, top_ks, top_ps, key):
    """A pass's first tokens, picked over all its rows' logits `[P, vocab]`
    (`paged_kv._pick_tokens`: greedy where a row's temperature is 0), and
    the decode loop's token buffer `tokens [slots]` with row `r`'s at
    `slots[r]`; a row whose slot is past the last puts nothing."""
    picked = paged_kv._pick_tokens(logits, temps, top_ks, top_ps, key)
    return picked, tokens.at[slots].set(picked, mode="drop")


class ContinuousBatchingEngine:
    """Iteration-level scheduler over a fixed set of decode slots whose
    K/V rows live in the paged pool (`paged_kv`).

    One background thread runs the decode loop; submit() enqueues a
    request which joins at the next step boundary when a slot frees.
    """

    def __init__(self, params, cfg: TransformerConfig, num_slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 default_max_new_tokens: int = 32, seed: int = 0,
                 mesh=None, prefill_chunk: int = 64,
                 page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 max_queued: Optional[int] = None):
        """mesh: a jax.sharding.Mesh with a "tp" axis for tensor-
        parallel serving (the pods layout): pass params already sharded
        via parallel.shard_params and the engine lays the KV cache out
        with KV heads split over tp — decode collectives then ride ICI
        inside the compiled step (GSPMD inserts them).

        prefill_chunk: prompts prefill in fixed chunks of this many
        tokens, one pass of up to `PASS_ROWS` chunks and
        `PASS_TOKENS` tokens between decode steps — a long prompt never
        stalls other slots' decoding for more than a pass (chunked
        prefill), and prefill compiles once a row count, at warm-up.

        page_size / kv_pages: the KV memory plane (ray_tpu/serve/
        paged_kv): a shared page pool + block tables and a prefix cache
        back the slots. None defers to config (serve_kv_page_size,
        serve_kv_pages; kv_pages 0/None = num_slots x max_len rows and
        the NULL page).

        max_queued: how many requests may wait for a slot before
        submit() sheds; None defers to config
        (serve_max_queued_per_engine), read at each submit."""
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_queued = max_queued
        self.max_len = max_len
        self.eos_id = eos_id
        self.default_max_new_tokens = default_max_new_tokens
        self.mesh = mesh
        self.prefill_chunk = max(1, min(int(prefill_chunk), max_len))
        # The widths of a prefill pass, in rows of one chunk each.
        widest = max(1, min(PASS_ROWS, PASS_TOKENS // self.prefill_chunk))
        self._pass_rows = (1, widest) if widest > 1 else (1,)
        if mesh is not None:
            if "tp" not in mesh.shape:
                raise ValueError(
                    "the engine's mesh needs a \"tp\" axis (KV heads "
                    f"shard over it); got axes {tuple(mesh.shape)}"
                )
            if cfg.n_kv_heads % mesh.shape["tp"]:
                raise ValueError(
                    f"the mesh's tp={mesh.shape['tp']} must divide "
                    f"n_kv_heads={cfg.n_kv_heads}"
                )
            if cfg.layer_pattern and mesh.shape["tp"] > 1:
                raise ValueError(
                    "a model with recurrent layers serves on one chip: its "
                    "recurrent pool (a recurrent state or a convolution's "
                    "inputs a slot and layer) "
                    f"is not sharded over tp={mesh.shape['tp']}"
                )
            if cfg.kv_lora_rank and mesh.shape["tp"] > 1:
                raise ValueError(
                    "a model with latent attention serves on one chip: a "
                    "row of its pool is nothing a head, and is not sharded "
                    f"over tp={mesh.shape['tp']}"
                )
            if cfg.window_layout and mesh.shape["tp"] > 1:
                raise ValueError(
                    "a model with window layers serves on one chip: its "
                    "ring of pages a slot is not sharded over "
                    f"tp={mesh.shape['tp']}"
                )
            if cfg.kv_lora_rank and mesh.shape.get("pp", 1) > 1:
                raise ValueError(
                    "stacks of unlike layers (dense before expert layers) "
                    f"do not split into pp={mesh.shape['pp']} stages"
                )
        rcfg = get_config()
        self.page_size = max(
            1, min(int(page_size or rcfg.serve_kv_page_size), max_len)
        )
        # A block-diffusion model: a pass yields 0 or B tokens a slot.
        self._block = cfg.block_length
        if self._block:
            if (self._block % cfg.denoise_steps
                    or self.prefill_chunk % self._block
                    or self.page_size % self._block):
                raise ValueError(
                    f"a block of {self._block} positions must be whole "
                    f"passes (denoise_steps {cfg.denoise_steps}), and "
                    f"divide the prefill chunk ({self.prefill_chunk}) and "
                    f"the page ({self.page_size})")
            if cfg.layer_pattern or cfg.kv_lora_rank or cfg.window_layout:
                raise ValueError("block diffusion serves over pages of "
                                 "keys and values a head alone")
        if cfg.window_layout and (cfg.layer_pattern or cfg.kv_lora_rank):
            raise ValueError("window layers beside recurrent layers or "
                             "latent attention are not written")
        self._pages_per_slot = -(-max_len // self.page_size)
        self.kv_pages = int(kv_pages or rcfg.serve_kv_pages or 0)
        if self.kv_pages <= 0:
            # Every slot can hold max_len rows (+ the reserved NULL page).
            self.kv_pages = num_slots * self._pages_per_slot + 1
        self._pool = paged_kv.PagePool(self.kv_pages, self.page_size)
        # A model with recurrent layers builds no prefix cache: pages
        # below a shared prefix are of no use without the recurrent state
        # at that boundary, and no one keeps snapshots of it. Nor does a
        # model with window layers: what lay in a slot's ring at a
        # prefix's end is gone once the slot has run on. Either holds
        # something a slot that no page holds, so a prompt takes one row
        # of a prefill pass (`_advance_prefills`).
        self._slot_state = bool(cfg.layer_pattern or cfg.window_layout)
        self._prefix_cache = (
            paged_kv.PrefixCache(self._pool)
            if rcfg.serve_prefix_cache and not self._slot_state else None
        )
        self._prefix_wanted = bool(rcfg.serve_prefix_cache)
        self._prefix_reuse_skipped = 0
        self._state_resets = 0
        # Host mirror of the device block table; uploaded as ONE array
        # only when admission/eviction changed it (same discipline — and
        # the same test pins — as the sampling params: the steady-state
        # decode step uploads nothing).
        self._bt_host = np.zeros(
            (num_slots, self._pages_per_slot), dtype=np.int32
        )
        self._bt_dirty = False
        self._bt_uploads = 0
        self._slot_pages: Dict[int, list] = {}
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefill_tok_skipped = 0
        self._chaos_held: list = []
        cache = self._fresh_cache()
        self._k, self._v = cache["k"], cache["v"]
        self._lengths = cache["lengths"]
        # What every step program takes after the cache and returns after
        # it, by the program's own argument names and in its order; a
        # dense attention model has none, and its programs are then what
        # they were. A call's results after the cache are the tail again,
        # in its order. `moe`: a model with experts' routing accumulator.
        # `rec`, `rec_count`: a model with recurrent layers' second pool,
        # part of the cache and donated with it, and its accumulator. The
        # accumulators are carried like the cache but not donated: stats()
        # reads them from another thread, and an array no program
        # consumes can be fetched at any time. Written by the loop thread
        # only.
        # `ring`: a model with window layers' second pool, part of the
        # cache and donated with it.
        self._tail: Dict = {}
        if cfg.num_experts:
            self._tail["moe"] = jax.tree.map(
                self._replicated, paged_kv.init_routing_counters(cfg))
        if cfg.layer_pattern:
            self._tail["rec"] = cache["rec"]
            self._tail["rec_count"] = paged_kv.init_ssm_counters()
            self._rec_bytes = sum(a.nbytes for a in cache["rec"].values())
        if cfg.window_layout:
            self._tail["ring"] = cache["ring"]
            # Rows of a slot's ring, for stats()["attention"].
            self._ring_rows = (cache["ring"]["k"].shape[1] - 1
                               ) // num_slots * self.page_size
        self._bt_dev = cache["block_tables"]
        self._decode_sampled = jax.jit(
            lambda p, t, k, v, ln, a, bt, tp, tk, tpp, key, **tail:
            paged_kv.decode_paged(
                p, t, k, v, ln, a, bt, tp, tk, tpp, key, cfg, max_len,
                mesh, **tail,
            ),
            donate_argnums=(2, 3), donate_argnames=("rec", "ring"),
        )
        self._decode_greedy = jax.jit(
            lambda p, t, k, v, ln, a, bt, **tail: paged_kv.decode_paged(
                p, t, k, v, ln, a, bt, None, None, None, None, cfg,
                max_len, mesh, **tail,
            ),
            donate_argnums=(2, 3), donate_argnames=("rec", "ring"),
        )
        self._prefill = jax.jit(
            lambda p, t, n, s, o, k, v, ln, bt, **tail:
            paged_kv.prefill_chunk_paged(
                p, t, n, s, o, k, v, ln, bt, cfg, max_len, mesh, **tail
            ),
            donate_argnums=(5, 6), donate_argnames=("rec", "ring"),
        )
        if self._block:
            # A slot's block on the device (`paged_kv.init_block_state`),
            # what a pass takes and returns in the token buffer's place,
            # and the host's mirrors of it: how many of a decoding slot's
            # block are still masked (under the static strategies a count
            # the host keeps: nothing is fetched to know a slot's phase)
            # and how many of its first block are the prompt's remainder.
            self._blk = jax.tree.map(
                self._replicated, paged_kv.init_block_state(cfg, num_slots))
            self._blk_masked = np.zeros(num_slots, dtype=np.int64)
            self._blk_skip = np.zeros(num_slots, dtype=np.int64)
            self._diffusion = dict.fromkeys((
                "passes", "slot_passes_offered", "slot_passes",
                "denoise_slot_passes", "commit_slot_passes",
                "tokens_committed", "blocks_committed", "head_rows",
                "head_rows_used"), 0)
            self._block_sampled = jax.jit(
                lambda p, st, k, v, ln, a, bt, tp, tk, tpp, key, **tail:
                paged_kv.block_pass_paged(
                    p, st, k, v, ln, a, bt, tp, tk, tpp, key, cfg, max_len,
                    mesh, **tail),
                donate_argnums=(2, 3),
            )
            self._block_greedy = jax.jit(
                lambda p, st, k, v, ln, a, bt, **tail:
                paged_kv.block_pass_paged(
                    p, st, k, v, ln, a, bt, None, None, None, None, cfg,
                    max_len, mesh, **tail),
                donate_argnums=(2, 3),
            )
            self._start_blocks = jax.jit(paged_kv.start_blocks)
            self._block_logits = jax.jit(
                lambda p, st, k, v, ln, a, bt: paged_kv.block_logits(
                    p, st, k, v, ln, a, bt, cfg, max_len, mesh),
                donate_argnums=(2, 3),
            )
        self._cow = jax.jit(paged_kv.cow_copy_page, donate_argnums=(0, 1))
        self._pick_first = jax.jit(_pick_first_tokens)
        self._lock = threading.Lock()
        self._work = threading.Event()
        # BOUNDED admission queue with per-tenant weighted-fair service:
        # one deque per tenant, served deficit-round-robin (weight w
        # accrues w credits per rotation; one credit admits one request,
        # so with equal weights this is plain round-robin and a chatty
        # tenant can no longer starve the others). The global bound
        # (serve_max_queued_per_engine) converts queue collapse into a
        # fast typed ServeOverloadedError shed at submit().
        self._waiting: Dict[str, deque] = {}
        self._waiting_n = 0
        self._wfq_rr: deque = deque()          # tenant rotation order
        self._wfq_credit: Dict[str, float] = {}
        self._tenant_weights: Dict[str, float] = {}
        self._shed_total = 0
        self._deadline_expired = 0
        self._slots: Dict[int, GenerationHandle] = {}
        # Mid-prefill requests: slot -> {"h": handle, "offset": rows
        # already prefilled}, oldest admission first. A loop iteration
        # advances them by one pass (_advance_prefills).
        self._prefilling: Dict[int, Dict] = {}
        self._free = deque(range(num_slots))
        # Next input token per slot, ON DEVICE: the decode loop feeds
        # each step's argmax straight into the next dispatch and fetches
        # results one step behind (host/RTT latency hides under decode).
        # Whole on every chip of the mesh, like each step's output that
        # replaces it: warm-up then compiles the program the loop runs.
        self._tokens_dev = self._replicated(
            np.zeros(num_slots, dtype=np.int32))
        # The decode step dispatched and not yet drained (loop-thread-
        # only): (snapshot [(slot, gen, handle)], tokens_dev, lengths_dev).
        self._inflight = None
        # Per-slot admission generation: suppresses the one in-flight
        # token a just-evicted slot still produces under the lag.
        self._gen = np.zeros(num_slots, dtype=np.int64)
        # Per-slot sampling params + active mask: HOST mirrors (written
        # at admission/eviction) with DEVICE-RESIDENT copies the decode
        # step reads. The steady-state step touches only the device
        # copies; _params_dirty triggers ONE host->device refresh when
        # slot membership changes — never four jnp.asarray uploads per
        # step.
        self._temps = np.zeros(num_slots, dtype=np.float32)
        self._top_ks = np.zeros(num_slots, dtype=np.int32)
        self._top_ps = np.ones(num_slots, dtype=np.float32)
        self._active = np.zeros(num_slots, dtype=bool)
        self._temps_dev = jnp.asarray(self._temps)
        self._top_ks_dev = jnp.asarray(self._top_ks)
        self._top_ps_dev = jnp.asarray(self._top_ps)
        self._active_dev = jnp.asarray(self._active)
        self._params_dirty = False
        self._sampled_active = False
        # stats()["sampler"]: sampled decode (or block) dispatches, and
        # those among them in which a live slot asks for a top-k, whose
        # passes over the vocabulary the step then pays for
        # (`paged_kv._pick_tokens`). From the host mirrors, loop-thread-
        # only: nothing is fetched for it.
        self._top_k_active = False
        self._sampler = {"dispatches": 0, "top_k_dispatches": 0}
        self._param_uploads = 0  # refresh events (tests pin steady state)
        # stats()["attention"]: how much of the cache decode attention is
        # given to read. `_rows_host` mirrors a decoding slot's device
        # length (the device's own reaches the host a step late): the
        # prompt's at the slot's first step, one more each step after.
        self._rows_host = np.zeros(num_slots, dtype=np.int64)
        self._attn_rows_read = 0
        self._attn_rows_held = 0
        self._attn_rows_live = 0
        # A model with window layers: the rows its window layers were
        # given to read, and what the same layers would have been given
        # without a window.
        self._attn_window_read = 0
        self._attn_window_unwindowed = 0
        # What the prefill passes' walks gathered of the tables (and
        # rings), and the same rows at full width
        # (`_count_prefill_walk_locked`).
        self._prefill_rows_walked = 0
        self._prefill_rows_held = 0
        # The loop's phase ledger (loop-thread-only) and the copy the
        # loop publishes under the lock after each turn for stats().
        self._phase = _PhaseLedger()
        self._ledger_pub = self._phase.snapshot()
        # `self._lock` as the loop's thread takes it: a wait for it is
        # the ledger's `lock_wait_engine`.
        self._lock_loop = _Held(self._phase, self._lock)
        self._rng = jax.random.PRNGKey(seed)
        self._next_id = 0
        self._steps = 0  # decode-step counter (observability + tests)
        # Head-of-line ledger (engine thread writes, stats() reads under
        # the lock): recent prefill passes that stalled active decode
        # slots past serve_hol_threshold_s, blamed on the prefilling
        # request(s) that ran in the pass.
        self._hol_events: deque = deque(maxlen=64)
        self._hol_blocked_s = 0.0
        self._last_prefill_work: list = []
        # Compilations are differences of the process's one counter of
        # JAX's own compile events: every program warm-up compiled, the
        # small eager ones included, and anything after it is a recompile.
        self._compiles_base = compile_events()
        t0 = time.monotonic()
        self._warmup()
        self._warmup_s = time.monotonic() - t0
        self._warm_compiles = compile_events() - self._compiles_base
        self._device = device_report()
        # Event, not a bare bool: set by shutdown() on the caller thread,
        # polled by the engine thread (RT006).
        self._stop_evt = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="llm-engine", daemon=True
        )
        self._thread.start()

    def _warmup(self):  # rtlint: disable=RT010 — runs before the loop thread starts; Thread.start() is the happens-before
        """Compile every steady-state program up front — BOTH decode
        variants (greedy and sampled), the prefill pass at each of its
        widths, and the first-token path of each (key split, pick into the
        token buffer) — so traffic flipping between greedy and sampled,
        or between one prompt and several, never compiles mid-serving.
        All warmup calls run with `active` all-False and an all-NULL
        block table: decode and prefill writes land in the NULL page,
        which is never read unmasked, so cache contents stay semantically
        untouched."""
        # The loop's own two-way split (and the unpacking's unstack).
        self._rng, k1 = jax.random.split(self._rng)
        zero = np.int32(0)
        if self._block:
            # Both block programs over idle slots, and the start of a
            # slot's first block with no slot starting.
            self._begin_blocks({})
            self._dispatch_block(None)
            self._dispatch_block(k1)
            self._begin_blocks({})
        else:
            (_, self._k, self._v, self._lengths,
             *tail) = self._decode_greedy(
                self.params, self._tokens_dev, self._k, self._v,
                self._lengths, self._active_dev, self._bt_dev, **self._tail,
            )
            self._tail = dict(zip(self._tail, tail))
            (_, self._k, self._v, self._lengths,
             *tail) = self._decode_sampled(
                self.params, self._tokens_dev, self._k, self._v,
                self._lengths, self._active_dev, self._bt_dev,
                self._temps_dev, self._top_ks_dev, self._top_ps_dev, k1,
                **self._tail,
            )
            self._tail = dict(zip(self._tail, tail))
        # Each pass with one real row (slot 0: a token, or a whole block)
        # and the rest inert, then (a next-token model) the first-token
        # pick over its logits (and the key's split), and the token buffer
        # as it was.
        tokens = self._tokens_dev
        for rows in self._pass_rows:
            row = np.zeros(rows, dtype=np.int32)
            n_valid = ((np.arange(rows, dtype=np.int32) == 0)
                       * max(1, self._block))
            logits = self._dispatch_prefill(
                np.zeros((rows, self.prefill_chunk), dtype=np.int32),
                n_valid.astype(np.int32), row, row)
            if not self._block:
                self._first_tokens(
                    logits, row, np.full(rows, 0.5, np.float32),
                    np.ones(rows, np.int32), np.ones(rows, np.float32))
        self._tokens_dev = tokens
        # Warm the copy-on-write page fork too (NULL page onto itself:
        # contents never observable).
        self._k, self._v = self._cow(self._k, self._v, zero, zero)
        # Undo the warmup prefill's lengths[0] = 1 and what warm-up
        # counted (device-side, keeps the mesh sharding of the arrays).
        # Slot 0's recurrent row stays as warm-up left it: a slot's first
        # chunk starts from zeros whatever its row holds.
        self._lengths = self._lengths * 0
        for name in ("moe", "rec_count"):
            if name in self._tail:
                self._tail[name] = jax.tree.map(lambda a: a * 0,
                                                self._tail[name])
        jax.block_until_ready(self._lengths)

    # Single-writer: KV cache and the step programs' tail are engine-
    # thread-owned device state.
    def _dispatch_prefill(self, tokens, n_valid, slots, offsets):  # rtlint: disable=RT006 — loop-thread-only (and warm-up, before the thread starts)
        """One prefill pass over `tokens [P, C]`, a row a `(n_valid, slot,
        offset)`, into the engine's cache. Returns the rows' logits
        `[P, vocab]`, on the device."""
        (logits, self._k, self._v, self._lengths, *tail) = self._prefill(
            self.params, tokens, n_valid, slots, offsets,
            self._k, self._v, self._lengths, self._bt_dev, **self._tail,
        )
        self._tail = dict(zip(self._tail, tail))
        return logits

    # Single-writer: rng and token buffer are engine-thread-owned.
    def _first_tokens(self, logits, slots, temps, top_ks, top_ps):  # rtlint: disable=RT006 — loop-thread-only (and warm-up, before the thread starts)
        """The first tokens of the requests a pass finished, each under
        its own sampling, from the pass's logits `[P, vocab]`, ON DEVICE
        and in one call: row `r`'s token feeds the decode loop's token
        buffer at `slots[r]` device-to-device (a row that ends no prompt
        names `num_slots` and feeds nothing), and the non-blocking copy
        the handle push drains is started. Returns the tokens' device
        array [P]."""
        key = self._rng  # a pass of greedy rows draws nothing from it
        if (temps > 0).any():
            self._rng, key = jax.random.split(self._rng)
        toks_dev, self._tokens_dev = self._pick_first(
            logits, self._tokens_dev, slots, temps, top_ks, top_ps, key)
        try:
            toks_dev.copy_to_host_async()
        except Exception:  # rtlint: disable=RT007 — optional prefetch; sharded layouts fetch at the drain
            pass
        return toks_dev

    # Single-writer: as _dispatch_prefill.
    def _dispatch_block(self, key):  # rtlint: disable=RT006,RT010 — loop-thread-only (and warm-up, before the thread starts)
        """One pass of every slot's block (`paged_kv.block_pass_paged`),
        the greedy-only program where `key` is None. The slots' blocks,
        the pools and the lengths advance on the device; returns the
        blocks' tokens after the pass, on the device."""
        args = (self.params, self._blk, self._k, self._v, self._lengths,
                self._active_dev, self._bt_dev)
        if key is None:
            out = self._block_greedy(*args, **self._tail)
        else:
            out = self._block_sampled(
                *args, self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                key, **self._tail)
        block, self._blk, self._k, self._v, self._lengths, *tail = out
        self._tail = dict(zip(self._tail, tail))
        return block

    def _begin_blocks(self, starts: Dict):  # rtlint: disable=RT006 — loop-thread-only (and warm-up, before the thread starts)
        """The slots of `starts` (slot -> the request's prompt) begin their
        first block, on the device: the prompt's remainder past its whole
        blocks clean, the rest masked, behind the whole blocks' rows."""
        self._blk, self._lengths = self._start_blocks(
            self._blk, self._lengths,
            *self._first_blocks(starts, self.num_slots))

    def _first_blocks(self, starts: Dict, slots: int):
        """`paged_kv.start_blocks`' host arguments for `starts` (slot ->
        prompt) among `slots` slots: which slots start, their block's clean
        tokens and masked bits, and the rows committed behind it."""
        b = self._block
        start = np.zeros(slots, dtype=bool)
        tokens = np.zeros((slots, b), dtype=np.int32)
        masked = np.ones((slots, b), dtype=bool)
        committed = np.zeros(slots, dtype=np.int32)
        for slot, prompt in starts.items():
            rest = len(prompt) % b
            start[slot] = True
            committed[slot] = len(prompt) - rest
            tokens[slot, :rest] = prompt[len(prompt) - rest:]
            masked[slot, :rest] = False
        return start, tokens, masked, committed

    # Single-writer: every *_dev array is owned by the engine thread
    # (this runs on it); submit() only flips _params_dirty under
    # self._lock.
    def _upload_sampling_state(self):  # rtlint: disable=RT006,RT010 — loop-thread-only; the lock is for submit()-side visibility
        """ONE host->device refresh of sampling params + active mask.
        Called only when slot membership changed (admission/eviction) —
        the steady-state decode step reads the device-resident copies
        and does zero uploads."""
        with self._phase("upload"):
            self._temps_dev = jnp.asarray(self._temps)
            self._top_ks_dev = jnp.asarray(self._top_ks)
            self._top_ps_dev = jnp.asarray(self._top_ps)
            self._active_dev = jnp.asarray(self._active)
            self._sampled_active = bool(
                (self._temps[self._active] > 0).any())
            self._top_k_active = bool(
                (self._top_ks[self._active] > 0).any())
            self._params_dirty = False
            self._param_uploads += 1

    # Single-writer: _bt_dev is engine-thread-owned device state.
    def _upload_block_table(self):  # rtlint: disable=RT006,RT010 — loop-thread-only; the lock is for submit()-side visibility
        """ONE host->device refresh of the block table. Admission-
        reserved paging means the table only changes when slot
        membership does — never per decode step (the block table's
        _upload_sampling_state, with its own counter so tests can pin
        the steady state)."""
        with self._phase("upload"):
            self._bt_dev = self._replicated(self._bt_host)
            self._bt_dirty = False
            self._bt_uploads += 1

    def _replicated(self, host_array):
        """On the device, whole on every chip of the engine's mesh."""
        arr = jnp.asarray(host_array)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            arr = jax.device_put(arr, NamedSharding(self.mesh, P()))
        return arr

    # Single-writer: pool/cache are engine-thread-owned host state.
    def _apply_kv_chaos(self):  # rtlint: disable=RT006
        """Consume pending paged-KV chaos injections (RT_CHAOS=1 only):
        a one-shot prefix-cache flush, and a persistent pool-pressure
        target — the engine holds `frac` of the usable pages hostage,
        adjusting toward the target as pages free up, until the frac is
        set back to 0."""
        if self._prefix_cache is not None and chaos.take_flush_prefix_cache():
            with self._lock_loop:
                self._prefix_cache.flush()
        frac = chaos.kv_exhaust_frac()
        if frac is None and not self._chaos_held:
            return
        target = int(round((frac or 0.0) * self._pool.usable))
        with self._lock_loop:
            if len(self._chaos_held) > target:
                give_back = self._chaos_held[target:]
                del self._chaos_held[target:]
                self._pool.release(give_back)
            elif len(self._chaos_held) < target:
                grab = min(target - len(self._chaos_held),
                           self._pool.free_pages)
                if grab > 0:
                    self._chaos_held.extend(self._pool.alloc(grab))

    def _fresh_cache(self) -> Dict:
        return paged_kv.init_paged_cache(
            self.cfg, self.num_slots, self.kv_pages, self.page_size,
            self._pages_per_slot, mesh=self.mesh,
            prefill_chunk=self.prefill_chunk,
        )

    def _count_attention_locked(self, reach):
        """stats()["attention"] advanced by the decode step about to be
        dispatched, in which each decoding slot attends to `reach` rows,
        the one it writes among them: the rows in the pages it is given to
        read, the rows the pool holds and the rows the live slots hold. A
        model with window layers: the three are summed over its layers, a
        full layer's as any model's, a window layer's read the lesser of
        the slot's rows and the window out of a ring a slot; and the
        window layers' rows read are counted again apart, beside what the
        same layers would have read without a window."""
        ps, cfg = self.page_size, self.cfg
        live = int(reach.sum())
        read = int((-(-reach // ps)).sum()) * ps
        held = self.num_slots * self.max_len
        if not cfg.window_layout:
            self._attn_rows_live += live
            self._attn_rows_read += read
            self._attn_rows_held += held
            return
        n_window = cfg.window_layers
        n_full = cfg.n_layers - n_window
        in_window = int(np.minimum(reach, cfg.sliding_window_size).sum())
        self._attn_rows_live += n_full * live + n_window * in_window
        self._attn_rows_read += n_full * read + n_window * in_window
        self._attn_rows_held += n_full * held + (
            n_window * self.num_slots * self._ring_rows)
        self._attn_window_read += n_window * in_window
        self._attn_window_unwindowed += n_window * live

    def _count_prefill_walk_locked(self, offsets, n_valid):
        """stats()["attention"] advanced by the prefill pass about to be
        dispatched: for each of its real rows, the rows of the blocks the
        pass's walk visits of the slot's table (`paged_kv._walk_blocks`: as
        far as the longest real row's end, an inert row lengthening
        nothing), summed over the layers that walk, and the same rows at
        the table's full width; a model with window layers adds its rings
        by the same rule. Keys and values: a latent pool's walk has a block
        of its own and is not counted."""
        ps, cfg = self.page_size, self.cfg
        real = n_valid > 0
        if self._v is None or not real.any():
            return
        rows = int(real.sum())
        reach = int((offsets + n_valid)[real].max())
        n_tables, n_window = self._k.shape[0], cfg.window_layers
        per_slot = self._pages_per_slot
        self._prefill_rows_walked += rows * n_tables * paged_kv.rows_walked(
            reach, ps, per_slot)
        self._prefill_rows_held += rows * n_tables * per_slot * ps
        if n_window:
            ring = self._ring_rows
            self._prefill_rows_walked += (
                rows * n_window * paged_kv.rows_walked(
                    min(reach, ring), ps, ring // ps))
            self._prefill_rows_held += rows * n_window * ring

    def _ssm_stats(self) -> Dict:
        """stats()["ssm"]: the recurrent pool's size (whatever its kind
        keeps a slot: a Mamba state, a convolution's rows, a matrix a head)
        and what the step programs counted since warm-up
        (`paged_kv.init_ssm_counters`, fetched now), with the two counts
        the host keeps: admissions that started a slot's state from zero,
        and admissions whose prompt filled a page and so would have been
        looked up in a prefix cache, had this model one."""
        acc = jax.device_get(self._tail["rec_count"])
        with self._lock:
            return {
                "pool_bytes": self._rec_bytes,
                "bytes_per_slot": self._rec_bytes // self.num_slots,
                **{name: int(n) for name, n in acc.items()},
                "state_resets": self._state_resets,
                "prefix_reuse_skipped": self._prefix_reuse_skipped,
            }

    # -- public API ------------------------------------------------------
    def queue_bound(self) -> int:
        """How many requests may wait for a slot before submit() sheds."""
        if self.max_queued is None:
            return get_config().serve_max_queued_per_engine
        return int(self.max_queued)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None) -> GenerationHandle:
        """temperature=0 decodes greedily (the default); >0 samples,
        optionally filtered by per-request top_k (<= MAX_TOP_K) and
        top_p — mixed greedy/sampled requests share one decode batch."""
        if top_k is not None and not 0 < top_k <= paged_kv.MAX_TOP_K:
            raise ValueError(f"top_k must be in (0, {paged_kv.MAX_TOP_K}]")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        limit = self.max_len - 2
        detail = f"max_len - 2 = {self.max_len - 2} positions"
        if self._block and self._last_block_end() - 1 < limit:
            # Room for one generated token in the last whole block.
            limit = self._last_block_end() - 1
            detail = (f"the last whole block of {self._block} ends at "
                      f"{limit + 1}")
        # The pool must hold the whole prompt plus one generated token
        # (+1 margin row for the pipelined in-flight step).
        pool_limit = self._pool.usable * self.page_size - 2
        if pool_limit < limit:
            limit = pool_limit
            detail = (
                f"page pool = {self._pool.usable} pages x "
                f"{self.page_size} tokens - 2 = {pool_limit}"
            )
        if len(prompt) > limit:
            raise PromptTooLongError(
                f"prompt length {len(prompt)} exceeds this engine's "
                f"limit of {limit} tokens ({detail})",
                prompt_len=len(prompt), max_prompt_len=limit,
            )
        if max_new_tokens is None:
            max_new_tokens = self.default_max_new_tokens
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        obs = observatory.current()
        meta = request_context.current()
        tenant = (meta.tenant if meta is not None else "") or "default"
        deadline_ts = meta.deadline_ts if meta is not None else 0.0
        cfg = get_config()
        if deadline_ts and time.time() > deadline_ts:
            # Budget already burned upstream (slow dispatch/wire): never
            # enqueue work that cannot make its deadline.
            with self._lock:
                self._deadline_expired += 1
            observatory.record_deadline_expired("", "engine_admission")
            raise RequestCancelledError(
                "deadline expired before engine admission",
                reason="deadline", rid=meta.rid if meta else "",
            )
        max_queued = self.queue_bound()
        with self._lock:
            if self._waiting_n >= max_queued:
                # Fast shed: reject BEFORE allocating anything. The
                # retry hint is a coarse backlog-drain estimate (queue
                # depth over slot count, capped) — good enough to spread
                # retries, not a latency promise.
                self._shed_total += 1
                retry = min(5.0, max(
                    0.1, 0.05 * self._waiting_n / max(1, self.num_slots)
                ))
                observatory.record_shed("", tenant, "queue_full")
                raise ServeOverloadedError(
                    f"engine admission queue full "
                    f"({self._waiting_n} waiting >= {max_queued})",
                    tenant=tenant, reason="queue_full", retry_after_s=retry,
                )
            h = GenerationHandle(self._next_id)
            self._next_id += 1
            h.submitted_at = time.perf_counter()
            h.prompt = prompt
            h.max_new_tokens = int(max_new_tokens)
            h.temperature = float(temperature)
            h.top_k = int(top_k or 0)
            h.top_p = float(1.0 if top_p is None else top_p)
            h.tenant = tenant
            h.deadline_ts = deadline_ts
            # Adopt the request thread's stamp card: engine admission
            # wait is measured from THIS enqueue, not from slot grant.
            h.obs = obs
            if obs is not None:
                obs.marks["engine_enqueue"] = h.submitted_at
                obs.tokens_in = len(prompt)
            q = self._waiting.get(tenant)
            if q is None:
                q = self._waiting[tenant] = deque()
                self._wfq_rr.append(tenant)
            q.append(h)
            self._waiting_n += 1
            _engine_metrics()["waiting"].set(float(self._waiting_n))
        self._work.set()
        return h

    def _last_block_end(self) -> int:
        """The positions a block-diffusion slot may fill: whole blocks
        within `max_len`."""
        return self.max_len // self._block * self._block

    def set_tenant_weight(self, tenant: str, weight: float) -> None:
        """Give a tenant a WFQ share (> 1 admits proportionally more per
        rotation, < 1 less; default 1.0 — equal shares)."""
        if weight <= 0:
            raise ValueError("tenant weight must be > 0")
        with self._lock:
            self._tenant_weights[tenant or "default"] = float(weight)

    def _kv_stats_locked(self) -> Dict:
        """The KV memory plane's health (stats()["kv"]): pool occupancy,
        prefix-cache effectiveness, and — for affinity routing — the
        cache's advertised root keys."""
        lookups = self._prefix_hits + self._prefix_misses
        cache_pages = (self._prefix_cache.pages_held
                       if self._prefix_cache is not None else 0)
        return {
            "mode": "paged",
            "page_size": self.page_size,
            "pages_total": self._pool.usable,
            "pages_in_use": self._pool.in_use,
            "pages_free": self._pool.free_pages,
            "util": self._pool.in_use / max(1, self._pool.usable),
            "prefix_cache_pages": cache_pages,
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "prefix_hit_rate": (self._prefix_hits / lookups
                                if lookups else None),
            "prefill_tokens_skipped": self._prefill_tok_skipped,
            # Admissions whose prompt filled a page and would have been
            # looked up in a prefix cache, had this model one (a model
            # with recurrent or window layers has none).
            "prefix_reuse_skipped": self._prefix_reuse_skipped,
            "bt_uploads": self._bt_uploads,
            "chaos_held_pages": len(self._chaos_held),
            "roots": (self._prefix_cache.roots()
                      if self._prefix_cache is not None else []),
        }

    def prefill_logits(self, prompt) -> np.ndarray:
        """Next-token logits [vocab], float32, for `prompt` (a block-
        diffusion model's: the logits at the first masked position of the
        block that follows the prompt's whole blocks): the engine's
        own prefill program run on a scratch cache of ONE slot, made of
        whatever the engine's cache is made of (a slot's pages and the
        NULL page; a model with recurrent layers' one row of state), i.e.
        what the first decode step picks from. For comparing two engines
        (one chip against a tensor-parallel mesh), where token equality is
        hostage to bf16 reduction order, and a served model against its
        reference. Shares nothing with the serving loop; the one-slot
        shapes compile on the first call."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if not 0 < len(prompt) <= self.max_len - 2:
            raise ValueError(
                f"prompt length {len(prompt)} not in [1, {self.max_len - 2}]"
            )
        pages = self._pages_per_slot
        cache = paged_kv.init_paged_cache(
            self.cfg, 1, pages + 1, self.page_size, pages, mesh=self.mesh,
            prefill_chunk=self.prefill_chunk)
        k, v, lengths = cache["k"], cache["v"], cache["lengths"]
        tail = {name: cache.get(name, acc)
                for name, acc in self._tail.items()}
        table = self._replicated(np.arange(1, pages + 1, dtype=np.int32)[None])
        c = self.prefill_chunk
        end = len(prompt) - (len(prompt) % self._block if self._block else 0)
        for off in range(0, end, c):
            chunk = prompt[off:min(off + c, end)]
            padded = np.zeros((1, c), dtype=np.int32)
            padded[0, :len(chunk)] = chunk
            logits, k, v, lengths, *out = self._prefill(
                self.params, padded, np.int32(len(chunk)),
                np.int32(0), np.int32(off), k, v, lengths, table, **tail,
            )
            tail = dict(zip(tail, out))
        if self._block:
            # A block-diffusion model: ONE pass of the block that follows
            # the prompt's whole blocks (its remainder clean, the rest
            # masked), and the logits at the block's first masked position:
            # what the first denoising pass draws its first token from.
            state, lengths = paged_kv.start_blocks(
                paged_kv.init_block_state(self.cfg, 1), lengths,
                *self._first_blocks({0: prompt}, 1))
            logits, _, _ = self._block_logits(
                self.params, jax.tree.map(self._replicated, state), k, v,
                self._replicated(lengths),
                self._replicated(np.ones(1, bool)), table)
            return np.asarray(logits[0, len(prompt) - end], dtype=np.float32)
        return np.asarray(logits, dtype=np.float32)[0]

    def _moe_stats(self) -> Dict:
        """stats()["moe"]: the routing accumulator, fetched now (the one
        place it leaves the device). Cumulative since warm-up, over every
        call of a step program and every row it computed, a prefill
        chunk's padding and idle slots included, since those rows' experts
        are read too: `assignments` rows x k x layers; `calls`;
        `experts_hit_sum` and `max_load_sum` summed over calls and layers
        (over calls x layers: a layer's mean experts hit and its largest
        expert's mean load), both of the experts held here; `per_expert
        [E]` assignments summed over layers. For a chip's share of the
        experts, `assignments` counts every expert the router chose and
        `held_assignments` those to the `experts_held` of `num_experts`
        whose weights are here, in each of `expert_layers` layers."""
        acc = jax.device_get(self._tail["moe"])
        per_layer_expert = acc["assignments"].astype(np.int64)
        cfg = self.cfg
        return {
            "held_assignments": int(
                per_layer_expert[:, paged_kv.held_experts(cfg)].sum()),
            "experts_held": cfg.held,
            "num_experts": cfg.num_experts,
            "expert_layers": cfg.expert_layers,
            "assignments": int(per_layer_expert.sum()),
            "calls": int(acc["calls"]),
            "experts_hit_sum": int(acc["experts_hit_sum"].sum()),
            "max_load_sum": int(acc["max_load_sum"].sum()),
            "per_expert": per_layer_expert.sum(0).tolist(),
        }

    def stats(self) -> Dict:
        compiles = compile_events() - self._compiles_base
        # Before the lock: the fetch waits for the step in flight.
        tail = {"moe": self._moe_stats()} if "moe" in self._tail else {}
        if "rec_count" in self._tail:
            tail["ssm"] = self._ssm_stats()
        with self._lock:
            if self._block:
                # A block-diffusion engine's passes, cumulative, from the
                # host's own counts (`_next_block_pass_locked` at a dispatch,
                # `_distribute_blocks` at a drain): passes dispatched;
                # slots a pass offers and the live ones it ran, a denoising
                # or a commit pass each; generated tokens and blocks handed
                # to the handles (a prompt's remainder and what is cut off
                # a last block are not among the tokens); rows the head and
                # the sampler computed, and those whose draw was kept.
                tail["diffusion"] = {
                    **self._diffusion, "block_length": self._block,
                    "denoise_steps": self.cfg.denoise_steps}
            return {
                **tail,
                # The device this engine's programs run on, as JAX
                # reports it in this process, and what warm-up cost.
                "device": self._device,
                "warmup_s": self._warmup_s,
                "kv": self._kv_stats_locked(),
                "steps": self._steps,
                "active": len(self._slots),
                "waiting": self._waiting_n,
                "waiting_tenants": {
                    t: len(q) for t, q in self._waiting.items() if q
                },
                "shed_total": self._shed_total,
                "deadline_expired": self._deadline_expired,
                "prefilling": len(self._prefilling),
                "free_slots": len(self._free),
                # Hot-loop hygiene (tests pin these in steady state).
                "compiles": compiles,
                "warm_compiles": self._warm_compiles,
                "recompiles_post_warm": compiles - self._warm_compiles,
                "param_uploads": self._param_uploads,
                # Cumulative: sampled decode (or block) steps dispatched,
                # and those in which a live slot's `top_k` made the step
                # find every row's k-th largest logit.
                "sampler": dict(self._sampler),
                # Cumulative over dispatched decode steps: rows in the
                # pages decode attention was given to read (each decoding
                # slot's length in whole pages, a latent pool's too), rows
                # the pool holds (slots x max_len a step) and rows the
                # live slots hold, from host mirrors: nothing is fetched
                # for it (`_count_attention_locked`; a model with window
                # layers also has the two counts of those layers alone).
                # And over dispatched prefill passes: rows in the blocks
                # the passes' walks visited, and the same rows at the
                # tables' full width (`_count_prefill_walk_locked`).
                "attention": {
                    "decode_rows_read": self._attn_rows_read,
                    "decode_rows_held": self._attn_rows_held,
                    "decode_rows_live": self._attn_rows_live,
                    "prefill_rows_walked": self._prefill_rows_walked,
                    "prefill_rows_held": self._prefill_rows_held,
                    **({"window_rows_read": self._attn_window_read,
                        "window_rows_unwindowed":
                            self._attn_window_unwindowed}
                       if self.cfg.window_layout else {}),
                },
                # Where the loop's time goes (EQuARX discipline — you
                # cannot shrink a step you cannot decompose). _total
                # fields are cumulative: probes delta two stats()
                # snapshots for a clean steady-state window. One ledger
                # (`_PhaseLedger`), every turn.
                "timing": _timing_of(self._ledger_pub),
                # Request-level latency (flight recorder): process-wide
                # lifetime summaries of the TTFT/TPOT histograms, plus
                # the instantaneous batch occupancy.
                "latency": {
                    "ttft": _engine_metrics()["ttft_s"].summary(),
                    "tpot": _engine_metrics()["tpot_s"].summary(),
                    "occupancy": len(self._slots) / self.num_slots,
                },
                # Head-of-line ledger: decode stalls attributed to the
                # long prefill that caused them (observatory + rt serve).
                "hol": {
                    "blocked_slot_seconds": self._hol_blocked_s,
                    "events": list(self._hol_events),
                },
            }

    def shutdown(self):
        self._stop_evt.set()
        self._work.set()
        self._thread.join(timeout=10)
        # Outstanding handles must resolve: a streaming consumer blocked
        # in __iter__ would otherwise wait forever.
        err = RuntimeError("engine shut down")
        with self._lock:
            pending = (list(self._slots.values())
                       + self._drain_waiting_locked()
                       + [e["h"] for e in self._prefilling.values()])
            for h in pending:
                h._fail(err)
            self._slots.clear()
            self._prefilling.clear()

    # -- engine loop -----------------------------------------------------
    def _drain_waiting_locked(self) -> list:
        """Flatten and empty every tenant queue (shutdown/failure)."""
        out: list = []
        for q in self._waiting.values():
            out.extend(q)
        self._waiting.clear()
        self._wfq_rr.clear()
        self._wfq_credit.clear()
        self._waiting_n = 0
        return out

    def _pop_waiting_locked(self) -> Optional[GenerationHandle]:
        """Next request under deficit-round-robin over tenant queues.

        Each rotation a tenant earns its weight in credits; one credit
        admits one request. Tenants whose queue empties leave the
        rotation (and forfeit leftover credit — standard DRR, so idle
        tenants cannot bank a burst). Terminates: credits grow every
        full rotation while any queue is non-empty."""
        while self._wfq_rr:
            t = self._wfq_rr.popleft()
            q = self._waiting.get(t)
            if not q:
                self._waiting.pop(t, None)
                self._wfq_credit.pop(t, None)
                continue
            credit = (self._wfq_credit.get(t, 0.0)
                      + self._tenant_weights.get(t, 1.0))
            h = None
            if credit >= 1.0:
                h = q.popleft()
                self._waiting_n -= 1
                credit -= 1.0
            self._wfq_credit[t] = credit
            self._wfq_rr.append(t)
            if h is not None:
                return h
        return None

    def _admit_locked(self):
        """Assign free slots to waiting requests; their prompts then
        prefill one pass of chunks per loop iteration (_advance_prefills),
        so a long prompt never stalls other slots' decode for more than a
        pass. Requests whose deadline expired while queued (or that the
        caller cancelled) are dropped here instead of burning a slot."""
        admitted = bool(self._waiting_n and self._free)
        now = time.time()
        while self._free and self._waiting_n:
            h = self._pop_waiting_locked()
            if h is None:
                break
            if h.cancelled:
                continue  # cancel() already failed the handle
            if h.deadline_ts and now > h.deadline_ts:
                self._deadline_expired += 1
                self._phase.lock_waited("handle", h._fail(
                    RequestCancelledError(
                        f"deadline expired in admission queue "
                        f"(request {h.request_id})",
                        reason="deadline", rid=str(h.request_id),
                    )))
                observatory.record_deadline_expired("", "engine_admission")
                continue
            # Deliverable budget: the loop cuts a sequence at lengths >=
            # max_len - 2 (one in-flight pipelined step keeps a margin
            # row), so a prompt of P rows can emit max_len - 1 - P
            # tokens; submit() guarantees that is >= 1. Clamp to what
            # will actually be delivered.
            h.max_new_tokens = min(
                h.max_new_tokens,
                # A block-diffusion slot fills whole blocks and is evicted
                # before one would pass max_len; nothing is in flight past
                # a request's last block that could write a page of its
                # own (rows past a slot's pages park in the NULL page).
                (self._last_block_end() if self._block
                 else self.max_len - 1) - len(h.prompt)
            )
            # Reserve EVERY page the request can ever touch now: decode
            # then never allocates, so the block table (like the sampling
            # params) uploads only on slot membership changes and pool
            # exhaustion can never strand a mid-decode sequence.
            res = self._reserve_paged_locked(h)
            if res is None:
                # Pool pressure: back to the FRONT of its tenant queue;
                # retried as decoding slots release pages.
                q = self._waiting.get(h.tenant)
                if q is None:
                    q = self._waiting[h.tenant] = deque()
                    self._wfq_rr.append(h.tenant)
                q.appendleft(h)
                self._waiting_n += 1
                break
            grant_t = time.perf_counter()
            if h.submitted_at is not None:
                _engine_metrics()["admission_wait_s"].observe(
                    grant_t - h.submitted_at
                )
            if h.obs is not None:
                h.obs.marks["slot_grant"] = grant_t
            slot = self._free.popleft()
            # Its first chunk starts every recurrent layer from zeros.
            self._state_resets += bool(self.cfg.layer_pattern)
            row = self._bt_host[slot]
            row[:] = 0
            row[:len(res["pages"])] = res["pages"]
            self._bt_dirty = True
            self._prefilling[slot] = {
                "h": h, "offset": res["skip"], "pages": res["pages"],
                "hashes": res["hashes"],
            }
        if admitted:
            _engine_metrics()["waiting"].set(float(self._waiting_n))

    # Caller holds self._lock (the `_locked` contract); the KV counters
    # it bumps are read back under the same lock in _kv_stats_locked.
    def _reserve_paged_locked(self, h) -> Optional[Dict]:  # rtlint: disable=RT006
        """Pages for one admission: shared prefix pages from the cache
        (refcount bump, prefill skipped below `skip`) plus freshly
        allocated pages covering the rest of the request's maximum
        footprint. None = pool exhausted even after LRU-evicting cache
        entries; the caller requeues."""
        ps = self.page_size
        p_len = len(h.prompt)
        hashes = (paged_kv.page_hashes(h.prompt, ps)
                  if self._prefix_cache is not None else [])
        if self._slot_state and self._prefix_wanted and p_len >= ps:
            self._prefix_reuse_skipped += 1
        shared = self._prefix_cache.match(hashes) if hashes else []
        # Footprint: prompt + generated tokens + one margin row for the
        # pipelined in-flight step, capped by addressable positions.
        rows = min(p_len + h.max_new_tokens + 1, self.max_len)
        if self._block:  # to the end of the block that holds the last token
            rows = -(-(p_len + h.max_new_tokens) // self._block) * self._block
        need = -(-rows // ps) - len(shared)
        try:
            own = self._pool.alloc(need)
        except paged_kv.OutOfPages:
            own = None
            if self._prefix_cache is not None and self._prefix_cache.pages_held:
                self._prefix_cache.evict_pages(
                    need - self._pool.free_pages
                )
                try:
                    own = self._pool.alloc(need)
                except paged_kv.OutOfPages:
                    own = None
        if own is None:
            if shared:
                self._pool.release(shared)
            return None
        pages = shared + own
        # Always recompute at least the final prompt token: its logits
        # seed the first generated token, and a partial tail page is
        # never cached anyway.
        skip = min(len(shared) * ps, p_len - 1)
        if self._block:
            # No token comes out of prefill, so nothing is recomputed: the
            # shared pages are whole blocks, and the slot writes behind
            # them.
            skip = len(shared) * ps
        m = _engine_metrics()
        if hashes:
            if shared:
                self._prefix_hits += 1
                m["prefix_hits"].inc(1)
            else:
                self._prefix_misses += 1
                m["prefix_misses"].inc(1)
        if skip > 0:
            self._prefill_tok_skipped += skip
            m["prefill_skipped"].inc(skip)
        fw = skip // ps
        if skip and fw < len(shared):
            # Full-prefix hit: the recomputed final token's K/V lands in
            # the LAST shared page — fork it copy-on-write first
            # (refcount > 1 pages are never written).
            try:
                fork = self._pool.alloc(1)[0]
            except paged_kv.OutOfPages:
                self._pool.release(pages)
                return None
            self._k, self._v = self._cow(
                self._k, self._v, np.int32(pages[fw]), np.int32(fork)
            )
            self._pool.release([pages[fw]])
            pages[fw] = fork
        return {"pages": pages, "hashes": hashes, "skip": skip}

    def _release_slot_pages_locked(self, slot: int):
        """Return a decoding slot's page references to the pool (slot
        eviction; prefix-cache entries keep their own references)."""
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self._pool.release(pages)

    # Single-writer: KV cache, rng, and token buffers are engine-thread-
    # owned device state; no other thread touches them after __init__.
    def _advance_prefills(self):  # rtlint: disable=RT006
        """One prefill PASS (interleaved between decode dispatches): one
        program over the chunks the mid-prefill slots owe, a row a chunk:
        one for every slot, oldest admission first, then the rows left to
        the oldest again. A prompt whose cache is pages alone may so take
        several rows, consecutive chunks in the rows' order (a later row
        reads the earlier one's keys from the pages); a model with
        recurrent layers gets one row a slot, since a chunk starts from
        the state the chunk before it left. The pass runs the smaller of
        the engine's two widths (one row, or `PASS_ROWS` within
        `PASS_TOKENS`) that holds what is owed, the rest inert; what does
        not fit waits a turn. A request whose final chunk lands emits its
        first token and joins the decode set. (A block-diffusion model's:
        its prompt's whole blocks are the chunks, no token comes out of
        them and nothing is fetched; the request joins the decode set with
        its first block started on the device, `_publish_blocks`.)

        First tokens stay ON DEVICE through admission: one pick over the
        pass's logits feeds _tokens_dev device-to-device, and ONE fetch
        (async copy started at dispatch, drained once) delivers all of
        this pass's first tokens to their handles — not one blocking
        scalar device_get per request."""
        c = self.prefill_chunk
        # Chaos hook: a deterministic stretch stands in for a genuinely
        # huge prompt so HOL-attribution tests don't need one. Inside
        # the timed window on purpose — the watchdog must see it.
        injected = chaos.take_prefill_delay()
        if injected:
            time.sleep(injected)
        if self._bt_dirty:
            self._upload_block_table()
        self._last_prefill_work = [
            {
                "request_id": e["h"].request_id,
                "prompt_tokens": int(len(e["h"].prompt)),
                "offset": int(e["offset"]),
            }
            for e in self._prefilling.values()
        ]
        owing = []  # (slot, entry, the offsets of the chunks it owes)
        per_slot = 1 if self._slot_state else self._pass_rows[-1]
        now_wall = time.time()
        for slot, entry in list(self._prefilling.items()):
            h = entry["h"]
            if h.cancelled or (h.deadline_ts and now_wall > h.deadline_ts):
                # Abandon the partial prefill: remaining chunks would be
                # work for a request nobody is waiting on.
                if not h.cancelled:
                    self._phase.lock_waited("handle", h._fail(
                        RequestCancelledError(
                            f"deadline expired mid-prefill "
                            f"(request {h.request_id})",
                            reason="deadline", rid=str(h.request_id),
                        )))
                    observatory.record_deadline_expired("", "engine_decode")
                with self._lock_loop:
                    self._deadline_expired += int(not h.cancelled)
                    del self._prefilling[slot]
                    self._free.append(slot)
                    self._pool.release(entry["pages"])
                continue
            # A block-diffusion model prefills a prompt's whole blocks; the
            # remainder enters the first block clean (`_begin_blocks`).
            entry["end"] = len(h.prompt) - (
                len(h.prompt) % self._block if self._block else 0)
            owing.append((slot, entry, range(
                entry["offset"], entry["end"], c)[:per_slot]))
        # A row for every slot first, so that a short prompt never waits
        # for a long one's chunks, then the rows left to the oldest.
        room = self._pass_rows[-1]
        take = [0] * len(owing)
        for share in (1, per_slot):
            for i, (_, _, owed) in enumerate(owing):
                given = min(min(share, len(owed)) - take[i], room)
                take[i] += given
                room -= given
        rows = [(slot, entry, off)
                for (slot, entry, owed), n in zip(owing, take)
                for off in owed[:n]]
        # Prompts with nothing (left) to prefill: shorter than a block, or
        # every whole block found in the prefix cache.
        began = [(slot, entry["h"], entry) for slot, entry, _ in owing
                 if self._block and entry["offset"] == entry["end"]]
        if not rows:
            if began:
                with self._phase("prefill_publish"):
                    self._publish_blocks(began)
            return
        with self._phase("prefill_dispatch"):
            width = next(p for p in self._pass_rows if p >= len(rows))
            tokens = np.zeros((width, c), dtype=np.int32)
            n_valid, slots, offsets = np.zeros((3, width), dtype=np.int32)
            # Rows that end a prompt: where their token goes, and how it
            # is picked (a row that ends none: nowhere, greedily).
            ends = np.full(width, self.num_slots, dtype=np.int32)
            temps = np.zeros(width, dtype=np.float32)
            top_ks = np.zeros(width, dtype=np.int32)
            top_ps = np.ones(width, dtype=np.float32)
            finished = []  # (row, slot, handle, entry)
            for r, (slot, entry, off) in enumerate(rows):
                h = entry["h"]
                chunk = h.prompt[off:min(off + c, entry["end"])]
                tokens[r, :len(chunk)] = chunk
                n_valid[r], slots[r], offsets[r] = len(chunk), slot, off
                entry["offset"] = off + len(chunk)
                if entry["offset"] == entry["end"]:
                    ends[r] = slot
                    temps[r], top_ks[r], top_ps[r] = (
                        h.temperature, h.top_k, h.top_p)
                    finished.append((r, slot, h, entry))
            logits = self._dispatch_prefill(tokens, n_valid, slots, offsets)
            self._phase.prefill_rows += len(rows)
            with self._lock_loop:
                self._count_prefill_walk_locked(offsets, n_valid)
            self._phase.newest = logits
            if self._block:
                began += [(slot, h, entry) for _, slot, h, entry in finished]
            elif finished:
                # Final chunks: their first tokens, fed to the decode loop
                # device-side (no host round trip), the copy started for
                # the handle push below.
                toks_dev = self._first_tokens(logits, ends, temps, top_ks,
                                              top_ps)
                self._phase.newest = toks_dev
        if self._block:
            # No token comes out of prefill and nothing is fetched: the
            # slots' first blocks start on the device, behind the pass.
            if began:
                with self._phase("prefill_publish"):
                    self._publish_blocks(began)
            return
        if not finished:
            return
        with self._phase("prefill_first_token_wait"):
            toks_np = jax.device_get(toks_dev)
        with self._phase("prefill_publish"):
            self._publish_first_tokens(finished, toks_np)

    def _publish_first_tokens(self, finished, toks_np):  # rtlint: disable=RT006 — loop-thread-only, see _advance_prefills
        """Push this pass's first tokens to their handles and move each
        request from the prefilling set to the decode set (or free its
        slot if that token finished it)."""
        for row, slot, h, entry in finished:
            tok = int(toks_np[row])
            h.produced = 1
            # admitted_at_step must be visible before the push wakes a
            # consumer (a request finishing on its prefill token would
            # otherwise be observable with the -1 sentinel). _steps is
            # only written by this thread.
            h.admitted_at_step = self._steps  # rtlint: disable=RT010 — _steps is loop-thread-only (see comment)
            done = (tok == self.eos_id if self.eos_id is not None
                    else False) or h.produced >= h.max_new_tokens
            self._phase.lock_waited("handle", h._push(tok, done))
            with self._lock_loop:
                self._prefilled_locked(slot, entry)
                if done:
                    self._free.append(slot)
                    self._pool.release(entry["pages"])
                else:
                    self._join_decode_locked(slot, h, entry, len(h.prompt))

    def _prefilled_locked(self, slot: int, entry: Dict):
        """A prompt's prefill is done: its full pages go to the prefix
        cache NOW (not at request completion: a concurrent same-prefix
        request admitted next tick already shares them) and it leaves the
        prefilling set."""
        hashes = entry.get("hashes") or []
        if self._prefix_cache is not None and hashes:
            self._prefix_cache.insert(hashes, entry["pages"][:len(hashes)])
        del self._prefilling[slot]

    def _join_decode_locked(self, slot: int, h, entry: Dict, rows: int):
        """Slot `slot` joins the decode set behind `rows` cached rows."""
        self._slot_pages[slot] = entry["pages"]
        self._slots[slot] = h
        self._rows_host[slot] = rows
        self._gen[slot] += 1
        self._temps[slot] = h.temperature
        self._top_ks[slot] = h.top_k
        self._top_ps[slot] = h.top_p
        self._active[slot] = True
        self._params_dirty = True

    def _publish_blocks(self, began):  # rtlint: disable=RT006 — loop-thread-only, see _advance_prefills
        """`_publish_first_tokens` for a block-diffusion model: the
        requests `began [(slot, handle, entry)]`, whose prompts' whole
        blocks are in the pages, start their first block on the device and
        join the decode set. No token is pushed: the first arrive with the
        first block's commit."""
        self._begin_blocks({slot: h.prompt for slot, h, _ in began})
        b = self._block
        with self._lock_loop:
            for slot, h, entry in began:
                h.admitted_at_step = self._steps
                self._prefilled_locked(slot, entry)
                self._join_decode_locked(slot, h, entry, entry["end"])
                self._blk_skip[slot] = len(h.prompt) - entry["end"]
                self._blk_masked[slot] = b - self._blk_skip[slot]

    def _next_block_pass_locked(self, snapshot):
        """The pass about to be dispatched, from the host's mirrors alone:
        `snapshot`'s entries with whether the slot's pass is its block's
        commit, the mirrors advanced as the device will advance the slots
        (a denoising pass fills B / denoise_steps of the masked positions,
        a commit pass adds B rows and starts the next block all masked),
        and the pass counted."""
        b, d = self._block, self._diffusion
        n = b // self.cfg.denoise_steps
        out, used = [], 0
        for s, gen, h in snapshot:
            commit = bool(self._blk_masked[s] == 0)
            out.append((s, gen, h, commit))
            if commit:
                self._blk_masked[s] = b
                self._rows_host[s] += b
            else:
                fills = min(n, self._blk_masked[s])
                self._blk_masked[s] -= fills
                used += fills
        commits = sum(e[3] for e in out)
        d["passes"] += 1
        d["slot_passes_offered"] += self.num_slots
        d["slot_passes"] += len(out)
        d["commit_slot_passes"] += commits
        d["denoise_slot_passes"] += len(out) - commits
        # The rows the head and the sampler compute (n a slot, idle and
        # committing slots' too), and those whose draw fills a position.
        d["head_rows"] += self.num_slots * n
        d["head_rows_used"] += int(used)
        return out

    def _note_hol(self, prefill_s: float, n_active: int):
        """Attribute a slow prefill pass to the decode slots it stalled.

        Chunked prefill bounds the stall at one pass of a few chunks, but
        a pass can still cross the threshold (huge chunk, slow host, chaos
        injection). Cost: one get_config() + comparison per PREFILL
        pass; the steady-state decode loop never reaches here."""
        if n_active <= 0 or prefill_s < get_config().serve_hol_threshold_s:
            return
        blocked = prefill_s * n_active  # slot-seconds of stalled decode
        culprits = self._last_prefill_work
        with self._lock_loop:
            self._hol_blocked_s += blocked
            self._hol_events.append({
                "ts": time.time(),
                "prefill_s": prefill_s,
                "victims": n_active,
                "blocked_slot_seconds": blocked,
                "culprits": culprits,
            })
        _engine_metrics()["hol_s"].inc(blocked)
        from ray_tpu.util import journal

        journal.emit("serve.hol", prefill_s=round(prefill_s, 4),
                     victims=n_active,
                     blocked_slot_seconds=round(blocked, 4))
        journal.trigger_postmortem(
            "hol_blocking", prefill_s=round(prefill_s, 4),
            victims=n_active)

    # Single-writer: the loop thread owns every *_dev array, the rng and
    # the ledger; shared host state is touched under self._lock.
    def _turn(self):  # rtlint: disable=RT006,RT010
        """One loop iteration with work, inside the ledger's `turn` span:
        admit, advance prefills by a pass, dispatch decode step k+1 (a
        block-diffusion model's: pass k+1 of every slot's block), drain
        and distribute step k."""
        phase = self._phase
        with phase("admit"):
            self._apply_kv_chaos()
            with self._lock_loop:
                self._admit_locked()
                n_active = len(self._slots)
        # HOL watchdog: a prefill pass that stalls active decode slots
        # past serve_hol_threshold_s is recorded with the prefilling
        # request(s) to blame. The pass is timed on the ledger's own
        # stamps (admit's exit to the pass's last phase's exit, the chaos
        # stretch in between included), not on a second clock.
        if self._prefilling:
            phase.prefill_passes += 1
            t_pass = phase.t
            self._advance_prefills()
            self._note_hol(phase.t - t_pass, n_active)
        with self._lock_loop:
            snapshot = [
                (s, int(self._gen[s]), h) for s, h in self._slots.items()
            ]
            if snapshot:
                # The step about to be dispatched: each decoding slot
                # attends to its rows and the one it writes (a block-
                # diffusion slot: its committed rows and its block's), in
                # whole pages; the pool holds max_len rows for every slot.
                live = list(self._slots)
                if self._block:
                    reach = self._rows_host[live] + self._block
                    snapshot = self._next_block_pass_locked(snapshot)
                else:
                    self._rows_host[live] += 1
                    reach = self._rows_host[live]
                self._count_attention_locked(reach)
        new_inflight = None
        if snapshot:
            if self._params_dirty:
                self._upload_sampling_state()
            if self._bt_dirty:
                self._upload_block_table()
            with phase("decode_dispatch"):
                step_key = None
                if self._sampled_active:
                    self._rng, step_key = jax.random.split(self._rng)
                    self._sampler["dispatches"] += 1
                    self._sampler["top_k_dispatches"] += self._top_k_active
                if self._block:
                    next_dev = phase.newest = self._dispatch_block(step_key)
                else:
                    if self._sampled_active:
                        (next_dev, self._k, self._v, self._lengths,
                         *tail) = self._decode_sampled(
                            self.params, self._tokens_dev,
                            self._k, self._v, self._lengths,
                            self._active_dev, self._bt_dev,
                            self._temps_dev, self._top_ks_dev,
                            self._top_ps_dev, step_key, **self._tail,
                        )
                    else:
                        (next_dev, self._k, self._v, self._lengths,
                         *tail) = self._decode_greedy(
                            self.params, self._tokens_dev,
                            self._k, self._v, self._lengths,
                            self._active_dev, self._bt_dev, **self._tail,
                        )
                    self._tail = dict(zip(self._tail, tail))
                    self._tokens_dev = phase.newest = next_dev
                # Start the D2H copy NOW: it lands while this thread
                # distributes the previous step's tokens and the next
                # turn dispatches — the drain below then finds a
                # finished buffer instead of blocking.
                try:
                    next_dev.copy_to_host_async()
                    self._lengths.copy_to_host_async()
                except Exception:  # rtlint: disable=RT007 — optional prefetch; device_get covers it
                    pass
                # Dropped inside the phase: see the drained step's arrays
                # below (0.24 ms a turn on the v5e).
                step_key = None
            new_inflight = (snapshot, next_dev, self._lengths)
        if self._inflight is not None:
            prev_snapshot, prev_tokens, prev_lengths = self._inflight
            with phase("decode_fetch_wait"):
                # Intentional single drain: copy_to_host_async above
                # started this transfer a full step ago, so this is the
                # double-buffered collect, not a per-step sync.
                toks, lengths_np = jax.device_get(  # rtlint: disable=RT001
                    (prev_tokens, prev_lengths)
                )
            with phase("distribute"):
                if self._block:
                    self._distribute_blocks(prev_snapshot, toks, lengths_np)
                else:
                    self._distribute(prev_snapshot, toks, lengths_np)
                # The drained step's device arrays die here, inside a
                # phase, not at scope exit: releasing two buffers the
                # in-flight step still reads costs 1.6 ms a turn on the
                # v5e (chip run, PR 24), which the ledger first showed
                # as `other`.
                self._inflight = prev_tokens = prev_lengths = None
        self._inflight = new_inflight
        if snapshot:
            m = _engine_metrics()
            m["occupancy"].set(len(snapshot) / self.num_slots)
            m["waiting"].set(float(self._waiting_n))  # gauge snapshot: a stale int is fine
            m["kv_pages"].set(float(self._pool.in_use))

    def _evict_locked(self, s: int):
        """Free decode slot `s` (finished, cancelled or expired): the
        generation bump suppresses the one in-flight token it still
        produces under the lag."""
        del self._slots[s]
        self._free.append(s)
        self._gen[s] += 1
        self._active[s] = False
        self._temps[s] = 0.0
        self._top_ks[s] = 0
        self._top_ps[s] = 1.0
        self._params_dirty = True
        self._release_slot_pages_locked(s)

    def _expired_locked(self, s: int, h, now_wall: float) -> bool:
        """Whether decoding slot `s`'s request was cancelled or ran out of
        deadline: dead work never holds a TPU slot, so it is evicted
        mid-decode and the handle failed (cancel() already did for the
        cancelled case). Under the engine's lock."""
        if not (h.cancelled or (h.deadline_ts and now_wall > h.deadline_ts)):
            return False
        if not h.cancelled:
            self._deadline_expired += 1
            self._phase.lock_waited("handle", h._fail(
                RequestCancelledError(
                    f"deadline expired mid-decode "
                    f"(request {h.request_id}, "
                    f"{h.produced} tokens produced)",
                    reason="deadline", rid=str(h.request_id),
                )))
            observatory.record_deadline_expired("", "engine_decode")
        self._evict_locked(s)
        return True

    def _distribute_blocks(self, prev_snapshot, blocks, lengths_np):
        """`_distribute` for a drained PASS of a block-diffusion model: a
        slot whose pass was its block's commit is handed the block's
        tokens (a first block less the prompt's remainder, a last one cut
        at `max_new_tokens`); `eos_id` ends a request at the end of the
        block that holds it, and a slot is evicted before a block would
        pass `max_len`."""
        now_wall, phase, d = time.time(), self._phase, self._diffusion
        with self._lock_loop:
            self._steps += 1
            for s, gen, h, commit in prev_snapshot:
                if self._gen[s] != gen or self._slots.get(s) is not h:
                    continue  # evicted under the lag
                if self._expired_locked(s, h, now_wall):
                    continue
                if not commit:
                    continue
                skip, self._blk_skip[s] = self._blk_skip[s], 0
                toks = blocks[s, skip:][:h.max_new_tokens - h.produced]
                last = (h.produced + len(toks) >= h.max_new_tokens
                        or (self.eos_id is not None
                            and self.eos_id in toks)
                        or int(lengths_np[s]) + self._block
                        > self._last_block_end())
                for j, tok in enumerate(toks):
                    h.produced += 1
                    phase.lock_waited("handle", h._push(
                        int(tok), last and j == len(toks) - 1))
                d["blocks_committed"] += 1
                d["tokens_committed"] += len(toks)
                if last:
                    self._evict_locked(s)
        _engine_metrics()["tokens_per_pass"].set(
            d["tokens_committed"] / max(1, d["slot_passes"]))

    def _distribute(self, prev_snapshot, toks, lengths_np):
        """Push a drained step's tokens to their handles; evict what
        finished, was cancelled or ran out of deadline."""
        now_wall = time.time()
        # A handle says what its push waited for a consumer that held its
        # condition (it holds it while it takes a token).
        phase = self._phase
        with self._lock_loop:
            self._steps += 1
            for s, gen, h in prev_snapshot:
                if self._gen[s] != gen or self._slots.get(s) is not h:
                    continue  # evicted under the lag
                if self._expired_locked(s, h, now_wall):
                    continue
                tok = int(toks[s])
                h.produced += 1
                done = (
                    (self.eos_id is not None and tok == self.eos_id)
                    or h.produced >= h.max_new_tokens
                    # One in-flight step may still write: keep a row of
                    # margin.
                    or int(lengths_np[s]) >= self.max_len - 2
                )
                phase.lock_waited("handle", h._push(tok, done))
                if done:
                    self._evict_locked(s)

    def _loop(self):
        """Pipelined decode loop with ASYNC double-buffered fetch:
        dispatch step k+1 (inputs taken from step k's ON-DEVICE pick),
        start the non-blocking device->host copy of step k+1's outputs,
        then drain step k's copy — which was started a full iteration
        ago and has had an entire decode step to complete — and
        distribute its tokens. Eviction therefore lags one step (a
        finished slot rides one extra suppressed step before its slot
        frees), buying max(step, fetch) instead of step + fetch per
        token; in steady state the drain returns an already-landed
        buffer and the loop does ZERO avoidable host<->device traffic
        per step (sampling params device-resident, no per-step
        uploads)."""
        phase = self._phase
        while not self._stop_evt.is_set():
            try:
                if self._inflight is None and not self._prefilling:  # rtlint: disable=RT010 — _prefilling is only mutated on this loop thread; the lock covers submit()-side readers
                    # Nothing on the device and nothing to prefill: idle
                    # beside the turns until submit() (or the poll that
                    # chaos injections and shutdown ride) wakes the loop.
                    with phase("wait_for_work"):
                        self._work.wait(timeout=0.5)
                        self._work.clear()
                    with self._lock_loop:
                        self._ledger_pub = phase.snapshot()
                        # submit() raises it before setting _work: a
                        # miss here is caught by the next wait.
                        idle = not self._waiting_n
                    if idle:
                        self._apply_kv_chaos()
                        continue
                with phase("turn"):
                    self._turn()
                with self._lock_loop:
                    self._ledger_pub = phase.snapshot()
            except BaseException as e:  # noqa: BLE001 — fail all, keep serving
                with self._lock_loop:
                    pending = (
                        list(self._slots.values())
                        + self._drain_waiting_locked()
                        + [en["h"] for en in self._prefilling.values()]
                    )
                    for h in pending:
                        phase.lock_waited("handle", h._fail(e))
                    self._slots.clear()
                    self._prefilling.clear()
                    self._free = deque(range(self.num_slots))
                    # Donated buffers may have been consumed mid-failure:
                    # rebuild the cache (mesh placement included) before
                    # serving again.
                    cache = self._fresh_cache()
                    self._k, self._v = cache["k"], cache["v"]
                    self._lengths = cache["lengths"]
                    for name in ("rec", "ring"):
                        if name in cache:
                            self._tail[name] = cache[name]
                    # Every outstanding page reference pointed into the
                    # dead cache: reset the allocator, drop the prefix
                    # cache WITHOUT releasing (the refs are void), zero
                    # the table.
                    self._bt_dev = cache["block_tables"]
                    self._pool.reset()
                    if self._prefix_cache is not None:
                        self._prefix_cache.reset()
                    self._slot_pages.clear()
                    self._chaos_held = []
                    self._bt_host[:] = 0
                    self._bt_dirty = False
                    self._tokens_dev = self._replicated(
                        np.zeros(self.num_slots, dtype=np.int32))
                    if self._block:
                        self._blk = jax.tree.map(
                            self._replicated, paged_kv.init_block_state(
                                self.cfg, self.num_slots))
                    self._gen += 1  # orphan any in-flight snapshot
                    self._active[:] = False
                    self._temps[:] = 0.0
                    self._top_ks[:] = 0
                    self._top_ps[:] = 1.0
                    self._params_dirty = True
                self._inflight = phase.newest = None
                time.sleep(0.1)


class LLMReplica:
    """Replica class wrapping the engine: blocking generate, token
    streaming (rides the replica generator protocol -> SSE at the
    proxy), and engine stats for observability."""

    def __init__(self, model_loader, num_slots: int = 4, max_len: int = 256,
                 eos_id: Optional[int] = None,
                 default_max_new_tokens: int = 32,
                 prefill_chunk: int = 64,
                 page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 max_queued: Optional[int] = None):
        # The loader runs IN the replica process and may return
        # (params, cfg) or (params, cfg, mesh) — a Mesh cannot cross
        # the actor boundary as an argument, so tensor-parallel serving
        # builds its mesh (and shards params) inside the loader.
        loaded = model_loader()
        mesh = None
        if len(loaded) == 3:
            params, cfg, mesh = loaded
        else:
            params, cfg = loaded
        self.engine = ContinuousBatchingEngine(
            params, cfg, num_slots=num_slots, max_len=max_len,
            eos_id=eos_id, default_max_new_tokens=default_max_new_tokens,
            mesh=mesh, prefill_chunk=prefill_chunk,
            page_size=page_size, kv_pages=kv_pages, max_queued=max_queued,
        )

    @property
    def admission_bound(self) -> int:
        """Requests the engine itself holds before its submit() sheds:
        every slot and its whole waiting queue. The replica around this
        object admits up to here even where the deployment's own bound is
        lower, since admission lives in the engine."""
        return self.engine.num_slots + self.engine.queue_bound()

    def __call__(self, prompt, max_new_tokens: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None):
        # A propagated deadline bounds the blocking wait too (the engine
        # would cancel the slot anyway — don't outlive it by waiting the
        # full configured timeout).
        budget = request_context.remaining_budget()
        timeout = get_config().serve_result_timeout_s
        if budget != float("inf"):
            timeout = max(0.01, min(timeout, budget))
        return self.engine.submit(
            prompt, max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p,
        ).result(timeout=timeout)

    def stream(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None):
        h = self.engine.submit(
            prompt, max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p,
        )
        try:
            yield from h
        except GeneratorExit:
            # The consumer abandoned the stream (replica cancel_stream,
            # deadline expiry, client disconnect): free the decode slot
            # instead of generating tokens nobody reads.
            h.cancel("client")
            raise

    def stats(self):
        return self.engine.stats()

    def prefill_logits(self, prompt):
        return self.engine.prefill_logits(prompt)

    def __del__(self):  # rtlint: disable=RT007
        # Finalizer during interpreter teardown: modules may already be
        # unloaded, and raising from __del__ only prints noise.
        try:
            self.engine.shutdown()
        except Exception:  # noqa: BLE001
            pass


def llm_deployment(model_loader, *, num_slots: int = 4, max_len: int = 256,
                   eos_id: Optional[int] = None,
                   default_max_new_tokens: int = 32, num_replicas: int = 1,
                   max_ongoing_requests: int = 64,
                   ray_actor_options: Optional[dict] = None,
                   prefill_chunk: int = 64,
                   page_size: Optional[int] = None,
                   kv_pages: Optional[int] = None,
                   max_queued: Optional[int] = None):
    """A ready-to-run continuous-batching LLM application.

        app = llm_deployment(lambda: (params, cfg), num_slots=8)
        handle = serve.run(app, name="llm")
        tokens = handle.remote([1, 2, 3])          # blocking generate
        for t in handle.options(stream=True, method_name="stream") \
                .remote([1, 2, 3]): ...            # token stream

    max_ongoing_requests defaults high: admission control lives in the
    engine (waiting queue + slots), not the router."""
    from ray_tpu.serve.deployment import deployment

    dep = deployment(
        LLMReplica,
        name="LLMReplica",
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options or {},
    )
    return dep.bind(
        model_loader, num_slots=num_slots, max_len=max_len, eos_id=eos_id,
        default_max_new_tokens=default_max_new_tokens,
        prefill_chunk=prefill_chunk,
        page_size=page_size, kv_pages=kv_pages, max_queued=max_queued,
    )
