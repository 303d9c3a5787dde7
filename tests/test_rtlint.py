"""rtlint: per-rule fixtures (positive + negative twin + suppression),
baseline round-trip, and the repo-wide gate.

Each rule's positive fixture is the minimal reproduction of the bug
class; its negative twin is the same code with the one property that
makes it safe (a timeout, a lock, an epoch, a hoisted jit). The
suppression case proves `# rtlint: disable=RTxxx` works at both line
and def granularity.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.rtlint import Baseline, lint_paths, lint_source
from tools.rtlint.rules import ALL_RULES, rule_by_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def findings(src: str, path: str = "ray_tpu/serve/x.py"):
    return lint_source(textwrap.dedent(src), path)


def rule_ids(src: str, path: str = "ray_tpu/serve/x.py"):
    return [f.rule for f in findings(src, path)]


# -- RT001: host sync ------------------------------------------------------
RT001_POS = """
    import jax

    @jax.jit
    def step(x):
        return float(x.sum())
"""

RT001_NEG = """
    import jax

    @jax.jit
    def step(x):
        return x.sum()

    def report(x):
        return float(step(x))
"""


def test_rt001_traced_sync():
    assert "RT001" in rule_ids(RT001_POS)


def test_rt001_negative_twin():
    assert "RT001" not in rule_ids(RT001_NEG)


def test_rt001_loop_sync():
    src = """
        def drain(xs):
            out = []
            for x in xs:
                out.append(x.item())
            return out
    """
    fs = findings(src)
    assert [f.rule for f in fs] == ["RT001"]
    assert fs[0].token == ".item()"


def test_rt001_item_outside_loop_ok():
    assert "RT001" not in rule_ids("def f(x):\n    return x.item()\n")


# -- RT002: retrace risk ---------------------------------------------------
RT002_POS = """
    import jax

    def train(fns, x):
        for f in fns:
            y = jax.jit(f)(x)
        return y
"""

RT002_NEG = """
    import jax

    def train(fns, x):
        compiled = [jax.jit(f) for f in fns]
        return [g(x) for g in compiled]
"""


def test_rt002_jit_in_loop():
    assert "RT002" in rule_ids(RT002_POS)


def test_rt002_negative_twin():
    # List comprehensions build the wrappers once per fn, not per call.
    assert "RT002" not in rule_ids(
        "import jax\n\ndef f(g, x):\n    h = jax.jit(g)\n    return h(x)\n"
    )


def test_rt002_mutable_static_argnums():
    src = """
        import jax

        def build(f):
            return jax.jit(f, static_argnums=[0, 1])
    """
    fs = findings(src)
    assert [f.rule for f in fs] == ["RT002"]
    assert fs[0].token == "static-static_argnums"
    assert "RT002" not in rule_ids(src.replace("[0, 1]", "(0, 1)"))


def test_rt002_jit_def_in_loop():
    src = """
        import jax

        def outer(xs):
            for x in xs:
                @jax.jit
                def inner(y):
                    return y + x
                inner(x)
    """
    assert "jit-def-in-loop" in [f.token for f in findings(src)]


# -- RT003: unbounded blocking get ----------------------------------------
RT003_POS = """
    import ray_tpu as rt

    @rt.remote
    class Worker:
        def run(self, ref):
            return rt.get(ref)
"""

RT003_NEG = RT003_POS.replace("rt.get(ref)", "rt.get(ref, timeout=30)")


def test_rt003_actor_get_without_timeout():
    fs = findings(RT003_POS, path="ray_tpu/rl/x.py")
    assert [f.rule for f in fs] == ["RT003"]
    assert fs[0].token == "rt.get"


def test_rt003_negative_twin():
    assert "RT003" not in rule_ids(RT003_NEG, path="ray_tpu/rl/x.py")


def test_rt003_control_plane_free_function():
    src = """
        import ray_tpu as rt

        def bootstrap(refs):
            rt.get(refs)
    """
    assert "RT003" in rule_ids(src, path="ray_tpu/util/collective/x.py")
    # Same helper outside the control-plane scopes: not flagged.
    assert "RT003" not in rule_ids(src, path="ray_tpu/rl/x.py")


def test_rt003_bare_result():
    src = """
        @rt.remote
        class A:
            def m(self, fut):
                return fut.result()
    """
    src = "import ray_tpu as rt\n" + textwrap.dedent(src)
    assert "RT003" in [f.rule for f in lint_source(src, "ray_tpu/rl/x.py")]


# -- RT004: discarded ObjectRef -------------------------------------------
RT004_POS = """
    def push(workers, w):
        for r in workers:
            r.set_weights.remote(w)
"""

RT004_NEG = """
    import ray_tpu as rt

    def push(workers, w):
        refs = [r.set_weights.remote(w) for r in workers]
        rt.get(refs, timeout=60)
"""


def test_rt004_discarded_ref():
    fs = findings(RT004_POS, path="ray_tpu/rl/x.py")
    assert [f.rule for f in fs] == ["RT004"]
    assert fs[0].token == "set_weights"


def test_rt004_negative_twin():
    assert "RT004" not in rule_ids(RT004_NEG, path="ray_tpu/rl/x.py")


# -- RT005: unfenced collective -------------------------------------------
RT005_POS = """
    from ray_tpu.util import collective as col

    def setup(ws, rank):
        col.init_collective_group(ws, rank, "dcn", "g")
"""

RT005_NEG = RT005_POS.replace('"g")', '"g", epoch=0)')


def test_rt005_missing_epoch():
    fs = findings(RT005_POS, path="ray_tpu/rl/x.py")
    assert [f.rule for f in fs] == ["RT005"]
    assert fs[0].token == "init_collective_group"


def test_rt005_negative_twin():
    # Explicit epoch=0 is the call site asserting "never rebuilt".
    assert "RT005" not in rule_ids(RT005_NEG, path="ray_tpu/rl/x.py")


# -- RT006: cross-thread race ---------------------------------------------
RT006_POS = """
    import threading

    class Engine:
        def __init__(self):
            self._running = True
            self._t = threading.Thread(target=self._loop)

        def _loop(self):
            while self._running:
                pass

        def shutdown(self):
            self._running = False
"""

RT006_NEG_LOCK = """
    import threading

    class Engine:
        def __init__(self):
            self._running = True
            self._lock = threading.Lock()
            self._t = threading.Thread(target=self._loop)

        def _loop(self):
            while True:
                with self._lock:
                    if not self._running:
                        return

        def shutdown(self):
            with self._lock:
                self._running = False
"""

RT006_NEG_EVENT = """
    import threading

    class Engine:
        def __init__(self):
            self._stop_event = threading.Event()
            self._t = threading.Thread(target=self._loop)

        def _loop(self):
            while not self._stop_event.is_set():
                pass

        def shutdown(self):
            self._stop_event.set()
"""


def test_rt006_unlocked_flag():
    fs = findings(RT006_POS, path="ray_tpu/rl/x.py")
    assert [f.rule for f in fs] == ["RT006"]
    assert fs[0].token == "_running"


def test_rt006_lock_negative_twin():
    assert "RT006" not in rule_ids(RT006_NEG_LOCK, path="ray_tpu/rl/x.py")


def test_rt006_event_negative_twin():
    assert "RT006" not in rule_ids(RT006_NEG_EVENT, path="ray_tpu/rl/x.py")


def test_rt006_init_writes_exempt():
    # Writes before the thread starts happen-before it; only the
    # post-start caller-side write races.
    src = RT006_POS.replace(
        "def shutdown(self):\n            self._running = False",
        "def status(self):\n            return True",
    )
    assert "RT006" not in rule_ids(src, path="ray_tpu/rl/x.py")


# -- RT007: swallowed exception -------------------------------------------
RT007_POS = """
    def teardown(group):
        try:
            group.destroy()
        except Exception:
            pass
"""

RT007_NEG = """
    import logging

    def teardown(group):
        try:
            group.destroy()
        except OSError:
            pass
"""


def test_rt007_swallow_in_control_plane():
    fs = findings(RT007_POS, path="ray_tpu/train/x.py")
    assert [f.rule for f in fs] == ["RT007"]


def test_rt007_narrow_negative_twin():
    assert "RT007" not in rule_ids(RT007_NEG, path="ray_tpu/train/x.py")


def test_rt007_logging_body_ok():
    src = """
        import logging

        def teardown(group):
            try:
                group.destroy()
            except Exception:
                logging.warning("destroy failed", exc_info=True)
    """
    assert "RT007" not in rule_ids(src, path="ray_tpu/train/x.py")


def test_rt007_scoped_to_control_plane():
    assert "RT007" not in rule_ids(RT007_POS, path="ray_tpu/rl/x.py")


# -- suppressions ----------------------------------------------------------
def test_line_suppression():
    src = RT007_POS.replace("except Exception:",
                            "except Exception:  # rtlint: disable=RT007")
    assert "RT007" not in rule_ids(src, path="ray_tpu/train/x.py")


def test_def_suppression_covers_body():
    src = RT006_POS.replace(
        "def shutdown(self):",
        "def shutdown(self):  # rtlint: disable=RT006",
    )
    assert "RT006" not in rule_ids(src, path="ray_tpu/rl/x.py")


def test_suppression_is_rule_specific():
    # Disabling RT001 does not hide the RT007.
    src = RT007_POS.replace("except Exception:",
                            "except Exception:  # rtlint: disable=RT001")
    assert "RT007" in rule_ids(src, path="ray_tpu/train/x.py")


def test_blanket_suppression():
    src = RT007_POS.replace("except Exception:",
                            "except Exception:  # rtlint: disable")
    assert "RT007" not in rule_ids(src, path="ray_tpu/train/x.py")


# -- engine behavior -------------------------------------------------------
def test_syntax_error_yields_rt000():
    fs = lint_source("def broken(:\n", "ray_tpu/x.py")
    assert [f.rule for f in fs] == ["RT000"]


def test_fingerprint_is_line_independent():
    fs1 = findings(RT007_POS, path="ray_tpu/train/x.py")
    fs2 = findings("\n\n\n" + textwrap.dedent(RT007_POS),
                   path="ray_tpu/train/x.py")
    assert fs1[0].fingerprint == fs2[0].fingerprint
    assert fs1[0].line != fs2[0].line


def test_baseline_roundtrip(tmp_path):
    fs = findings(RT007_POS, path="ray_tpu/train/x.py")
    bl = Baseline.from_findings(fs)
    p = tmp_path / "baseline.json"
    bl.save(str(p))
    loaded = Baseline.load(str(p))
    assert loaded.counts == bl.counts
    assert loaded.new_findings(fs) == []
    # A second identical violation exceeds the baselined count.
    doubled = fs + fs
    assert len(loaded.new_findings(doubled)) == len(fs)
    # JSON on disk is the documented shape.
    data = json.loads(p.read_text())
    assert set(data) == {"comment", "findings"}


def test_baseline_stale_entries():
    bl = Baseline({"RT007|gone.py|f|swallow": 1})
    assert bl.stale_entries([]) == ["RT007|gone.py|f|swallow"]


def test_rule_catalog():
    ids = [r.id for r in ALL_RULES]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    assert ids == [f"RT{i:03d}" for i in range(1, 18)]
    assert rule_by_id("rt003").id == "RT003"
    assert rule_by_id("rt013").id == "RT013"
    assert rule_by_id("rt017").id == "RT017"
    for r in ALL_RULES:
        assert r.name and r.__doc__


# -- repo-wide gate --------------------------------------------------------
def test_repo_is_clean_against_baseline():
    """The tier-1 gate: linting ray_tpu/ yields no findings beyond the
    committed baseline. New violations fail here, with the finding text
    in the assertion message."""
    bl = Baseline.load(os.path.join(REPO, "tools", "rtlint",
                                    "baseline.json"))
    fs = lint_paths([os.path.join(REPO, "ray_tpu")], root=REPO)
    new = bl.new_findings(fs)
    assert not new, "new rtlint findings:\n" + "\n".join(map(str, new))


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "x.py"
    bad.write_text(textwrap.dedent(RT004_POS))
    env = dict(os.environ, PYTHONPATH=REPO)
    run = lambda *a: subprocess.run(  # noqa: E731
        [sys.executable, "-m", "tools.rtlint", *a],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    clean = run("--no-baseline", str(tmp_path / "nothing"))
    assert clean.returncode == 0
    dirty = run("--no-baseline", str(bad))
    assert dirty.returncode == 1
    assert "RT004" in dirty.stdout
    assert run("--explain", "RT006").returncode == 0
    assert run("--explain", "RT999").returncode == 2


# -- RT008: blocking call in async ----------------------------------------
RT008_POS = """
    import time

    async def handler():
        time.sleep(1.0)
"""

RT008_NEG = """
    import asyncio

    async def handler():
        await asyncio.sleep(1.0)
"""


def test_rt008_sleep_in_async():
    assert "RT008" in rule_ids(RT008_POS)


def test_rt008_negative_twin():
    assert "RT008" not in rule_ids(RT008_NEG)


def test_rt008_popen_in_async():
    src = """
        import subprocess

        async def launch(cmd):
            return subprocess.Popen(cmd)
    """
    assert "RT008" in rule_ids(src)


def test_rt008_executor_shipped_ok():
    src = """
        import asyncio, time

        async def handler(loop):
            await loop.run_in_executor(None, time.sleep, 1.0)
    """
    assert "RT008" not in rule_ids(src)


def test_rt008_suppression():
    src = """
        import time

        async def handler():
            time.sleep(1.0)  # rtlint: disable=RT008 — test hook
    """
    assert "RT008" not in rule_ids(src)


# -- RT009: deadline taint drop -------------------------------------------
RT009_POS = """
    def dispatch(handle, payload, meta):
        return handle.remote(payload)
"""

RT009_NEG = """
    def dispatch(handle, payload, meta):
        return handle.remote(payload, meta=meta)
"""


def test_rt009_dropped_meta():
    assert "RT009" in rule_ids(RT009_POS)


def test_rt009_negative_twin():
    assert "RT009" not in rule_ids(RT009_NEG)


def test_rt009_bind_counts_as_forwarding():
    src = """
        def dispatch(handle, payload, meta):
            with bind(meta):
                return handle.remote(payload)
    """
    assert "RT009" not in rule_ids(src)


def test_rt009_local_deadline_taint():
    src = """
        import time

        def handle_request(handle, payload, deadline_ms):
            deadline_ts = time.time() + deadline_ms / 1000.0
            return handle.remote(payload)
    """
    assert "RT009" in rule_ids(src)


def test_rt009_closure_hop_is_outer_functions():
    src = """
        def handle_request(handle, payload, meta):
            def go():
                return handle.remote(payload)
            return go()
    """
    assert "RT009" in rule_ids(src)


def test_rt009_annotation_taint():
    src = """
        def dispatch(handle, payload, card: "RequestMeta"):
            return handle.remote(payload)
    """
    assert "RT009" in rule_ids(src)


def test_rt009_suppression():
    src = """
        def dispatch(handle, payload, meta):
            return handle.remote(payload)  # rtlint: disable=RT009 — rides .options
    """
    assert "RT009" not in rule_ids(src)


# -- RT010: lock discipline ------------------------------------------------
RT010_POS = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def bump(self):
            with self._lock:
                self.n += 1

        def reset(self):
            self.n = 0
"""

RT010_NEG = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def bump(self):
            with self._lock:
                self.n += 1

        def reset(self):
            with self._lock:
                self.n = 0
"""


def test_rt010_bare_access():
    assert "RT010" in rule_ids(RT010_POS)


def test_rt010_negative_twin():
    assert "RT010" not in rule_ids(RT010_NEG)


def test_rt010_locked_suffix_exempt():
    src = """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def bump(self):
                with self._lock:
                    self._reset_locked()
                    self.n += 1

            def _reset_locked(self):
                self.n = 0
    """
    assert "RT010" not in rule_ids(src)


def test_rt010_init_exempt():
    src = """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def bump(self):
                with self._lock:
                    self.n += 1
    """
    assert "RT010" not in rule_ids(src)


def test_rt010_suppression():
    src = """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def bump(self):
                with self._lock:
                    self.n += 1

            def peek(self):
                return self.n  # rtlint: disable=RT010 — single-writer snapshot
    """
    assert "RT010" not in rule_ids(src)


# -- RT011: clock domains --------------------------------------------------
RT011_POS = """
    import time

    def elapsed(deadline_ts):
        t0 = time.monotonic()
        return deadline_ts - t0
"""

RT011_NEG = """
    import time

    def elapsed():
        t0 = time.monotonic()
        return time.monotonic() - t0
"""


def test_rt011_cross_domain_sub():
    assert "RT011" in rule_ids(RT011_POS)


def test_rt011_negative_twin():
    assert "RT011" not in rule_ids(RT011_NEG)


def test_rt011_monotonic_deadline_ok():
    src = """
        import time

        def waiter(timeout):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                pass
    """
    assert "RT011" not in rule_ids(src)


def test_rt011_wall_anchor_shape():
    src = """
        import time

        def stamp(dur_unknowable):
            return time.time() - dur_unknowable
    """
    assert "RT011" in rule_ids(src)


def test_rt011_suppression():
    src = """
        import time

        def stamp(mono_t):
            return time.time() - mono_t  # rtlint: disable=RT011 — wall anchor
    """
    assert "RT011" not in rule_ids(src)


# -- RT012: donated buffer reuse ------------------------------------------
RT012_POS = """
    import jax

    step = jax.jit(_step, donate_argnums=(0,))

    def loop(kv, x):
        out = step(kv, x)
        return kv.sum()
"""

RT012_NEG = """
    import jax

    step = jax.jit(_step, donate_argnums=(0,))

    def loop(kv, x):
        kv = step(kv, x)
        return kv.sum()
"""


def test_rt012_use_after_donate():
    assert "RT012" in rule_ids(RT012_POS)


def test_rt012_negative_twin():
    assert "RT012" not in rule_ids(RT012_NEG)


def test_rt012_swallowing_handler_without_rebind():
    src = """
        import jax

        step = jax.jit(_step, donate_argnums=(0,))

        def loop(kv, x):
            try:
                kv = step(kv, x)
            except RuntimeError:
                log("oops")
            return kv.sum()
    """
    assert "RT012" in rule_ids(src)


def test_rt012_handler_rebuilds_donated_state():
    src = """
        import jax

        step = jax.jit(_step, donate_argnums=(0,))

        def loop(kv, x):
            try:
                kv = step(kv, x)
            except RuntimeError:
                kv = fresh_cache()
            return kv.sum()
    """
    assert "RT012" not in rule_ids(src)


def test_rt012_reraising_handler_ok():
    src = """
        import jax

        step = jax.jit(_step, donate_argnums=(0,))

        def loop(kv, x):
            try:
                kv = step(kv, x)
            except RuntimeError:
                raise
            return kv.sum()
    """
    assert "RT012" not in rule_ids(src)


def test_rt012_suppression():
    src = """
        import jax

        step = jax.jit(_step, donate_argnums=(0,))

        def loop(kv, x):
            out = step(kv, x)
            return kv.sum()  # rtlint: disable=RT012 — loop rebinds first
    """
    assert "RT012" not in rule_ids(src)


# -- RT013: metrics discipline --------------------------------------------
RT013_POS = """
    BOUNDARIES = [0.1, 0.5, 1.0]

    def widen():
        BOUNDARIES.append(5.0)
"""

RT013_NEG = """
    BOUNDARIES = (0.1, 0.5, 1.0)

    def widen():
        return BOUNDARIES + (5.0,)
"""


def test_rt013_boundary_mutation():
    assert "RT013" in rule_ids(RT013_POS)


def test_rt013_negative_twin():
    assert "RT013" not in rule_ids(RT013_NEG)


def test_rt013_boundaries_list_literal():
    src = """
        h = Histogram("latency", boundaries=[0.1, 0.5, 1.0])
    """
    assert "RT013" in rule_ids(src)


def test_rt013_boundaries_tuple_ok():
    src = """
        h = Histogram("latency", boundaries=(0.1, 0.5, 1.0))
    """
    assert "RT013" not in rule_ids(src)


def test_rt013_per_request_label():
    src = """
        def record(m, rid):
            m.inc(1, tags={"rid": rid})
    """
    assert "RT013" in rule_ids(src)


def test_rt013_bounded_label_ok():
    src = """
        def record(m, model):
            m.inc(1, tags={"model": model})
    """
    assert "RT013" not in rule_ids(src)


def test_rt013_suppression():
    src = """
        def record(m, tenant):
            m.inc(1, tags={"tenant": tenant})  # rtlint: disable=RT013 — admission-bounded
    """
    assert "RT013" not in rule_ids(src)


# -- project model / call graph -------------------------------------------
def _write(tree, base):
    for rel, src in tree.items():
        p = base / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))


def test_callgraph_actor_reach_across_files(tmp_path):
    from tools.rtlint import analyze_paths
    _write({
        "helpers.py": """
            import ray_tpu as rt

            def fetch(ref):
                return rt.get(ref)
        """,
        "actors.py": """
            import ray_tpu as rt
            from helpers import fetch

            @rt.remote
            class A:
                def m(self, ref):
                    return fetch(ref)
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    hits = [f for f in res.findings if f.rule == "RT003"]
    assert hits and hits[0].path == "helpers.py"
    assert "A.m" in hits[0].message


def test_callgraph_reexport_and_self_method_resolution(tmp_path):
    """One chain exercising both: `from pkg import work` resolves
    through pkg/__init__'s re-export, and async context propagates
    through a self-method call (`run` -> self._go -> work)."""
    from tools.rtlint import analyze_paths
    _write({
        "pkg/__init__.py": "from pkg.impl import work\n",
        "pkg/impl.py": """
            import time

            def work():
                time.sleep(1)
        """,
        "loop.py": """
            from pkg import work

            class Srv:
                async def run(self):
                    self._go()

                def _go(self):
                    work()
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    hits = [f for f in res.findings if f.rule == "RT008"]
    assert hits and hits[0].path == "pkg/impl.py"


def test_callgraph_import_cycle_terminates(tmp_path):
    from tools.rtlint import analyze_paths
    _write({
        "a_mod.py": """
            import b_mod

            def fa():
                return b_mod.fb()
        """,
        "b_mod.py": """
            import a_mod

            def fb():
                return a_mod.fa()
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert res.files == 2
    assert not [f for f in res.findings if f.rule == "RT000"]


def test_crash_safety_rt000_on_syntax_error(tmp_path):
    from tools.rtlint import analyze_paths
    (tmp_path / "broken.py").write_text("def broken(:\n")
    (tmp_path / "fine.py").write_text("x = 1\n")
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    rt000 = [f for f in res.findings if f.rule == "RT000"]
    assert len(rt000) == 1 and rt000[0].path == "broken.py"
    assert res.files == 2


# -- CLI: formats, jobs, cache, changed, stats -----------------------------
def _cli(*args, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "tools.rtlint", *args],
        capture_output=True, text=True, env=env, cwd=cwd,
    )


def test_cli_json_format(tmp_path):
    bad = tmp_path / "x.py"
    bad.write_text(textwrap.dedent(RT004_POS))
    out = _cli("--no-baseline", "--no-cache", "--format", "json", str(bad))
    assert out.returncode == 1
    doc = json.loads(out.stdout)
    assert doc["tool"] == "rtlint"
    assert doc["new_findings"] and \
        doc["new_findings"][0]["rule"] == "RT004"


def test_cli_sarif_format(tmp_path):
    bad = tmp_path / "x.py"
    bad.write_text(textwrap.dedent(RT004_POS))
    out = _cli("--no-baseline", "--no-cache", "--format", "sarif", str(bad))
    assert out.returncode == 1
    doc = json.loads(out.stdout)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "RT004"
    assert results[0]["partialFingerprints"]["rtlint/v1"]


def test_cli_jobs_matches_serial(tmp_path):
    serial = _cli("--no-baseline", "--no-cache", "--format", "json",
                  "ray_tpu/serve/")
    par = _cli("--no-baseline", "--no-cache", "--format", "json",
               "--jobs", "4", "ray_tpu/serve/")
    assert serial.returncode == par.returncode
    a, b = json.loads(serial.stdout), json.loads(par.stdout)
    key = lambda f: (f["rule"], f["path"], f["line"])  # noqa: E731
    assert sorted(map(key, a["new_findings"])) == \
        sorted(map(key, b["new_findings"]))
    assert a["total_findings"] == b["total_findings"]


def test_cli_cache_warm_run_consistent(tmp_path):
    cache = tmp_path / "cache.json"
    cold = _cli("--no-baseline", "--cache", str(cache), "ray_tpu/util/")
    assert cache.exists()
    warm = _cli("--no-baseline", "--cache", str(cache), "ray_tpu/util/")
    assert cold.stdout == warm.stdout
    assert cold.returncode == warm.returncode


def test_cli_changed_mode(tmp_path):
    git = lambda *a: subprocess.run(  # noqa: E731
        ["git", *a], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                 GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t"),
    )
    git("init", "-q")
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")
    # no changed files: exits clean without linting anything
    out = _cli("--no-baseline", "--no-cache", "--changed", str(tmp_path),
               "--root", str(tmp_path), cwd=str(tmp_path))
    assert out.returncode == 0 and "no changed" in out.stdout
    # an untracked offender is picked up by --changed
    (tmp_path / "bad.py").write_text(textwrap.dedent(RT004_POS))
    out = _cli("--no-baseline", "--no-cache", "--changed", str(tmp_path),
               "--root", str(tmp_path), cwd=str(tmp_path))
    assert out.returncode == 1 and "RT004" in out.stdout


def test_cli_stats(tmp_path):
    bad = tmp_path / "x.py"
    bad.write_text(textwrap.dedent(RT004_POS))
    out = _cli("--no-baseline", "--no-cache", "--stats", str(bad))
    assert out.returncode == 1
    assert "RT004" in out.stdout and "total" in out.stdout


def test_cli_usage_errors():
    assert _cli("--jobs", "0").returncode == 2
    assert _cli("--rules", "RT999").returncode == 2


def test_default_targets_cover_tools():
    from tools.rtlint import DEFAULT_TARGETS
    assert "ray_tpu" in DEFAULT_TARGETS
    assert "tools" in DEFAULT_TARGETS


def test_repo_default_targets_clean_against_baseline():
    """The full gate over the default target set (ray_tpu/, tools/),
    exactly what `make lint` runs."""
    out = _cli("--no-cache")
    assert out.returncode == 0, out.stdout + out.stderr


# =========================================================================
# v3: path-sensitive lifecycle rules (RT014-RT016), protocol conformance
# (RT017), CFG twins, and the --fix autofixer.
# =========================================================================

# -- RT014: PagePool pages ------------------------------------------------
RT014_POS = """
    class KV:
        def grab(self, n):
            pages = self._pool.alloc(n)
            if n > 4:
                return None
            self._pool.release(pages)
"""

RT014_NEG = """
    class KV:
        def grab(self, n):
            pages = self._pool.alloc(n)
            if n > 4:
                self._pool.release(pages)
                return None
            self._pool.release(pages)
"""


def test_rt014_early_return_leak():
    ids = rule_ids(RT014_POS)
    assert "RT014" in ids


def test_rt014_negative_twin():
    assert "RT014" not in rule_ids(RT014_NEG)


def test_rt014_exception_path_leak_and_finally_twin():
    """The PR 11 incident shape: a step between alloc and release
    raises, and the pages never come back. try/finally (with its
    re-raise edge) is the negative twin."""
    pos = """
        class KV:
            def grab(self, n):
                pages = self._pool.alloc(n)
                self._log(n)
                self._pool.release(pages)
    """
    fs = findings(pos)
    assert any(f.rule == "RT014" and "exception path" in f.message
               for f in fs)
    neg = """
        class KV:
            def grab(self, n):
                pages = self._pool.alloc(n)
                try:
                    self._log(n)
                finally:
                    self._pool.release(pages)
    """
    assert "RT014" not in rule_ids(neg)


def test_rt014_double_free():
    src = """
        class KV:
            def drop(self, n, err):
                pages = self._pool.alloc(n)
                self._pool.release(pages)
                if err:
                    self._pool.release(pages)
    """
    fs = findings(src)
    assert any(f.rule == "RT014" and "released twice" in f.message
               for f in fs)


def test_rt014_rollback_twin():
    """Release on the except edge (all-or-nothing rollback) is clean."""
    src = """
        class KV:
            def grab(self, n):
                pages = self._pool.alloc(n)
                try:
                    self._fill(n)
                except Exception:
                    self._pool.release(pages)
                    raise
                return pages
    """
    assert "RT014" not in rule_ids(src)


def test_rt014_loop_carried_acquire_twins():
    """CFG twin: rebinding the holding variable on the loop back edge
    leaks one allocation per iteration."""
    pos = """
        class KV:
            def churn(self, xs):
                for x in xs:
                    pages = self._pool.alloc(x)
                self._pool.release(pages)
    """
    fs = findings(pos)
    assert any(f.rule == "RT014" and "rebound" in f.message for f in fs)
    neg = """
        class KV:
            def churn(self, xs):
                for x in xs:
                    pages = self._pool.alloc(x)
                    self._pool.release(pages)
    """
    assert "RT014" not in rule_ids(neg)


def test_rt014_with_suppress_twins():
    """CFG twin: contextlib.suppress turns the raise edge into a fall-
    through exit, so the leak survives the with block."""
    pos = """
        import contextlib

        class KV:
            def grab(self, n):
                with contextlib.suppress(ValueError):
                    pages = self._pool.alloc(n)
                    self._step(n)
                return None
    """
    fs = findings(pos)
    assert any(f.rule == "RT014" for f in fs)
    neg = """
        import contextlib

        class KV:
            def grab(self, n):
                with contextlib.suppress(ValueError):
                    pages = self._pool.alloc(n)
                    try:
                        self._step(n)
                    finally:
                        self._pool.release(pages)
                return None
    """
    assert "RT014" not in rule_ids(neg)


def test_rt014_generator_early_close_twins():
    """CFG twin: a generator can be close()d at any yield
    (GeneratorExit), so pages held across a yield leak unless a
    try/finally releases them."""
    pos = """
        class KV:
            def stream(self, n):
                pages = self._pool.alloc(n)
                yield n
                self._pool.release(pages)
    """
    fs = findings(pos)
    assert any(f.rule == "RT014" for f in fs)
    neg = """
        class KV:
            def stream(self, n):
                pages = self._pool.alloc(n)
                try:
                    yield n
                finally:
                    self._pool.release(pages)
    """
    assert "RT014" not in rule_ids(neg)


def test_rt014_incref_obligation_twins():
    """Arg-form acquire: `pool.incref(tok)` owes a decref on every
    path that can raise before the handoff."""
    pos = """
        class KV:
            def pin(self, tok):
                self._pool.incref(tok)
                self._check_capacity()
                self._table.adopt(tok)
    """
    fs = findings(pos)
    assert any(f.rule == "RT014" and "exception path" in f.message
               for f in fs)
    neg = """
        class KV:
            def pin(self, tok):
                self._pool.incref(tok)
                try:
                    self._check_capacity()
                except Exception:
                    self._pool.decref(tok)
                    raise
                self._table.adopt(tok)
    """
    assert "RT014" not in rule_ids(neg)


def test_rt014_suppression():
    src = """
        class KV:
            def grab(self, n):
                pages = self._pool.alloc(n)  # rtlint: disable=RT014
                if n > 4:
                    return None
                self._pool.release(pages)
    """
    assert "RT014" not in rule_ids(src)


# -- RT015: bundles + fences ----------------------------------------------
def test_rt015_release_leak():
    """The PR 14 shape: reserved bundles never released on the early
    exit, wedging the placement group."""
    src = """
        def scale(idx, err):
            b = reserve_pg_bundles(idx)
            if err:
                return None
            release_pg_bundles(b)
            return b
    """
    fs = findings(src, path="ray_tpu/train/x.py")
    assert any(f.rule == "RT015" and "still held" in f.message
               for f in fs)


def test_rt015_double_credit():
    """The PR 10 cancel_bundle shape: one bundle credited twice."""
    src = """
        def teardown(idx, force):
            b = reserve_pg_bundles(idx)
            cancel_bundle(b)
            if force:
                cancel_bundle(b)
    """
    fs = findings(src, path="ray_tpu/train/x.py")
    assert any(f.rule == "RT015" and "released twice" in f.message
               for f in fs)


def test_rt015_negative_twin():
    src = """
        def scale(idx, err):
            b = reserve_pg_bundles(idx)
            if err:
                release_pg_bundles(b)
                return None
            release_pg_bundles(b)
            return None
    """
    assert "RT015" not in rule_ids(src, path="ray_tpu/train/x.py")


def test_rt015_fence_obligation_twins():
    """Fences are arg-form: arming owes a lift on every exit path even
    though the token keeps circulating as a plain id."""
    pos = """
        class GCS:
            def claim(self, job):
                self.arm_fence(job)
                self._audit(job)
                if self._stale(job):
                    return False
                self.lift_fence(job)
                return True
    """
    fs = findings(pos, path="ray_tpu/gcs.py")
    assert any(f.rule == "RT015" and "fence" in f.message for f in fs)
    neg = """
        class GCS:
            def claim(self, job):
                self.arm_fence(job)
                try:
                    self._audit(job)
                    if self._stale(job):
                        return False
                    return True
                finally:
                    self.lift_fence(job)
    """
    assert "RT015" not in rule_ids(neg, path="ray_tpu/gcs.py")


# -- RT016: refs + locks --------------------------------------------------
def test_rt016_dropped_ref():
    src = """
        def kick(f, x):
            r = f.remote(x)
            return None
    """
    fs = findings(src)
    assert any(f.rule == "RT016" and "ObjectRef" in f.message
               for f in fs)


def test_rt016_got_ref_twin():
    src = """
        import ray_tpu as rt

        def kick(f, x):
            r = f.remote(x)
            return rt.get(r)
    """
    assert "RT016" not in rule_ids(src)


def test_rt016_stored_ref_twin():
    """Storing the ref somewhere it will be reaped counts as an escape,
    not a leak."""
    src = """
        def kick(self, f, x):
            r = f.remote(x)
            self._inflight.append(r)
    """
    assert "RT016" not in rule_ids(src)


def test_rt016_actor_handle_not_a_ref():
    """`Actor.options().remote()` builds a handle and `rt.remote(cls)`
    wraps a class — neither is an ObjectRef."""
    src = """
        import ray_tpu as rt

        def boot(cls):
            actor = Worker.options(num_cpus=1).remote()
            wrapped = rt.remote(cls)
            return None
    """
    assert "RT016" not in rule_ids(src)


def test_rt016_lock_across_yield_twins():
    pos = """
        class Buf:
            def drain(self):
                self._lock.acquire()
                for item in self._q:
                    yield item
                self._lock.release()
    """
    fs = findings(pos)
    assert any(f.rule == "RT016" and "yield" in f.message for f in fs)
    neg = """
        class Buf:
            def drain(self):
                while True:
                    with self._lock:
                        item = self._q.pop()
                    yield item
    """
    assert "RT016" not in rule_ids(neg)


def test_rt016_lock_exception_path():
    pos = """
        class Buf:
            def push(self, x):
                self._lock.acquire()
                self._validate(x)
                self._lock.release()
    """
    fs = findings(pos)
    assert any(f.rule == "RT016" and "lock" in f.message for f in fs)
    neg = """
        class Buf:
            def push(self, x):
                self._lock.acquire()
                try:
                    self._validate(x)
                finally:
                    self._lock.release()
    """
    assert "RT016" not in rule_ids(neg)


def test_rt016_suppression():
    src = """
        def kick(f, x):
            r = f.remote(x)  # rtlint: disable=RT016 — reaped by GC test
            return None
    """
    assert "RT016" not in rule_ids(src)


def test_lifecycle_interprocedural_release(tmp_path):
    """A helper that releases counts: `self._cleanup(pages)` is the
    release when _cleanup reaches pool.release, project-wide."""
    from tools.rtlint import analyze_paths
    _write({
        "kv.py": """
            class KV:
                def grab(self, n):
                    pages = self._pool.alloc(n)
                    if n > 4:
                        self._cleanup(pages)
                        return None
                    self._pool.release(pages)

                def _cleanup(self, pages):
                    self._pool.release(pages)
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert not [f for f in res.findings if f.rule == "RT014"]


def test_lifecycle_interprocedural_returns_fresh(tmp_path):
    """`pages = self._grab(n)` starts tracking when _grab returns a
    fresh alloc two frames down."""
    from tools.rtlint import analyze_paths
    _write({
        "kv.py": """
            class KV:
                def _grab(self, n):
                    return self._pool.alloc(n)

                def use(self, n):
                    pages = self._grab(n)
                    if n > 4:
                        return None
                    self._pool.release(pages)
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in res.findings if f.rule == "RT014"]


def test_lifecycle_path_in_message():
    """Findings carry the exact leaking line sequence."""
    fs = findings(RT014_POS)
    leak = [f for f in fs if f.rule == "RT014"]
    assert leak and "path" in leak[0].message
    assert "->" in leak[0].message or leak[0].message.count("path")


# -- RT017: protocol conformance ------------------------------------------
def test_rt017_gcs_field_drift(tmp_path):
    from tools.rtlint import analyze_paths
    _write({
        "server.py": """
            class GCS:
                def h_frob(self, d):
                    job = d["job"]
                    return {"ok": True, "seq": 1}
        """,
        "client.py": """
            class Client:
                def frob(self):
                    resp = self._gcs_call("frob", {"jbo": 1})
                    return resp["seq"]

                def nope(self):
                    return self._gcs_call("norb", {})
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    msgs = [f.message for f in res.findings if f.rule == "RT017"]
    assert any("omits key(s) ['job']" in m for m in msgs)
    assert any("['jbo']" in m and "never reads" in m for m in msgs)
    assert any("h_norb" in m for m in msgs)


def test_rt017_gcs_negative_twin(tmp_path):
    from tools.rtlint import analyze_paths
    _write({
        "server.py": """
            class GCS:
                def h_frob(self, d):
                    job = d["job"]
                    extra = d.get("extra")
                    return {"ok": True, "seq": 1}
        """,
        "client.py": """
            class Client:
                def frob(self):
                    resp = self._gcs_call("frob", {"job": 1, "extra": 2})
                    return resp["seq"]
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert not [f for f in res.findings if f.rule == "RT017"]


def test_rt017_gcs_response_key_drift(tmp_path):
    from tools.rtlint import analyze_paths
    _write({
        "server.py": """
            class GCS:
                def h_frob(self, d):
                    job = d["job"]
                    return {"ok": True}
        """,
        "client.py": """
            class Client:
                def frob(self):
                    resp = self._gcs_call("frob", {"job": 1})
                    return resp["seq"]
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    msgs = [f.message for f in res.findings if f.rule == "RT017"]
    assert any("'seq'" in m and "only returns" in m for m in msgs)


def test_rt017_gcs_conditional_read_is_optional(tmp_path):
    """A d["k"] read only reachable under a branch is optional from the
    client's view — the h_actor_ready error-path shape."""
    from tools.rtlint import analyze_paths
    _write({
        "server.py": """
            class GCS:
                def h_ready(self, d):
                    if d.get("error"):
                        return {"ok": False}
                    else:
                        addr = d["address"]
                        return {"ok": True}
        """,
        "client.py": """
            class Client:
                def fail(self):
                    return self._gcs_call("ready", {"error": "boom"})
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert not [f for f in res.findings if f.rule == "RT017"]


def test_rt017_chaos_table_twins(tmp_path):
    from tools.rtlint import analyze_paths
    pos = '''
        """Chaos hooks.

        Injection table:

          drop_gcs(p)        | gcs        | drops p of RPCs
          ghost_hook(x)      | nowhere    | stale row
        """

        def drop_gcs(p):
            _require_enabled()
            return p

        def undocumented_hook(q):
            _require_enabled()
            return q
    '''
    _write({"pkg/_private/chaos.py": pos}, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    msgs = [f.message for f in res.findings if f.rule == "RT017"]
    assert any("undocumented_hook" in m and "missing from" in m
               for m in msgs)
    assert any("ghost_hook" in m and "stale row" in m for m in msgs)
    neg = '''
        """Chaos hooks.

        Injection table:

          drop_gcs(p)        | gcs        | drops p of RPCs
        """

        def drop_gcs(p):
            _require_enabled()
            return p
    '''
    _write({"pkg2/_private/chaos.py": neg}, tmp_path)
    res = analyze_paths([str(tmp_path / "pkg2")], root=str(tmp_path))
    assert not [f for f in res.findings if f.rule == "RT017"]


def test_rt017_panel_metric_drift(tmp_path):
    from tools.rtlint import analyze_paths
    _write({
        "metrics.py": """
            from ray_tpu.util.metrics import Counter

            REQS = Counter("requests")
        """,
        "dashboard/grafana.py": """
            PANELS = [
                {"title": "good", "expr": "rate(requests_total[5m])"},
                {"title": "bad", "expr": "rate(gone_metric_total[5m])"},
            ]
        """,
    }, tmp_path)
    res = analyze_paths([str(tmp_path)], root=str(tmp_path))
    msgs = [f.message for f in res.findings if f.rule == "RT017"]
    assert any("gone_metric_total" in m for m in msgs)
    assert not any("requests" in m for m in msgs)


def test_rt017_version_literal_twins():
    pos = """
        def read(doc):
            if doc.get("schema") == 2:
                return doc
        def write():
            return {"schema": 2, "x": 1}
    """
    fs = findings(pos)
    assert sum(1 for f in fs if f.rule == "RT017") == 2
    neg = """
        SCHEMA_VERSION = 2
        def read(doc):
            if doc.get("schema") == SCHEMA_VERSION:
                return doc
        def write():
            return {"schema": SCHEMA_VERSION, "x": 1}
    """
    assert "RT017" not in rule_ids(neg)


def test_rt017_suppression():
    src = """
        def read(doc):
            if doc.get("schema") == 2:  # rtlint: disable=RT017 — v2 migration shim
                return doc
    """
    assert "RT017" not in rule_ids(src)


# -- CFG builder ----------------------------------------------------------
def test_cfg_try_finally_reraise_edges():
    """The finally body must be reachable on the exceptional path and
    that copy must re-raise (edge toward the raise exit), not fall
    through to the normal tail."""
    import ast as _ast
    from tools.rtlint.cfg import build_cfg
    src = textwrap.dedent("""
        def f(self, n):
            self.step(n)
            try:
                self.work(n)
            finally:
                self.cleanup(n)
            return n
    """)
    fn = _ast.parse(src).body[0]
    cfg = build_cfg(fn)
    # at least two copies of the finally body exist (normal + exc)
    cleanup_line = fn.body[1].finalbody[0].lineno
    cleanups = [i for i, s in enumerate(cfg.stmts)
                if getattr(s, "lineno", None) == cleanup_line]
    assert len(cleanups) >= 2


def test_cfg_loop_back_edge():
    import ast as _ast
    from tools.rtlint.cfg import build_cfg
    src = textwrap.dedent("""
        def f(self, xs):
            for x in xs:
                self.step(x)
            return None
    """)
    fn = _ast.parse(src).body[0]
    cfg = build_cfg(fn)
    # some edge points backward (to an earlier node): the loop
    assert any(dst < src_i for src_i, dsts in cfg.succ.items()
               for dst, _label in dsts)


# -- --fix autofixer ------------------------------------------------------
def test_fix_rt004_leash_and_idempotency():
    from tools.rtlint.fix import fix_source
    src = textwrap.dedent("""
        import ray_tpu as rt

        def kick(f, xs):
            for x in xs:
                f.remote(x)
    """)
    out, notes = fix_source(src, "t.py")
    assert "rt.wait([_reaped], timeout=0)" in out
    assert any("RT004" in n for n in notes)
    # the rewritten form is clean under both RT004 and RT016
    ids = [f.rule for f in lint_source(out, "ray_tpu/serve/x.py")]
    assert "RT004" not in ids and "RT016" not in ids
    # idempotent: fix(fix(s)) == fix(s)
    out2, notes2 = fix_source(out, "t.py")
    assert out2 == out and not notes2


def test_fix_rt004_requires_rt_import():
    from tools.rtlint.fix import fix_source
    src = "def kick(f):\n    f.remote()\n"
    out, notes = fix_source(src, "t.py")
    assert out == src
    assert any("skipped" in n for n in notes)


def test_fix_rt013_tuple_freeze_and_idempotency():
    from tools.rtlint.fix import fix_source
    src = textwrap.dedent("""
        H = Histogram("lat", boundaries=[0.1, 1.0])
        ONE = get_or_create("n", boundaries=[5])
    """)
    out, notes = fix_source(src, "t.py")
    assert 'boundaries=(0.1, 1.0)' in out
    assert 'boundaries=(5,)' in out          # single elt stays a tuple
    assert "RT013" not in [f.rule for f in lint_source(
        out, "ray_tpu/serve/x.py")]
    out2, notes2 = fix_source(out, "t.py")
    assert out2 == out and not notes2


def test_fix_respects_line_restriction():
    """Driven by finding lines: sites not in the restriction set (e.g.
    suppressed ones) stay untouched."""
    from tools.rtlint.fix import fix_source
    src = textwrap.dedent("""
        import ray_tpu as rt

        def kick(f, x):
            f.remote(x)
            f.remote(x)
    """)
    out, _ = fix_source(src, "t.py", rt004_lines={5}, rt013_lines=set())
    assert out.count("rt.wait") == 1


def test_cli_fix_applies_and_exits_clean(tmp_path):
    bad = tmp_path / "x.py"
    bad.write_text(textwrap.dedent("""
        import ray_tpu as rt

        def kick(f, x):
            f.remote(x)
    """))
    out = _cli("--no-baseline", "--no-cache", "--fix", str(bad),
               "--root", str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "rt.wait" in bad.read_text()


def test_cli_sarif_out_artifact(tmp_path):
    bad = tmp_path / "x.py"
    bad.write_text(textwrap.dedent(RT004_POS))
    art = tmp_path / "out.sarif"
    out = _cli("--no-baseline", "--no-cache", "--sarif-out", str(art),
               str(bad))
    assert out.returncode == 1
    doc = json.loads(art.read_text())
    assert doc["runs"][0]["results"][0]["ruleId"] == "RT004"
