"""The chip-free wait of `bench/procs.py` and `run.setup_seconds`, held by
the tests the driver runs: the cases of `bench/tests/test_procs.py` (the
harness's own, which tier-1 does not run), owed to this gate since PR 49
(`PERF.md` section 7). At once on a clean machine, a message naming the
holder when a (fake) device file stays open, and, once no process holds
one, a wait until the kernel opens every file (an injected opener that is
busy n times stands for a VFIO group being given back). And `setup_s`,
which leaves that wait at a run's start out and nothing else."""

import errno
import os
import subprocess
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import procs  # noqa: E402
import spec  # noqa: E402


def fake_devices(tmp_path, names=("0", "1")):
    for n in names:
        (tmp_path / n).write_bytes(b"")
    return (str(tmp_path / "*"),)


def opener_failing(err, times, only=None):
    """An opener that raises `err` on its first `times` calls for a path
    (every path, or those named `only`), then opens it like `os.open`."""
    calls = {}

    def opener(path, flags):
        calls[path] = calls.get(path, 0) + 1
        if calls[path] <= times and (only is None
                                     or os.path.basename(path) in only):
            raise OSError(err, os.strerror(err), path)
        return os.open(path, flags)

    opener.calls = calls
    return opener


def test_returns_at_once_on_a_clean_machine():
    t0 = time.monotonic()
    assert procs.wait_chip_free(5.0) < 1.0
    assert time.monotonic() - t0 < 1.0


def test_times_out_naming_the_holder(tmp_path):
    fake = tmp_path / "accel0"
    fake.write_bytes(b"")
    holder = subprocess.Popen(
        [sys.executable, "-c",
         f"import time; f = open({str(fake)!r}); print('open', flush=True); "
         "time.sleep(60)"], stdout=subprocess.PIPE)
    try:
        holder.stdout.readline()
        with pytest.raises(procs.ChipBusy) as e:
            procs.wait_chip_free(0.5, globs=(str(tmp_path / "accel*"),))
        assert f"pid {holder.pid}" in str(e.value) and "accel0" in str(e.value)
    finally:
        holder.kill()
        holder.wait()
    assert procs.wait_chip_free(5.0, globs=(str(tmp_path / "accel*"),)) < 1.0


@pytest.mark.parametrize("n", [0, 1, 3])
def test_busy_n_times_then_free_returns_after_the_nth_poll(tmp_path, n):
    globs = fake_devices(tmp_path)
    opener = opener_failing(errno.EBUSY, n, only={"1"})
    waited = procs.wait_chip_free(5.0, globs=globs, poll_s=0.05, opener=opener)
    # Every file is tried at every look; the look after the n-th busy one
    # is the last.
    assert opener.calls == {str(tmp_path / "0"): n + 1,
                            str(tmp_path / "1"): n + 1}
    assert isinstance(waited, float) and waited >= n * 0.05
    assert waited == pytest.approx(waited.unheld_s + waited.opened_s)
    assert waited.unheld_s < 0.05 and waited.opened_s >= n * 0.05
    assert waited.probe_ms < 50.0
    assert "no holder after" in waited.parts()


def test_busy_past_the_limit_names_the_file_and_ebusy(tmp_path):
    globs = fake_devices(tmp_path)
    opener = opener_failing(errno.EBUSY, 10**6, only={"1"})
    t0 = time.monotonic()
    with pytest.raises(procs.ChipBusy) as e:
        procs.wait_chip_free(0.3, globs=globs, poll_s=0.05, opener=opener)
    assert 0.3 <= time.monotonic() - t0 < 2.0
    said = str(e.value)
    assert str(tmp_path / "1") in said and "EBUSY" in said
    assert str(tmp_path / "0") not in said and "no process holds" in said


@pytest.mark.parametrize("err", [errno.EACCES, errno.ENOENT, errno.EINVAL,
                                 errno.EISDIR])
def test_an_error_other_than_ebusy_is_not_waited_on(tmp_path, err):
    globs = fake_devices(tmp_path)
    opener = opener_failing(err, 10**6)
    t0 = time.monotonic()
    waited = procs.wait_chip_free(5.0, globs=globs, poll_s=0.5, opener=opener)
    assert waited < 0.4 and time.monotonic() - t0 < 0.4
    assert set(opener.calls.values()) == {1}
    assert procs.busy_files(globs, opener) == []


def test_a_file_that_is_no_group_opens_or_fails_without_a_wait(tmp_path):
    """`/dev/vfio/*` also names the container file and, on newer kernels,
    a directory: the real `os.open` on a directory is EISDIR."""
    globs = fake_devices(tmp_path)
    (tmp_path / "devices").mkdir()
    assert procs.busy_files(globs) == []
    assert procs.wait_chip_free(5.0, globs=globs) < 0.4


def test_a_holder_is_named_before_the_files_are_tried(tmp_path):
    globs = fake_devices(tmp_path, names=("accel0",))
    holder = subprocess.Popen(
        [sys.executable, "-c",
         f"import time; f = open({str(tmp_path / 'accel0')!r}); "
         "print('open', flush=True); time.sleep(60)"], stdout=subprocess.PIPE)
    opener = opener_failing(errno.EBUSY, 10**6)
    try:
        holder.stdout.readline()
        with pytest.raises(procs.ChipBusy) as e:
            procs.wait_chip_free(0.3, globs=globs, opener=opener)
        assert f"pid {holder.pid}" in str(e.value)
        assert "EBUSY" not in str(e.value)
        assert opener.calls == {}, "a held file is /proc's to answer for"
    finally:
        holder.kill()
        holder.wait()
    # The holder gone, the kernel still busy twice: the wait has two parts.
    opener = opener_failing(errno.EBUSY, 2)
    waited = procs.wait_chip_free(5.0, globs=globs, poll_s=0.05, opener=opener)
    assert opener.calls == {str(tmp_path / "accel0"): 3}
    assert waited.opened_s >= 0.1


@pytest.mark.parametrize("start_wait", [0.0, 13.2])
def test_setup_s_leaves_out_the_start_wait_and_nothing_else(start_wait):
    import run as harness

    t_start = 1_790_000_000.0
    alone = harness.setup_seconds(t_start + 40.5, t_start, 0.0)
    assert alone == pytest.approx(40.5)
    # The same set-up behind a wait for an earlier run's chips: the window
    # opens that much later on the clock and setup_s reads the same.
    assert harness.setup_seconds(t_start + 40.5 + start_wait, t_start,
                                 procs.Waited(0.2, start_wait - 0.2, 0.03)
                                 if start_wait else 0.0) == pytest.approx(alone)
    # Anything else that delays the window counts.
    assert harness.setup_seconds(t_start + 40.5 + start_wait + 7.0, t_start,
                                 start_wait) == pytest.approx(alone + 7.0)


FILES_NEVER_OPEN = """
import errno, functools, os, sys
sys.path.insert(0, {bench!r})
import procs, run as harness

def busy(path, flags):
    raise OSError(errno.EBUSY, os.strerror(errno.EBUSY), path)

procs.wait_chip_free = functools.partial(
    procs.wait_chip_free, globs=({glob!r},), opener=busy)
harness.CHIP_FREE_LIMIT_S = 0.3
sys.exit(harness.main(["--workload", "train-d4-8x1024", "--seed", "5",
                       "--seconds", "1", "--trace", "0", "--platform", "cpu"]))
"""


def test_files_that_never_open_end_the_run_in_exit_1_naming_them(tmp_path):
    """The repair waits; it does not hide a chip that is truly held."""
    (tmp_path / "7").write_bytes(b"")
    done = subprocess.run(
        [sys.executable, "-c", FILES_NEVER_OPEN.format(
            bench=spec.BENCH, glob=str(tmp_path / "*"))],
        cwd=spec.REPO, capture_output=True, text=True, timeout=120)
    assert done.returncode == 1, done.stdout[-2000:] + done.stderr[-2000:]
    assert "no result:" in done.stdout and "ChipBusy" in done.stdout
    assert f"{tmp_path / '7'} would not open: EBUSY" in done.stdout
    assert not done.stdout.strip().splitlines()[-1].startswith("{")
    assert procs.tagged() == []


def test_fewer_chips_than_the_cell_needs_is_no_result():
    """No number under a per-chip unit where the node shows no chip: the
    case of `bench/tests/test_rehearsal.py`, under the gate since PR 55."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", RT_TPU_CHIPS="0")
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-d4-8x1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "nothing was run" in done.stdout
    assert not done.stdout.strip().splitlines()[-1].startswith("{")
    assert procs.tagged() == []


def test_a_tagged_process_is_found_and_ended(tmp_path):
    env = dict(os.environ, **{procs.TOKEN_ENV: "test-token"})
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                             env=env, start_new_session=True)
    try:
        pid_file = str(tmp_path / "pids.json")
        seen = procs.write_pid_file(pid_file, "test-token")
        assert [e["pid"] for e in seen] == [child.pid]
        # A reused pid (another start time) is let be.
        assert not procs._same_process(dict(seen[0], start=seen[0]["start"] + 1))
        done = procs.reap_previous(pid_file, grace_s=2.0)
        assert done["found"] == 1 and done["left"] == 0
        child.wait(timeout=5)
        assert procs.tagged("test-token") == []
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
