"""The chip-free wait: at once on a clean machine, and a message naming the
holder when a (fake) device file stays open."""

import os
import subprocess
import sys
import time

import pytest

import procs


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
