"""The processes of a run: which they are, that the last run's are gone
before this one starts, and that every one of this run's has ended before
the result line is printed.

Every run of a cell is a new process on one machine, and a chip belongs to
one process at a time. The runtime's workers are forked from a zygote and
become session leaders, nothing gives them a parent-death signal, and
`Raylet.stop()` does not wait for the ones it kills. So the harness itself
keeps the account:

  * the driver sets `RT_BENCH_RUN_TOKEN` before `rt.init()`. The zygote
    and every worker started by `Popen` are exec'ed with it, and a worker
    forked from the zygote shows the zygote's exec-time environment in
    `/proc/<pid>/environ`, so one scan of `/proc` finds every process of
    a run whatever session it leads;
  * what the scan finds is written to the pid file with each process's
    start time and command line, so that the next run can signal what is
    left and leave a reused pid alone;
  * a chip is free when no process holds a TPU device file open
    (`/proc/*/fd` against `/dev/accel*`, `/dev/vfio/*`) AND every such
    file opens. The second half is the kernel's: a VFIO group opens for
    one holder at a time, and a worker that held chips goes on giving
    them back, one after the other, after it has left `/proc`: 2-5 s for
    one chip, 14-22 s for four, in which an open of a group answers
    `EBUSY` or sleeps until the group is back (PERF.md section 6, PR 49).
    libtpu's lock file is never touched.
"""

from __future__ import annotations

import errno
import glob
import json
import os
import signal
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence

TOKEN_ENV = "RT_BENCH_RUN_TOKEN"
DEVICE_GLOBS = ("/dev/accel*", "/dev/vfio/*")


class ChipBusy(RuntimeError):
    """Processes still hold the node's TPU device files, or the kernel
    still refuses to open them."""


class Waited(float):
    """The seconds `wait_chip_free` waited, in its two parts: `unheld_s`
    until /proc showed no holder, `opened_s` more until every device file
    opened. `probe_ms` is what the last look at the files itself took."""

    unheld_s: float
    opened_s: float
    probe_ms: float

    def __new__(cls, unheld_s: float, opened_s: float, probe_ms: float):
        self = super().__new__(cls, unheld_s + opened_s)
        self.unheld_s, self.opened_s, self.probe_ms = (
            unheld_s, opened_s, probe_ms)
        return self

    def parts(self) -> str:
        return (f"{float(self):.2f}s (no holder after {self.unheld_s:.2f}s, "
                f"the device files opened {self.opened_s:.2f}s later; the "
                f"last look at them took {self.probe_ms:.2f} ms)")


def _pids() -> List[int]:
    return [int(p) for p in os.listdir("/proc") if p.isdigit()]


def _start_ticks(pid: int) -> Optional[int]:
    """Start time in clock ticks since boot (field 22 of /proc/<pid>/stat):
    with the pid it names one process for the life of the machine."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            return int(f.read().rsplit(b")", 1)[1].split()[19])
    except (OSError, IndexError, ValueError):
        return None


def _cmdline(pid: int) -> str:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode("utf-8", "replace").strip()
    except OSError:
        return ""


def _token_of(pid: int) -> Optional[str]:
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            env = f.read()
    except OSError:
        return None
    key = TOKEN_ENV.encode() + b"="
    for item in env.split(b"\0"):
        if item.startswith(key):
            return item[len(key):].decode("utf-8", "replace")
    return None


def _is_zombie(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            return f.read().rsplit(b")", 1)[1].split()[0] == b"Z"
    except (OSError, IndexError):
        return True


def tagged(token: Optional[str] = None) -> List[Dict]:
    """Live processes exec'ed (or forked from one exec'ed) under a run
    token: `token`'s, or any run's when None. Never this process."""
    me = os.getpid()
    out = []
    for pid in _pids():
        if pid == me:
            continue
        tok = _token_of(pid)
        if tok is None or (token is not None and tok != token):
            continue
        start = _start_ticks(pid)
        if start is None or _is_zombie(pid):
            continue
        out.append({"pid": pid, "start": start, "cmd": _cmdline(pid),
                    "token": tok})
    return out


def new_token() -> str:
    return f"{os.getpid()}-{_start_ticks(os.getpid())}"


def write_pid_file(path: str, token: str) -> List[Dict]:
    """Record every process of this run seen so far (the file keeps the
    union: a worker that has gone is kept until the run ends)."""
    seen = {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("token") == token:
            seen = {(e["pid"], e["start"]): e for e in doc["processes"]}
    except (OSError, ValueError, KeyError):
        pass
    for e in tagged(token):
        seen[(e["pid"], e["start"])] = e
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"token": token, "driver": os.getpid(),
                   "processes": list(seen.values())}, f)
    os.replace(tmp, path)
    return list(seen.values())


def _same_process(entry: Dict) -> bool:
    return (_start_ticks(entry["pid"]) == entry["start"]
            and _cmdline(entry["pid"]) == entry["cmd"]
            and not _is_zombie(entry["pid"]))


def _signal_all(entries: Iterable[Dict], sig: int) -> None:
    for e in entries:
        if _same_process(e):
            try:
                os.kill(e["pid"], sig)
            except (ProcessLookupError, PermissionError):
                pass


def _wait_gone(entries: Sequence[Dict], seconds: float) -> List[Dict]:
    deadline = time.monotonic() + seconds
    left = [e for e in entries if _same_process(e)]
    while left and time.monotonic() < deadline:
        time.sleep(0.05)
        left = [e for e in left if _same_process(e)]
    return left


def end_processes(entries: Sequence[Dict], grace_s: float,
                  kill_wait_s: float = 10.0) -> Dict:
    """SIGTERM, wait `grace_s`, SIGKILL what is left and wait for that
    too. Returns what it had to do, for the run's log."""
    alive = [e for e in entries if _same_process(e)]
    if not alive:
        return {"found": 0, "killed": 0, "left": 0}
    _signal_all(alive, signal.SIGTERM)
    left = _wait_gone(alive, grace_s)
    killed = len(left)
    if left:
        _signal_all(left, signal.SIGKILL)
        left = _wait_gone(left, kill_wait_s)
    return {"found": len(alive), "killed": killed, "left": len(left)}


def reap_previous(pid_file: str, grace_s: float = 5.0) -> Dict:
    """End what is left of earlier runs: the pid file's processes (start
    time and command line matching, so a reused pid is let be) and, as a
    backstop, anything else still carrying a run token."""
    entries: List[Dict] = []
    try:
        with open(pid_file) as f:
            entries = list(json.load(f).get("processes", []))
    except (OSError, ValueError):
        pass
    known = {(e["pid"], e["start"]) for e in entries}
    entries += [e for e in tagged() if (e["pid"], e["start"]) not in known]
    return end_processes(entries, grace_s)


def holders(globs: Sequence[str] = DEVICE_GLOBS) -> Dict[int, List[str]]:
    """pid -> the device files it holds open, over all of /proc."""
    devices = {os.path.realpath(p) for g in globs for p in glob.glob(g)}
    if not devices:
        return {}
    out: Dict[int, List[str]] = {}
    for pid in _pids():
        fd_dir = f"/proc/{pid}/fd"
        try:
            fds = os.listdir(fd_dir)
        except OSError:
            continue
        for fd in fds:
            try:
                target = os.readlink(f"{fd_dir}/{fd}")
            except OSError:
                continue
            if target in devices:
                out.setdefault(pid, []).append(target)
    return out


def busy_files(globs: Sequence[str] = DEVICE_GLOBS,
               opener: Callable[[str, int], int] = os.open) -> List[str]:
    """The device files the kernel will not open yet: `EBUSY` on an open
    for reading and writing, closed again at once. Any other error
    (`EACCES`, `ENOENT`, `EISDIR`, a file that is no group) says nothing
    about a release in progress: for such a file /proc's answer stands."""
    busy = []
    for path in sorted({p for g in globs for p in glob.glob(g)}):
        try:
            fd = opener(path, os.O_RDWR)
        except OSError as e:
            if e.errno == errno.EBUSY:
                busy.append(path)
            continue
        os.close(fd)
    return busy


def wait_chip_free(limit_s: float, globs: Sequence[str] = DEVICE_GLOBS,
                   poll_s: float = 0.1,
                   opener: Callable[[str, int], int] = os.open) -> Waited:
    """Return, as soon as no process holds a TPU device file and every
    one of them opens, the seconds waited; past `limit_s` raise ChipBusy
    naming the holders, or the files that would not open."""
    t0 = time.monotonic()
    unheld_at = None
    while True:
        held = holders(globs)
        looked_at = time.monotonic()
        busy = [] if held else busy_files(globs, opener)
        now = time.monotonic()
        if not held and unheld_at is None:
            unheld_at = looked_at
        if not held and not busy:
            return Waited(unheld_at - t0, now - unheld_at,
                          (now - looked_at) * 1e3)
        if now - t0 >= limit_s:
            if held:
                why = "; ".join(
                    f"pid {pid} ({_cmdline(pid)[:80]}) holds "
                    f"{', '.join(sorted(set(paths)))}"
                    for pid, paths in sorted(held.items()))
            else:
                why = (f"no process holds a device file (since "
                       f"{unheld_at - t0:.2f}s), yet {', '.join(busy)} "
                       f"would not open: EBUSY, {os.strerror(errno.EBUSY)}")
            raise ChipBusy(
                f"the chip was not free within {limit_s:g}s: {why}")
        time.sleep(poll_s)


def end_run(token: str, pid_file: str, grace_s: float = 10.0) -> Dict:
    """After the runtime's own shutdown: wait until every process the run
    started has exited, SIGKILL what has not after the grace period, and
    wait for that too. The pid file is left empty for the next run."""
    entries = write_pid_file(pid_file, token)
    alive = [e for e in entries if _same_process(e)]
    left = _wait_gone(alive, 2.0)  # what the runtime signalled is exiting
    done = end_processes(left, grace_s)
    done["waited_for"] = len(alive)
    stragglers = tagged(token)
    if stragglers:  # forked while we were waiting
        extra = end_processes(stragglers, 1.0)
        done["killed"] += extra["found"]
        done["left"] += extra["left"]
    if done["left"] == 0:
        with open(pid_file, "w") as f:
            json.dump({"token": token, "driver": os.getpid(),
                       "processes": []}, f)
    return done
