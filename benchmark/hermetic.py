"""A run that stays on its own ground, depends on nothing else of its
caller's environment, and leaves nothing behind. Imports no ``ray_tpu`` and
no jax: ``enter()`` runs before either is imported.

A run writes only under its checkout and under the ``TMPDIR`` its caller
gave it (the driver gives each side its own). What it owns:

- a private directory, exported as ``TMPDIR`` to itself and so to the
  daemon, conductor, workers and probe. The runtime puts its Unix sockets
  under ``tempfile.gettempdir()`` (``cluster/protocol.py:_uds_path``, and
  ``store-*.sock`` / ``zygote-*.sock`` in a ``mkdtemp`` session directory
  there) and an ``AF_UNIX`` path ends at 107 bytes, so the directory is made
  under the caller's ``TMPDIR`` only where those paths stay under 100 bytes,
  else under ``<checkout>/.rt``; ``/tmp`` is the last resort when both are
  too long, and the run says so loudly. Only sockets, pidfiles, the
  session's logs, the trainer's trial directory and the profiler's trace
  live there; it is removed on every way out. The one thing that outlasts a
  run, the compile cache, stays where ``JAX_COMPILATION_CACHE_DIR`` says,
  else at ``<checkout>/.jax_cache``;
- a record of itself under ``<checkout>/benchmark/out/runs/``: its pid and
  start time, its private directory and its marker. The next run from this
  checkout sweeps what the records of dead runs name, and nothing else:
  no directory it did not record, no process that does not carry a
  recorded marker;
- an environment marker with a random token that every descendant
  inherits, by which they are found in ``/proc`` whoever their parent has
  become;
- a reaper: a small child that waits for this process to end, however it
  ends (SIGKILL included), then kills what still carries the marker and
  removes the directory and the record.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

MARK = "RTPU_BENCH_RUN"            # <pid>:<random token>, inherited
PREFIX = "rtb-"                    # private directories: <base>/rtb-xxxxxxxx
OWNER_FILE = ".owner"              # inside one: the marker of the run it is
SOCKET_LIMIT = 100                 # AF_UNIX stops at 107; keep clear of it
# The longest socket paths the runtime builds under TMPDIR (see above).
SOCKET_SHAPES = ("rtpu-rpc-65535.sock",
                 "rtpu-session-xxxxxxxx/zygote-xxxxxxxx.sock",
                 "rtpu-session-xxxxxxxx/store-xxxxxxxx.sock")
CHIP_NODE_GLOBS = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")

_REAPER = r"""
import os, signal, shutil, sys, time
fd, mark, priv, record = (int(sys.argv[1]), sys.argv[2].encode(),
                          sys.argv[3], sys.argv[4])
for s in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
    signal.signal(s, signal.SIG_IGN)
while True:
    try:
        if not os.read(fd, 1):
            break
    except InterruptedError:
        continue
    except OSError:
        break
def marked():
    out = []
    for name in os.listdir('/proc'):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open('/proc/%s/environ' % name, 'rb') as f:
                if mark in f.read().split(b'\0'):
                    out.append(int(name))
        except OSError:
            pass
    return out
deadline = time.time() + 20
while time.time() < deadline:
    pids = marked()
    if not pids:
        break
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    time.sleep(0.05)
shutil.rmtree(priv, ignore_errors=True)
if os.path.basename(os.path.dirname(priv)) == '.rt':
    try:
        os.rmdir(os.path.dirname(priv))      # <checkout>/.rt, when empty
    except OSError:
        pass
try:
    os.remove(record)
except OSError:
    pass
"""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def start_ticks(pid: int):
    """When ``pid`` started, in the kernel's clock ticks since boot; None if
    it is gone or a zombie. With the pid it names one process, whatever pid
    is handed out again later."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except (OSError, IndexError):
        return None
    return None if fields[0] == "Z" else int(fields[19])


def _alive(pid: int) -> bool:
    return start_ticks(pid) is not None


def marked_pids(mark: str) -> list:
    """Live processes, other than this one, whose environment carries
    exactly ``mark`` (a marker holds a random token: no other run's
    processes match)."""
    want = f"{MARK}={mark}".encode()
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                env = f.read().split(b"\0")
        except OSError:
            continue
        if want in env and _alive(int(name)):
            out.append(int(name))
    return out


def kill_marked(mark: str, timeout: float) -> list:
    """SIGKILL whatever carries ``mark`` until nothing does; -> pids that
    would not die."""
    def kill_all():
        pids = marked_pids(mark)
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        return not pids
    wait_until(kill_all, timeout, 0.05)
    return marked_pids(mark)


def wait_until(cond, timeout: float, step: float = 0.1) -> bool:
    deadline = time.monotonic() + timeout
    while True:
        if cond():
            return True
        if time.monotonic() >= deadline:
            return False
        time.sleep(step)


def chip_nodes(chips: int) -> list:
    """The device nodes of the chips a lease of ``chips`` takes: the node
    daemon hands out the lowest ids first, so those of index < chips."""
    nodes = [p for pattern in CHIP_NODE_GLOBS for p in glob.glob(pattern)]
    return sorted(p for p in nodes
                  if int(re.search(r"(\d+)$", p).group(1)) < chips)


def chip_holders(chips: int) -> list:
    """Pids that have one of those nodes open (as far as /proc shows). A
    neighbour on another chip of the host is not among them."""
    nodes = set(chip_nodes(chips))
    out = []
    if not nodes:
        return out
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            fds = os.listdir(f"/proc/{name}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                if os.readlink(f"/proc/{name}/fd/{fd}") in nodes:
                    out.append(int(name))
                    break
            except OSError:
                pass
    return out


def _fits(base: str) -> int:
    """Bytes of the longest socket path under a private directory made in
    ``base`` (mkdtemp adds eight characters to the prefix)."""
    private = os.path.join(base, PREFIX + "x" * 8)
    return max(len(os.path.join(private, s).encode()) for s in SOCKET_SHAPES)


def private_dir(checkout: str) -> str:
    """Under the caller's TMPDIR where the runtime's socket paths fit, else
    under ``<checkout>/.rt``; ``/tmp`` only if both are too long."""
    caller = tempfile.gettempdir()        # TMPDIR, as the caller set it
    own = os.path.join(checkout, ".rt")
    tried = []
    for base, note in ((caller, ""), (own, ""),
                       ("/tmp", "LEAVING THE RUN'S OWN GROUND: ")):
        if _fits(base) >= SOCKET_LIMIT:
            tried.append(f"{base}: socket paths would be {_fits(base)} bytes")
            continue
        try:
            os.makedirs(base, exist_ok=True)
            path = tempfile.mkdtemp(prefix=PREFIX, dir=base)
        except OSError as e:
            tried.append(f"{base}: {e}")
            continue
        if tried:
            log(f"{note}runtime directory {path} ({'; '.join(tried)})")
        return path
    raise RuntimeError("no short writable directory for the runtime's "
                       "Unix sockets: " + "; ".join(tried))


def runs_dir(checkout: str) -> str:
    return os.path.join(checkout, "benchmark", "out", "runs")


def sweep_stale(checkout: str) -> None:
    """What a killed earlier run from this checkout may have left, by its
    own record: processes that carry its marker (one may still hold the
    chip) and its private directory. Nothing this checkout did not record
    is looked for or touched, and never libtpu's lock file."""
    for path in glob.glob(os.path.join(runs_dir(checkout), "*.json")):
        try:
            with open(path) as f:
                rec = json.load(f)
            pid, ticks = int(rec["pid"]), int(rec["start_ticks"])
            mark, tmp = str(rec["mark"]), str(rec["tmp"])
        except (OSError, ValueError, KeyError, TypeError):
            _remove(path)                 # half-written by a killed run
            continue
        if start_ticks(pid) == ticks:
            continue                      # that run is alive
        stale = marked_pids(mark)
        if stale:
            log(f"killing {len(stale)} process(es) the dead run {pid} of "
                f"this checkout left: {stale}")
            left = kill_marked(mark, 15.0)
            if left:
                log(f"processes that would not die: {left}")
        try:
            with open(os.path.join(tmp, OWNER_FILE)) as f:
                ours = f.read() == mark
        except OSError:
            ours = False
        if ours:
            log(f"removing {tmp}, left by the dead run {pid}")
            shutil.rmtree(tmp, ignore_errors=True)
        _remove(path)


def _remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


class Terminated(BaseException):
    """SIGTERM/SIGINT/SIGHUP, raised in the main thread so that every
    ``finally`` on the way out runs."""

    def __init__(self, signum: int):
        super().__init__(f"signal {signum}")
        self.signum = signum


class Run:
    """One run's private world. ``enter()`` before anything of the runtime
    is imported; ``leave()`` on every way out (idempotent)."""

    def __init__(self, checkout: str):
        self.checkout = checkout
        self.tmp = ""
        self.mark = ""
        self.record = ""
        self._reaper = None
        self._pipe_w = None
        self._left = False

    def enter(self) -> "Run":
        sweep_stale(self.checkout)
        self.tmp = private_dir(self.checkout)
        self.mark = f"{os.getpid()}:{os.path.basename(self.tmp)}"
        with open(os.path.join(self.tmp, OWNER_FILE), "w") as f:
            f.write(self.mark)
        os.makedirs(runs_dir(self.checkout), exist_ok=True)
        self.record = os.path.join(
            runs_dir(self.checkout),
            f"{os.getpid()}-{os.path.basename(self.tmp)}.json")
        with open(self.record + ".part", "w") as f:
            json.dump({"pid": os.getpid(),
                       "start_ticks": start_ticks(os.getpid()),
                       "mark": self.mark, "tmp": self.tmp}, f)
        os.replace(self.record + ".part", self.record)
        r, w = os.pipe()
        # Started before the marker is exported: the reaper must not find
        # itself. It holds the read end; this process alone the write end.
        self._reaper = subprocess.Popen(
            [sys.executable, "-S", "-E", "-c", _REAPER, str(r),
             f"{MARK}={self.mark}", self.tmp, self.record],
            pass_fds=(r,), stdin=subprocess.DEVNULL,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd="/")
        os.close(r)
        self._pipe_w = w
        home = os.path.join(self.tmp, "home")
        os.makedirs(home)
        os.environ.update({
            MARK: self.mark, "TMPDIR": self.tmp, "TEMP": self.tmp,
            "TMP": self.tmp, "HOME": home,
            "XDG_CACHE_HOME": os.path.join(home, ".cache"),
            # chip-owning workers import the benchmark's own modules
            "PYTHONPATH": os.pathsep.join(
                [self.checkout] + [p for p in os.environ.get(
                    "PYTHONPATH", "").split(os.pathsep) if p]),
        })
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(self.checkout, ".jax_cache"))
        # every program of a cell goes into that cache, the small ones too,
        # so that a warm run compiles nothing
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                              "0")
        tempfile.tempdir = None       # re-read TMPDIR
        if tempfile.gettempdir() != self.tmp:
            raise RuntimeError(f"tempfile.gettempdir() is "
                               f"{tempfile.gettempdir()!r}, not {self.tmp!r}")
        for signum in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
            signal.signal(signum, self._on_signal)
        return self

    @staticmethod
    def _on_signal(signum, _frame):
        raise Terminated(signum)

    def wait_for_chips(self, chips: int, timeout: float = 60.0) -> None:
        """A chip belongs to one process; an earlier run's owner may still
        be dying. Wait a bounded time for the chips this cell will lease,
        instead of failing on the refusal."""
        holders = chip_holders(chips)
        if not holders:
            return
        log(f"{chip_nodes(chips)} held by pid(s) {holders}; waiting up to "
            f"{timeout:.0f}s")
        t0 = time.monotonic()
        if wait_until(lambda: not chip_holders(chips), timeout, 0.25):
            log(f"free after {time.monotonic() - t0:.1f}s")
        else:
            log(f"still held by {chip_holders(chips)} after {timeout:.0f}s;"
                " going on, the lease will say why if it fails")

    def children_gone(self, timeout: float = 30.0) -> list:
        """Kill what still carries this run's marker; -> pids that would
        not die."""
        return kill_marked(self.mark, timeout)

    def leave(self) -> None:
        if self._left or not self.tmp:
            return
        self._left = True
        for signum in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
            signal.signal(signum, signal.SIG_IGN)
        left = self.children_gone()
        if left:
            log(f"processes that would not die: {left}")
        shutil.rmtree(self.tmp, ignore_errors=True)
        if self._pipe_w is not None:
            os.close(self._pipe_w)       # the reaper sees EOF, finds nothing,
            self._pipe_w = None          # removes the record
        if self._reaper is not None:
            try:
                self._reaper.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self._reaper.kill()
                self._reaper.wait()
                _remove(self.record)
