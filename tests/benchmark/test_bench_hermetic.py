"""benchmark/hermetic.py piece by piece: where a run puts its runtime
directory, what it sweeps at its start, and whose chips it waits for."""

import json
import os
import shutil
import subprocess
import tempfile

import pytest

from bench_paths import BENCH  # noqa: F401  (puts the checkout on sys.path)
from benchmark import hermetic


@pytest.fixture()
def checkout():
    """A stand-in checkout with a path as short as a real one's: under the
    tests' own TMPDIR where that leaves room for a socket path, else /tmp."""
    base = tempfile.gettempdir()
    if hermetic._fits(os.path.join(base, "c" * 9, ".rt")) >= \
            hermetic.SOCKET_LIMIT:
        base = "/tmp"
    path = tempfile.mkdtemp(prefix="c", dir=base)
    yield path
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture()
def caller_tmpdir(monkeypatch):
    def set_to(path: str) -> None:
        os.makedirs(path, exist_ok=True)
        monkeypatch.setenv("TMPDIR", path)
        monkeypatch.setattr(tempfile, "tempdir", None)
        assert tempfile.gettempdir() == path
    yield set_to
    tempfile.tempdir = None


def longest_socket(private: str) -> int:
    return max(len(os.path.join(private, s)) for s in hermetic.SOCKET_SHAPES)


def test_the_callers_tmpdir_is_taken_where_the_sockets_fit(
        checkout, caller_tmpdir, capfd):
    """The driver's normal case: each side has a TMPDIR of its own, and the
    run stays under it."""
    mine = os.path.join(checkout, "side-a", "tmp")
    caller_tmpdir(mine)
    private = hermetic.private_dir(checkout)
    assert os.path.dirname(private) == mine
    assert os.path.basename(private).startswith(hermetic.PREFIX)
    assert longest_socket(private) < hermetic.SOCKET_LIMIT
    assert not os.path.exists(os.path.join(checkout, ".rt"))
    assert capfd.readouterr().err == ""          # nothing to remark on


def test_a_tmpdir_too_long_for_a_socket_sends_the_run_to_its_checkout(
        checkout, caller_tmpdir, capfd):
    long_tmp = os.path.join(checkout, "x" * 150, "tmp")
    caller_tmpdir(long_tmp)
    private = hermetic.private_dir(checkout)
    assert os.path.dirname(private) == os.path.join(checkout, ".rt")
    assert longest_socket(private) < hermetic.SOCKET_LIMIT
    assert os.listdir(long_tmp) == []
    err = capfd.readouterr().err
    assert "socket paths would be" in err and "LEAVING" not in err


def test_tmp_is_the_last_resort_and_said_loudly(checkout, caller_tmpdir,
                                                capfd):
    deep = os.path.join(checkout, "y" * 120)     # no short place in it
    caller_tmpdir(os.path.join(deep, "tmp"))
    private = hermetic.private_dir(deep)
    try:
        assert os.path.dirname(private) == "/tmp"
        assert "LEAVING THE RUN'S OWN GROUND" in capfd.readouterr().err
    finally:
        shutil.rmtree(private)


def plant(checkout, name, pid, ticks, owner=None):
    """A run's record and private directory, as ``Run.enter`` leaves them."""
    private = os.path.join(checkout, ".rt", hermetic.PREFIX + name)
    mark = f"{pid}:{hermetic.PREFIX}{name}"
    os.makedirs(os.path.join(private, "rtpu-session-x"))
    with open(os.path.join(private, hermetic.OWNER_FILE), "w") as f:
        f.write(mark if owner is None else owner)
    os.makedirs(hermetic.runs_dir(checkout), exist_ok=True)
    record = os.path.join(hermetic.runs_dir(checkout), f"{pid}-{name}.json")
    with open(record, "w") as f:
        json.dump({"pid": pid, "start_ticks": ticks, "mark": mark,
                   "tmp": private}, f)
    return private, record, mark


def sleeper(mark: str):
    return subprocess.Popen(["sleep", "600"],
                            env=dict(os.environ, **{hermetic.MARK: mark}))


def test_sweep_takes_what_a_dead_run_of_this_checkout_recorded(checkout):
    dead = 4194000 + os.getpid() % 300           # above pid_max's default
    private, record, mark = plant(checkout, "dead", dead, 1)
    child = sleeper(mark)
    try:
        hermetic.sweep_stale(checkout)
        assert child.wait(timeout=20) == -9
    finally:
        child.kill()
        child.wait()
    assert not os.path.exists(private) and not os.path.exists(record)


def test_sweep_knows_a_reused_pid_from_the_run_that_had_it(checkout):
    """This test's own pid with another start time: that run is dead."""
    me = os.getpid()
    private, record, _ = plant(checkout, "reused", me,
                               hermetic.start_ticks(me) - 5)
    hermetic.sweep_stale(checkout)
    assert not os.path.exists(private) and not os.path.exists(record)


def test_sweep_leaves_a_live_run_and_everything_it_has_no_record_of(checkout):
    me = os.getpid()
    live_dir, live_record, live_mark = plant(
        checkout, "live", me, hermetic.start_ticks(me))
    dead = 4194000 + os.getpid() % 300
    # recorded, but the directory says it is another run's
    other_dir, other_record, _ = plant(checkout, "other", dead, 1,
                                       owner="someone else")
    # not recorded at all: a directory and a process of a run that looks
    # dead from here (another checkout's, another pid namespace's)
    unknown_dir = os.path.join(checkout, ".rt", f"{hermetic.PREFIX}unknown")
    os.makedirs(unknown_dir)
    children = [sleeper(live_mark), sleeper(f"{dead + 1}:unknown")]
    half = os.path.join(hermetic.runs_dir(checkout), "1-half.json")
    with open(half, "w") as f:
        f.write('{"pid": 1, "sta')               # a run killed mid-write
    try:
        hermetic.sweep_stale(checkout)
        assert all(c.poll() is None for c in children)
    finally:
        for c in children:
            c.kill()
            c.wait()
    assert os.path.isdir(live_dir) and os.path.exists(live_record)
    assert os.path.isdir(other_dir) and not os.path.exists(other_record)
    assert os.path.isdir(unknown_dir) and not os.path.exists(half)


def test_a_run_records_itself_and_takes_everything_away(checkout,
                                                        caller_tmpdir):
    """``enter`` and ``leave`` in a child process (they rewrite the
    environment and the signal handlers)."""
    caller_tmpdir(os.path.join(checkout, "x" * 150))
    code = (
        "import json, os, sys, subprocess\n"
        f"sys.path.insert(0, {os.path.dirname(BENCH)!r})\n"
        "from benchmark import hermetic\n"
        f"run = hermetic.Run({checkout!r}).enter()\n"
        "child = subprocess.Popen(['sleep', '600'])\n"
        "records = os.listdir(hermetic.runs_dir(run.checkout))\n"
        "print(json.dumps({'tmp': run.tmp, 'env': os.environ['TMPDIR'],\n"
        "                  'records': records, 'child': child.pid,\n"
        "                  'home': os.environ['HOME']}))\n"
        "run.leave()\n")
    proc = subprocess.run(["python3", "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    seen = json.loads(proc.stdout)
    assert seen["tmp"] == seen["env"]
    assert seen["tmp"].startswith(os.path.join(checkout, ".rt") + os.sep)
    assert seen["home"].startswith(seen["tmp"])
    assert len(seen["records"]) == 1
    assert not os.path.exists(f"/proc/{seen['child']}")
    assert not os.path.exists(os.path.join(checkout, ".rt"))
    assert os.listdir(hermetic.runs_dir(checkout)) == []


def test_only_the_chips_the_cell_will_lease_are_waited_for(tmp_path,
                                                           monkeypatch):
    """A neighbour on another chip of the host holds nothing of ours."""
    for i in range(4):
        (tmp_path / f"accel{i}").write_text("")
    monkeypatch.setattr(hermetic, "CHIP_NODE_GLOBS",
                        (str(tmp_path / "accel[0-9]*"),))
    assert hermetic.chip_nodes(1) == [str(tmp_path / "accel0")]
    assert len(hermetic.chip_nodes(4)) == 4
    with open(tmp_path / "accel2"):
        assert hermetic.chip_holders(1) == []
        assert hermetic.chip_holders(4) == [os.getpid()]
    with open(tmp_path / "accel0"):
        assert hermetic.chip_holders(1) == [os.getpid()]
    assert hermetic.chip_holders(4) == []
    run = hermetic.Run(str(tmp_path))
    run.wait_for_chips(4, timeout=0.5)            # free: returns at once
