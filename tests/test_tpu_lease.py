"""One chip-owning worker per TPU lease; nobody else may open a backend.

Chips are faked (tpu_chips_per_host_override): nothing here touches jax,
the workers only report the environment the daemon spawned them with.
"""

import os
import time

import pytest

import ray_tpu as rt
from ray_tpu import config

_VARS = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
         "TPU_PROCESS_BOUNDS", "JAX_COMPILATION_CACHE_DIR")


def _probes():
    """(actor class, function) reporting the worker's pid and spawn env;
    local definitions, so they are shipped to the workers by value."""
    names = _VARS

    def spawn_env():
        import os
        return os.getpid(), {k: os.environ.get(k) for k in names}

    class Probe:
        def env(self):
            return spawn_env()

    return rt.remote(Probe), spawn_env


@pytest.fixture()
def daemon(monkeypatch):
    # The daemon's own environment names the TPU (as on a TPU VM): CPU
    # workers must be forced off it all the same.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    config.set_override("tpu_chips_per_host_override", 4)
    runtime = rt.init(num_cpus=4)
    try:
        yield runtime._owned_daemon
    finally:
        rt.shutdown()
        config.clear_override("tpu_chips_per_host_override")


def _wait(pred, what, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.05)


def _alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


def test_tpu_actors_own_disjoint_chips_and_die_with_their_lease(daemon):
    Probe, _ = _probes()
    assert daemon.total_resources["TPU"] == 4.0
    a, b = (Probe.options(num_tpus=1).remote() for _ in range(2))
    (pid_a, env_a), (pid_b, env_b) = rt.get([a.env.remote(), b.env.remote()],
                                            timeout=60)
    for env in (env_a, env_b):
        assert env["JAX_PLATFORMS"] == "tpu"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        # one fixed path inside the checkout, not under the session dir
        assert env["JAX_COMPILATION_CACHE_DIR"].endswith("/.jax_cache")
        assert not env["JAX_COMPILATION_CACHE_DIR"].startswith(
            daemon.session_dir)
    chips = {env_a["TPU_VISIBLE_CHIPS"], env_b["TPU_VISIBLE_CHIPS"]}
    assert len(chips) == 2 and chips <= {"0", "1", "2", "3"}
    state = daemon.rpc_debug_state()
    assert len(state["free_chips"]) == 2
    assert not {int(c) for c in chips} & set(state["free_chips"])

    # The actor ends: its process exits (never pooled, never recycled) and
    # only then are its chip ids back.
    rt.kill(a)
    _wait(lambda: not _alive(pid_a), "chip owner to exit")
    _wait(lambda: len(daemon.rpc_debug_state()["free_chips"]) == 3,
          "chip id to return")
    assert pid_a not in daemon.rpc_debug_state()["worker_pids"]
    assert not any(w.chips for q in daemon._idle.values() for t in q
                   if (w := daemon._workers.get(t)) is not None)
    rt.kill(b)
    _wait(lambda: daemon.rpc_debug_state()["free_chips"] == [0, 1, 2, 3],
          "all chip ids to return")

    # All four chips to one worker: the full list, the host's own bounds.
    whole = Probe.options(num_tpus=4).remote()
    pid_w, env_w = rt.get(whole.env.remote(), timeout=60)
    assert pid_w not in (pid_a, pid_b)
    assert env_w["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert env_w["TPU_CHIPS_PER_PROCESS_BOUNDS"] is None
    rt.kill(whole)
    _wait(lambda: not _alive(pid_w), "4-chip owner to exit")


def test_cpu_workers_are_forced_off_the_tpu(daemon):
    Probe, spawn_env = _probes()
    assert os.environ["JAX_PLATFORMS"] == "tpu"   # what the daemon inherits
    actor = Probe.remote()
    task = rt.remote(spawn_env)
    # a runtime_env cannot point a CPU worker at a chip either
    sneaky = rt.remote(spawn_env).options(
        runtime_env={"env_vars": {"JAX_PLATFORMS": "tpu"}})
    for _, env in rt.get([actor.env.remote(), task.remote(),
                          sneaky.remote()], timeout=60):
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["TPU_VISIBLE_CHIPS"] is None


def test_tpu_task_lease_worker_is_not_reused_by_the_pool(daemon):
    _, spawn_env = _probes()
    tpu_task = rt.remote(spawn_env).options(num_tpus=1)
    pid, env = rt.get(tpu_task.remote(), timeout=60)
    assert env["JAX_PLATFORMS"] == "tpu" and env["TPU_VISIBLE_CHIPS"]
    # the lease goes back: the process is killed, not checked in
    _wait(lambda: not _alive(pid), "TPU lease worker to exit", timeout=30.0)
    _wait(lambda: daemon.rpc_debug_state()["free_chips"] == [0, 1, 2, 3],
          "chip id to return")
    assert not daemon.rpc_debug_state()["idle_workers"].get("tpu")
    cpu_pid, cpu_env = rt.get(rt.remote(spawn_env).remote(), timeout=60)
    assert cpu_pid != pid and cpu_env["JAX_PLATFORMS"] == "cpu"


def test_tpu_lease_of_a_size_no_process_can_own_is_refused(daemon):
    # Two of four chips: neither one chip nor the whole host. Refused
    # before anything is allotted, with the reason, for a task and for an
    # actor alike; nothing hangs and no chip id leaks.
    Probe, spawn_env = _probes()
    t0 = time.monotonic()
    with pytest.raises(rt.TaskError, match="one chip or all 4"):
        rt.get(rt.remote(spawn_env).options(num_tpus=2).remote(), timeout=30)
    actor = Probe.options(num_tpus=2).remote()
    with pytest.raises(rt.TaskError, match="one chip or all 4"):
        rt.get(actor.env.remote(), timeout=30)
    assert time.monotonic() - t0 < 20.0
    state = daemon.rpc_debug_state()
    assert state["free_chips"] == [0, 1, 2, 3]
    assert daemon._avail["TPU"] == 4.0
    # the node still serves TPU leases afterwards
    _, env = rt.get(rt.remote(spawn_env).options(num_tpus=1).remote(),
                    timeout=60)
    assert env["TPU_VISIBLE_CHIPS"] == "0"


def test_failed_spawn_returns_what_the_lease_took(daemon, monkeypatch):
    # Whatever the spawn raises, counts and chip ids go back to the node.
    _, spawn_env = _probes()
    real = daemon._spawn_worker
    failed = []

    def spawn(env_key, runtime_env, chips=()):
        if chips and not failed:
            failed.append(chips)
            raise OSError("spawn failed")
        return real(env_key, runtime_env, chips)

    monkeypatch.setattr(daemon, "_spawn_worker", spawn)
    _, env = rt.get(rt.remote(spawn_env).options(num_tpus=1).remote(),
                    timeout=60)
    assert failed == [(0,)]
    # the retried lease was allotted the very id the failed one gave back
    assert env["TPU_VISIBLE_CHIPS"] == "0"
    _wait(lambda: daemon.rpc_debug_state()["free_chips"] == [0, 1, 2, 3],
          "chip ids to return")
    assert daemon._avail["TPU"] == 4.0


def test_bounds_follow_the_hosts_chips_not_the_advertised_count(monkeypatch):
    # init(num_tpus=1) on a 4-chip host: the one advertised chip is still
    # one of four, so its owner needs the 1,1,1 bounds pair.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    config.set_override("tpu_chips_per_host_override", 4)
    try:
        runtime = rt.init(num_cpus=2, num_tpus=1)
        assert runtime._owned_daemon.total_resources["TPU"] == 1.0
        _, spawn_env = _probes()
        _, env = rt.get(rt.remote(spawn_env).options(num_tpus=1).remote(),
                        timeout=60)
        assert env["TPU_VISIBLE_CHIPS"] == "0"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    finally:
        rt.shutdown()
        config.clear_override("tpu_chips_per_host_override")


def test_fractional_tpus_are_refused():
    with pytest.raises(ValueError, match="one process at a time"):
        _probes()[0].options(num_tpus=0.5)
