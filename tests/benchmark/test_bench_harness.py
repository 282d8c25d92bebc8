"""The harness end to end, as separate processes, at toy size on the CPU
(``--rehearse``): the soak the chip run makes at full size, a cell added as
data only, and what a failure in each phase looks like. A rehearsal prints
no result line and exits 3; the line it would have printed goes to stderr.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

from bench_paths import BENCH, CHECKOUT
from benchmark import hermetic

RUN = [sys.executable, os.path.join(BENCH, "run.py")]
RESULT_MARK = "REHEARSAL result (not printed to stdout): "


RT = os.path.join(CHECKOUT, ".rt")          # where a run goes when its
RUNS = hermetic.runs_dir(CHECKOUT)          # caller's TMPDIR is too long


def hostile_env(tmp_path) -> dict:
    """What the driver may hand a run: a TMPDIR far too long for a Unix
    socket (the run then keeps its runtime directory under the checkout's
    ``.rt``), and a HOME with nothing in it."""
    long_tmp = tmp_path / ("x" * 160) / "tmp"
    long_tmp.mkdir(parents=True, exist_ok=True)
    home = tmp_path / "home"
    home.mkdir(exist_ok=True)
    assert len(str(long_tmp)) > 150
    env = dict(os.environ, TMPDIR=str(long_tmp), HOME=str(home),
               XDG_CACHE_HOME=str(home / ".cache"), BENCH_RUN="17")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def rehearse(args, env, timeout=300):
    return subprocess.run(RUN + args + ["--rehearse"], env=env,
                          capture_output=True, text=True, timeout=timeout)


def marker_carriers(owner=None) -> list:
    """Processes that carry a run's marker (of the run ``owner``, or of any
    run whose owner is gone)."""
    found = []
    prefix = f"{hermetic.MARK}=".encode()
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                env = f.read().split(b"\0")
        except OSError:
            continue
        for item in env:
            if not item.startswith(prefix):
                continue
            pid = item[len(prefix):].split(b":")[0].decode()
            if str(owner) == pid if owner else \
                    not os.path.exists(f"/proc/{pid}"):
                found.append(f"process {name} ({item.decode()})")
    return found


def leftovers(pid: int) -> list:
    """Processes, private directories and records a run of ``pid`` left."""
    found = marker_carriers(pid)
    if os.path.isdir(RUNS):
        found += [os.path.join(RUNS, n) for n in os.listdir(RUNS)
                  if n.startswith(f"{pid}-")]
    return found


def result_of(proc) -> dict:
    lines = [ln for ln in proc.stderr.splitlines() if RESULT_MARK in ln]
    assert len(lines) == 1, proc.stderr[-3000:]
    return json.loads(lines[0].split(RESULT_MARK, 1)[1])


def check_clean_rehearsal(proc) -> dict:
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == "", "a rehearsal prints no result line"
    assert "Traceback" not in proc.stderr, proc.stderr[-3000:]
    assert "SECOND ATTEMPT" not in proc.stderr
    return result_of(proc)


def check_result_line(line: dict, metrics: set, traced: bool) -> None:
    """Exactly the contract's keys, and last what ``correct`` compared:
    each number beside its limit."""
    want = {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert set(line) - {"breakdown"} == want
    assert list(line)[-1] == "checks" and line["checks"]
    for value, limit in line["checks"].values():
        assert isinstance(value, (int, float))
        assert isinstance(limit, (int, float))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) - {"busy_s", "window_s"} == \
        {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["memory_peak_bytes"] > 0
    assert set(line["metrics"]) == metrics
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float) and value["value"] > 0
    if not traced:
        assert "breakdown" not in line


@pytest.fixture(scope="module")
def soak(tmp_path_factory):
    """The driver's sequence, back to back, from one checkout, under a
    hostile environment: untraced, a run killed with SIGKILL in the middle
    of its window followed at once by a traced one, then untraced again;
    seeds 0, 7 and at and above 2**31 and 2**32; ``benchmark/out`` and
    ``.rt`` holding what a killed earlier run of this checkout left, and
    beside it what looks like another checkout's dead run."""
    tmp_path = tmp_path_factory.mktemp("soak")
    env = hostile_env(tmp_path)
    out_dir = os.path.join(BENCH, "out")
    os.makedirs(out_dir, exist_ok=True)
    stale = [os.path.join(out_dir, "failure-soak-stale-0.txt"),
             os.path.join(out_dir, "record-soak-stale.json")]
    for path in stale:
        with open(path, "w") as f:
            f.write("{ left by a killed run")
    dead_pid = 4194000 + os.getpid() % 300      # above pid_max's default
    assert not os.path.exists(f"/proc/{dead_pid}")
    # a dead run of this checkout, by its record: swept
    stale_dir = os.path.join(RT, f"{hermetic.PREFIX}soakdead")
    stale_mark = f"{dead_pid}:{hermetic.PREFIX}soakdead"
    os.makedirs(os.path.join(stale_dir, "rtpu-session-dead"), exist_ok=True)
    with open(os.path.join(stale_dir, "rtpu-rpc-1.sock"), "w"):
        pass
    with open(os.path.join(stale_dir, hermetic.OWNER_FILE), "w") as f:
        f.write(stale_mark)
    os.makedirs(RUNS, exist_ok=True)
    stale_record = os.path.join(RUNS, f"{dead_pid}-soakdead.json")
    with open(stale_record, "w") as f:
        json.dump({"pid": dead_pid, "start_ticks": 1, "mark": stale_mark,
                   "tmp": stale_dir}, f)
    # another checkout's run, which this one has no record of: its
    # directory and its process look dead from here and are not touched
    foreign_dir = os.path.join(RT, f"{hermetic.PREFIX}{dead_pid + 1}-other")
    os.makedirs(foreign_dir, exist_ok=True)
    foreign = subprocess.Popen(
        ["sleep", "600"],
        env=dict(os.environ, **{hermetic.MARK: f"{dead_pid + 1}:other"}))
    got = {}
    try:
        got["train0"] = rehearse(
            ["--workload", "mistral7b-train-1chip", "--seed", "0",
             "--seconds", "2", "--trace", "0"], env)
        victim_log = tmp_path / "victim.err"
        with open(victim_log, "w") as err:
            victim = subprocess.Popen(
                RUN + ["--workload", "mistral7b-serve-closed32", "--seed",
                       str(2 ** 32 + 7), "--seconds", "60", "--trace", "0",
                       "--rehearse"],
                env=env, stdout=subprocess.DEVNULL, stderr=err)
        seen = ""
        deadline = time.monotonic() + 240
        while "phase window" not in seen and time.monotonic() < deadline:
            assert victim.poll() is None, seen[-3000:]
            seen = victim_log.read_text()
            time.sleep(0.1)
        assert "phase window" in seen, seen[-3000:]
        time.sleep(1.0)                          # in mid-window
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)
        got["victim_pid"] = victim.pid
        got["victim_rc"] = victim.returncode
        got["traced"] = rehearse(
            ["--workload", "internlm2-train-fsdp4", "--seed", str(2 ** 31),
             "--seconds", "2", "--trace", "1"], env)
        got["victim_left"] = leftovers(victim.pid)
        got["serve7"] = rehearse(
            ["--workload", "mistral7b-serve-closed32", "--seed", "7",
             "--seconds", "3", "--trace", "0"], env)
        got["stale_left"] = [p for p in (stale_dir, stale_record)
                             if os.path.exists(p)]
        got["foreign_kept"] = os.path.isdir(foreign_dir) \
            and foreign.poll() is None
        got["rt_entries"] = os.listdir(RT)
        got["runs_entries"] = os.listdir(RUNS)
        got["tmpdir_entries"] = os.listdir(env["TMPDIR"])
        got["home_entries"] = os.listdir(env["HOME"])
    finally:
        foreign.kill()
        foreign.wait()
        for path in stale + [stale_record]:
            if os.path.exists(path):
                os.remove(path)
        shutil.rmtree(stale_dir, ignore_errors=True)
        shutil.rmtree(foreign_dir, ignore_errors=True)
        if os.path.isdir(RT) and not os.listdir(RT):
            os.rmdir(RT)
    return got


def test_soak_untraced_train_run(soak):
    line = check_clean_rehearsal(soak["train0"])
    check_result_line(line, {"train.tokens_per_s", "setup_s"}, traced=False)
    assert line["device"] == dict(line["device"], platform="cpu", count=1)


def test_soak_run_after_a_killed_one_is_clean_and_traced(soak):
    assert soak["victim_rc"] == -signal.SIGKILL
    line = check_clean_rehearsal(soak["traced"])
    # a rehearsal has no TPU planes: device readers find nothing and their
    # metrics are left out; the host-clock ones are there
    check_result_line(line, {"lease.worker_ready_s",
                             "trainer.host_ms_per_step"}, traced=True)
    assert line["device"]["count"] == 4      # four virtual CPU devices


def test_soak_traced_run_reads_the_session_before_teardown(soak):
    """With the runtime still up the harness reads the conductor's span
    records: rank 0's ``train.loop`` is there, under ``train.fit``'s ident,
    before ``rt.shutdown()`` reads anything. The metrics that it feeds are
    left out of a rehearsal's line, each by name and with the reason."""
    import re
    err = soak["traced"].stderr
    said = [ln for ln in err.splitlines()
            if "before teardown the conductor holds" in ln]
    assert len(said) == 1, err[-3000:]
    assert err.index(said[0]) < err.index("[bench] phase metrics")
    fit = re.search(r"train\.fit x1 \['(\w+)'\]", said[0])
    loop = re.search(r"train\.loop x1 \['(\w+)'\]", said[0])
    assert fit and loop and fit[1] == loop[1], said[0]
    # a rehearsal probes no chip; nothing else the readers need is missing
    assert re.findall(r"NOT THERE after \S+: ([^;]+);", said[0]) == \
        ["init.probe"], said[0]
    for name in ("trainer.start_s", "trainer.report_ms", "init.probe_s",
                 "lease.spawn_s"):
        assert f"[bench] metric {name} not read: not a TPU run" in err
    assert "[bench] metric flash_roofline not read: no trace" in err
    # an untraced run reads nothing early and leaves nothing out
    assert "before teardown" not in soak["train0"].stderr
    assert "not read" not in soak["train0"].stderr


def test_soak_killed_run_leaves_no_child_and_no_directory(soak):
    assert soak["victim_left"] == []
    assert soak["stale_left"] == [], "a dead run's own record is swept"


def test_soak_untraced_serve_run_and_the_callers_environment(soak):
    line = check_clean_rehearsal(soak["serve7"])
    check_result_line(line, {"serve.tokens_per_s", "serve.request_p95_s",
                             "serve.ttft_p95_s", "setup_s"}, traced=False)
    # stderr ends with each number compared beside its limit
    said = soak["serve7"].stderr.strip().splitlines()
    assert said[-1] == "[bench] correct=True"
    ends = "\n".join(said[-1 - len(line["checks"]):-1])
    for name, (value, limit) in line["checks"].items():
        assert f"check {name}: {value!r} limit {limit!r}" in ends
    assert {"rms_over_floor", "token_deficit_over_std", "eps_off_known",
            "compute_dtype_not_as_configured"} <= set(line["checks"])
    # nothing was written where the caller's TMPDIR and HOME point
    assert soak["tmpdir_entries"] == [] and soak["home_entries"] == []


def test_soak_sweeps_what_this_checkout_recorded_and_nothing_else(soak):
    """Every run's directory and record are gone; what this checkout has no
    record of (another checkout's run, dead as seen from here) is not."""
    assert soak["foreign_kept"]
    assert [n for n in soak["rt_entries"] if not n.endswith("-other")] == []
    assert soak["runs_entries"] == []
    assert marker_carriers() == []


# ---------------------------------------------------------------------------

def toy_manifest(tmp_path, edit) -> str:
    """A copy of the manifest and of the benchmark's data files in a
    temporary directory; ``edit(data, bench_dir)`` adds to it."""
    root = tmp_path / "bench-root"
    bench = root / "benchmark"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        data = json.load(f)
    edit(data, bench)
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    return str(path)


def add_toy_cell(data, bench, family="llama", app="train_lm"):
    config = {"family": family, "param_dtype": "float32",
              "hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "num_hidden_layers": 1, "vocab_size": 256,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
              "source": "https://example.org/toy", "reduced": [],
              "assumed": ["a test's toy"]}
    (bench / "configs" / "toy.json").write_text(json.dumps(config))
    traffic = {"app": app, "seq": 32, "rows_per_chip": 2, "mesh_axis": "dp",
               "remat": False, "reference_rows_per_pass": 1,
               "first_update_fall": {"about": 0.0, "within": 100.0},
               "clients": 2, "prompt_tokens": 8,
               "new_tokens": 4, "max_batch_size": 2,
               "batch_wait_timeout_s": 0.01, "max_ongoing_requests": 2}
    (bench / "traffic" / "toy-mix.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "toy.last_loss.json").write_text(json.dumps({
        "name": "toy.last_loss", "unit": "nats", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "train.tokens_per_s", "workloads": ["toy-cell"],
        "kind": "per_layer", "definition": "the last step's loss"}))
    (bench / "metrics" / "toy.last_loss.py").write_text(
        "def read(record, cell):\n"
        "    return record['window']['steps'][-1][2]\n")
    data["configs"].append({
        "name": "toy", "source": config["source"],
        "file": "benchmark/configs/toy.json", "reduced": [], "why": "toy"})
    data["workloads"].append({"name": "toy-cell", "config": "toy",
                              "traffic": "toy-mix", "chips": 1,
                              "why": "a cell added as data only"})
    data["per_layer"].append({
        "name": "toy.last_loss", "unit": "nats", "better": "lower",
        "source": "program_counter", "layer": "train step",
        "moves": "train.tokens_per_s", "workloads": ["toy-cell"]})
    for metric in data["end_to_end"] + data["per_layer"]:
        if metric["name"] in ("train.tokens_per_s",
                              "trainer.host_ms_per_step"):
            metric["workloads"].append("toy-cell")


def test_a_cell_added_as_data_only_is_found_and_run(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell, each
    new files plus one entry, no file of the benchmark edited."""
    manifest = toy_manifest(tmp_path, add_toy_cell)
    env = hostile_env(tmp_path)
    args = ["--manifest", manifest, "--workload", "toy-cell", "--seed",
            "5", "--seconds", "1"]
    traced = check_clean_rehearsal(rehearse(args + ["--trace", "1"], env))
    assert set(traced["metrics"]) == {
        "lease.worker_ready_s", "trainer.host_ms_per_step", "toy.last_loss"}
    assert traced["metrics"]["toy.last_loss"]["unit"] == "nats"
    assert 4.0 < traced["metrics"]["toy.last_loss"]["value"] < 7.0
    plain = check_clean_rehearsal(rehearse(args + ["--trace", "0"], env))
    assert set(plain["metrics"]) == {"train.tokens_per_s", "setup_s"}


def check_failure(proc, phase: str) -> None:
    """A non-zero exit, no result line, the phase named in the last line of
    stderr and the traceback above it."""
    assert proc.returncode not in (0, 3), proc.stderr[-3000:]
    assert proc.stdout == ""
    assert RESULT_MARK not in proc.stderr
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith(f"[bench] FAILED in phase {phase!r}"), \
        proc.stderr[-3000:]
    assert "Traceback (most recent call last)" in proc.stderr


def test_failure_before_anything_starts_names_the_manifest(tmp_path):
    env = hostile_env(tmp_path)
    proc = rehearse(["--workload", "no-such-cell", "--seed", "1"], env)
    check_failure(proc, "manifest")
    assert "no workload 'no-such-cell'" in proc.stderr
    with open(os.path.join(BENCH, "out",
                           "failure-no-such-cell-1.txt")) as f:
        assert "FAILED in phase 'manifest'" in f.read()
    os.remove(os.path.join(BENCH, "out", "failure-no-such-cell-1.txt"))


def test_failure_to_find_the_application_names_the_import(tmp_path):
    def edit(data, bench):
        add_toy_cell(data, bench, app="no_such_app")
    env = hostile_env(tmp_path)
    proc = rehearse(["--manifest", toy_manifest(tmp_path, edit),
                     "--workload", "toy-cell", "--seed", "1"], env)
    check_failure(proc, "import app")
    os.remove(os.path.join(BENCH, "out", "failure-toy-cell-1.txt"))


def test_failure_to_configure_names_it(tmp_path):
    def edit(data, bench):
        add_toy_cell(data, bench, family="no_such_family")
    env = hostile_env(tmp_path)
    proc = rehearse(["--manifest", toy_manifest(tmp_path, edit),
                     "--workload", "toy-cell", "--seed", "2"], env)
    check_failure(proc, "configure")
    os.remove(os.path.join(BENCH, "out", "failure-toy-cell-2.txt"))


def test_failure_in_the_worker_is_retried_once_then_explained(tmp_path):
    """The worker raises before its window (the traffic names a mesh axis
    the program does not have): one second attempt, loudly, then a non-zero
    exit that carries the worker's own traceback and its log's tail."""
    def edit(data, bench):
        add_toy_cell(data, bench)
        path = bench / "traffic" / "toy-mix.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        mesh_axis="no_such_axis")))
    env = hostile_env(tmp_path)
    proc = rehearse(["--manifest", toy_manifest(tmp_path, edit),
                     "--workload", "toy-cell", "--seed", "3",
                     "--seconds", "1"], env)
    check_failure(proc, "lease+train")
    assert proc.stderr.count("SECOND ATTEMPT") == 1
    assert "no_such_axis" in proc.stderr
    assert "--- tail of worker-" in proc.stderr
    os.remove(os.path.join(BENCH, "out", "failure-toy-cell-3.txt"))
    assert marker_carriers() == []


PLANT = '''"""Plants one fault under a rehearsal, in every process of the run
that imports the program's module (this file is ``sitecustomize`` on the
run's PYTHONPATH)."""
import importlib.abc
import importlib.util
import os
import sys


def altered_token(module):
    """Every token the sampler returns is the next id."""
    sample = module._sample
    module._sample = lambda logits, *a: \\
        (sample(logits, *a) + 1) % logits.shape[-1]


def state_unchanged(module):
    """The optimizer hands the parameters back as it got them."""
    import optax
    make = module.make_lm_train_step
    module.make_lm_train_step = lambda cfg, mesh, *a, **kw: make(
        cfg, mesh, tx=optax.adamw(0.0, weight_decay=0.0))


PLANTS = {"altered_token": ("ray_tpu.models.generate", altered_token),
          "state_unchanged": ("ray_tpu.train.jax_step", state_unchanged)}


class Plant(importlib.abc.MetaPathFinder):
    def __init__(self, name, plant):
        self.name, self.plant = name, plant

    def find_spec(self, name, path, target=None):
        if name != self.name:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        run = spec.loader.exec_module

        def exec_module(module):
            run(module)
            self.plant(module)
        spec.loader.exec_module = exec_module
        return spec


if os.environ.get("BENCH_TEST_PLANT") in PLANTS:
    sys.meta_path.insert(0, Plant(*PLANTS[os.environ["BENCH_TEST_PLANT"]]))
'''


@pytest.mark.parametrize("plant,cell,check", [
    ("altered_token", "mistral7b-serve-closed32", "token_deficit_over_std"),
    ("state_unchanged", "mistral7b-train-1chip", "first_update_fall_off")])
def test_a_run_with_the_timed_path_broken_underneath_is_not_correct(
        tmp_path, plant, cell, check):
    """The whole of a run but its look for a chip, with a fault planted in
    the program it drives: ``correct`` comes out false, the line says which
    check failed with the number beside its limit, and stderr ends with
    the same."""
    (tmp_path / "plant").mkdir()
    (tmp_path / "plant" / "sitecustomize.py").write_text(PLANT)
    env = hostile_env(tmp_path)
    env["BENCH_TEST_PLANT"] = plant
    env["PYTHONPATH"] = os.pathsep.join(
        [str(tmp_path / "plant")] + env.get("PYTHONPATH", "").split(
            os.pathsep)).rstrip(os.pathsep)
    proc = rehearse(["--workload", cell, "--seed", "21", "--seconds", "2",
                     "--trace", "0"], env)
    assert proc.returncode == 3 and proc.stdout == "", proc.stderr[-3000:]
    line = result_of(proc)
    assert line["correct"] is False and line["failed"] == 0
    assert list(line)[-2:] == ["why_not_correct", "checks"]
    assert [why.split(":")[0] for why in line["why_not_correct"]] == [check]
    value, limit = line["checks"][check]
    assert f"{value:.6g}" in line["why_not_correct"][0]
    assert f"limit {limit:.6g}" in line["why_not_correct"][0]
    said = proc.stderr.strip().splitlines()
    assert said[-1] == "[bench] correct=False"
    assert said[-2] == "[bench] NOT CORRECT: " + line["why_not_correct"][0]
    assert f"[bench] check {check}: {value!r} limit {limit!r}" in said


def test_no_accelerator_is_a_non_zero_exit_with_no_result(tmp_path):
    """Without --rehearse there is no CPU fallback."""
    env = hostile_env(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        RUN + ["--workload", "mistral7b-train-1chip", "--seed", "9",
               "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    check_failure(proc, "rt.init")
    assert "0 TPU chip(s)" in proc.stderr
    os.remove(os.path.join(BENCH, "out",
                           "failure-mistral7b-train-1chip-9.txt"))


def test_in_a_directory_with_only_the_benchmark_it_fails(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone run nothing."""
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), root)
    env = hostile_env(tmp_path)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode not in (0, 3) and proc.stdout == ""
    assert "No module named 'ray_tpu'" in proc.stderr
