"""One run of one cell:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: a private runtime directory under the caller's
``TMPDIR`` or the checkout (``benchmark/hermetic.py``), ``rt.init()``, one
TPU lease through the cell's application (``benchmark/apps/<app>.py``:
``JaxTrainer(...).fit()`` or ``serve.run(...)``), weights made on the device
from ``--seed``, the correctness check against the plain reference, warm-up
of the cell's own shapes, the measured window, in a traced run one read of
the conductor's span records while the runtime is still up,
``rt.shutdown()``, every child gone, then one JSON line on stdout. This
process never opens a JAX backend. A metric of the cell whose reader found
nothing to read is left out of the line and named on stderr with the
reader's reason (``[bench] metric <name> not read: <why>``). The line ends
with ``checks``: every number ``correct`` compared, ``{name: [value,
limit]}``, and a line that is not correct carries ``why_not_correct`` before
it; the same numbers are the last lines on stderr (``[bench] check <name>:
<value> limit <limit>``).

No TPU, too few chips, a failed phase, a compile inside the window or a
device kind without published peaks is a non-zero exit with no result line;
the last lines of stderr then name the phase and carry the traceback and
the tails of the runtime's own logs, and the same text is kept in
``benchmark/out/failure-<cell>-<seed>.txt``. There is no CPU fallback.
``--rehearse`` drives the same calls at the toy sizes of the files'
``rehearse`` groups on the CPU to debug this command; it prints no result
line and always exits 3.

Everything that belongs to one cell, one traffic mix, one configuration or
one metric is a file found by the name in ``BENCHMARK.json``
(``benchmark/manifest.py``); nothing here knows one by name.
"""

from __future__ import annotations

import time

_IMPORTED_AT = time.time()

import argparse      # noqa: E402
import glob          # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import signal        # noqa: E402
import sys           # noqa: E402
import traceback     # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark import hermetic, manifest as manifest_mod, ops  # noqa: E402
from benchmark import spans as spans_mod                       # noqa: E402

REHEARSAL_EXIT = 3
DEADLINE_S = 1150          # a cold run may take 1200 s; a warm one 360 s
LOG_TAIL_BYTES = 6000
log = hermetic.log


def process_start() -> float:
    """When this process began, by the kernel's word (the interpreter's own
    start-up is set-up too)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        started = btime + ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= _IMPORTED_AT - started < 60:
            return started
    except (OSError, ValueError, StopIteration):
        pass
    return _IMPORTED_AT


class BenchFailure(Exception):
    def __init__(self, msg: str, before_window: bool = False):
        super().__init__(msg)
        self.before_window = before_window


class RunContext:
    """What an application's ``drive(run)`` is given."""

    def __init__(self, args, world: hermetic.Run, started: float):
        self.cell = None
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.world = world
        self.started = started
        self.current_phase = "start"
        self.rt = None
        self.serve = None
        self.logs = ""
        self.span_needs = []       # span kinds this cell's readers read

    def phase(self, name: str) -> None:
        self.current_phase = name
        log(f"phase {name} at {time.time() - self.started:.2f}s")

    def path(self, name: str) -> str:
        return os.path.join(self.world.tmp, name)

    def failure(self, msg: str, before_window: bool = False) -> BenchFailure:
        return BenchFailure(msg, before_window)

    def init_runtime(self, rt, chips: int) -> None:
        if not self.rehearse:
            self.world.wait_for_chips(chips)
        try:
            rt.init()
        except Exception as e:
            raise BenchFailure(f"rt.init() failed: {e!r}",
                               before_window=True) from e
        self.rt = rt
        have = int(rt.cluster_resources().get("TPU", 0))
        log(f"rt.init() done at {time.time() - self.started:.1f}s: {have} "
            f"TPU chip(s); cell {self.cell['name']} asks for {chips}")
        if not self.rehearse and have < chips:
            raise BenchFailure(
                f"this machine has {have} TPU chip(s), the cell needs "
                f"{chips} (jax found no accelerator, or too few)")

    def teardown(self) -> None:
        """Runtime down, in order; what is left is killed by the caller.
        A traced run first reads the conductor's span records with the
        runtime still up: the readers' session does not hang on what the
        last flush of a process that is killed next shipped."""
        if self.rt is not None and self.trace:
            spans_mod.keep_before_teardown(self.span_needs)
        if self.serve is not None:
            try:
                self.serve.shutdown()
            except Exception as e:          # noqa: BLE001 - on the way out
                log(f"serve.shutdown(): {e!r}")
            self.serve = None
        if self.rt is not None:
            try:
                self.rt.shutdown()
            except Exception as e:          # noqa: BLE001 - on the way out
                log(f"rt.shutdown(): {e!r}")
            self.rt = None


def log_tails(tmp: str) -> str:
    """The ends of the worker's, daemon's and replica's own logs, copied
    out of the session directory before it is removed."""
    out = []
    paths = sorted(glob.glob(os.path.join(tmp, "rtpu-session-*", "*.out"))
                   + glob.glob(os.path.join(tmp, "rtpu-session-*", "*.log"))
                   + glob.glob(os.path.join(tmp, "rtpu-session-*", "*.err")))
    for path in paths:
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - LOG_TAIL_BYTES))
                tail = f.read().decode("utf-8", "replace")
        except OSError:
            continue
        out.append(f"--- tail of {os.path.basename(path)} "
                   f"({size} bytes) ---\n{tail.rstrip()}".rstrip())
    return "\n".join(out) or "(the runtime's session directory holds no logs)"


def explain(run: RunContext, args, exc: BaseException) -> None:
    text = "\n".join([
        f"[bench] FAILED in phase {run.current_phase!r} "
        f"(cell {args.workload}, seed {args.seed}, "
        f"{time.time() - run.started:.1f}s after start)",
        "".join(traceback.format_exception(exc)).rstrip(),
        run.logs or log_tails(run.world.tmp),
        f"[bench] FAILED in phase {run.current_phase!r}: "
        f"{type(exc).__name__}: " + (str(exc).strip().splitlines()
                                     or [""])[-1]])
    print(text, file=sys.stderr, flush=True)
    try:
        out_dir = os.path.join(CHECKOUT, "benchmark", "out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"failure-{args.workload}-{args.seed}.txt"),
                "w") as f:
            f.write(text + "\n")
    except OSError as e:
        log(f"could not keep the failure text: {e!r}")


def result_line(run: RunContext, mf, record: dict) -> dict:
    facts = record["facts"]
    if not run.rehearse:
        ops.peaks(facts["kind"])      # an unknown kind is an error
    if record["compiles_in_window"]:
        raise BenchFailure("something compiled inside the measured window")
    kind = "per_layer" if run.trace else "end_to_end"
    record["setup_s"] = record["window_start"] - run.started
    metrics = mf.read_metrics(kind, run.cell, record)
    device = {"platform": facts["platform"], "kind": facts["kind"],
              "count": facts["count"],
              "memory_peak_bytes": record["memory"]["peak_bytes"]}
    line = {"correct": not record["why_not_correct"],
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device}
    reduced = record.get("trace") or {}
    if run.trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    elif run.trace and not run.rehearse:
        raise BenchFailure("the traced run's profile holds no whole period "
                           "of device work")
    # what ``correct`` compared, each number beside its limit: last on the
    # line, so that the end of a line that is kept says why
    if record["why_not_correct"]:
        line["why_not_correct"] = record["why_not_correct"]
    line["checks"] = record["judged"]
    return line


def report(run: RunContext, record: dict, line: dict) -> None:
    """The run in words, on stderr. Nothing is logged after it, so its end
    is the end of stderr: each number compared beside its limit."""
    out_dir = os.path.join(CHECKOUT, "benchmark", "out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"record-{run.cell['name']}-trace"
                f"{int(run.trace)}.json"), "w") as f:
            json.dump(record, f, default=str)
    except OSError as e:
        log(f"could not keep the record: {e!r}")
    stamps = record["stamps"]
    order = sorted((v, k) for k, v in stamps.items())
    log("set-up: " + " ".join(f"{k}={v - run.started:.2f}s"
                              for v, k in order)
        + f" window_start={record['window_start'] - run.started:.2f}s")
    log("checks: " + json.dumps(record["checks"]))
    log(f"memory: {json.dumps(record['memory'])}")
    log(f"attempted={line['attempted']} failed={line['failed']}")
    for name, (value, limit) in record["judged"].items():
        log(f"check {name}: {value!r} limit {limit!r}")
    for why in record["why_not_correct"]:
        log(f"NOT CORRECT: {why}")
    log(f"correct={line['correct']}")


def attempt(run: RunContext, app) -> dict:
    try:
        record = app.drive(run)
    except BaseException:
        # rt.shutdown() removes the session directory: copy the logs' ends
        # out of it first
        run.logs = log_tails(run.world.tmp)
        raise
    finally:
        run.teardown()
    return record


def main(argv=None) -> int:
    started = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU to debug this command; "
                         "never a result, always exits 3")
    ap.add_argument("--manifest", default="",
                    help="another BENCHMARK.json (tests add cells as data)")
    args = ap.parse_args(argv)

    world = hermetic.Run(CHECKOUT)
    run = RunContext(args, world, started)
    os.chdir(CHECKOUT)
    code = 1
    try:
        run.phase("manifest")
        mf = manifest_mod.Manifest(args.manifest)
        cell = run.cell = mf.cell(args.workload)
        if args.trace:
            run.span_needs = mf.span_needs(cell["name"])
        if args.seconds is None:
            args.seconds = float(mf.data["run_seconds"])
        run.seconds = args.seconds
        if args.rehearse:
            # the CPU stands in, with as many virtual devices as the cell
            # has chips
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={cell['chips']}"
            log("REHEARSAL on the CPU at toy size: nothing below is a "
                "result")
        run.phase("hermetic")
        world.enter()
        if args.rehearse:   # CPU programs stay out of the chip's cache
            os.environ["JAX_COMPILATION_CACHE_DIR"] = run.path("cache")
        signal.signal(signal.SIGALRM, hermetic.Run._on_signal)
        signal.alarm(DEADLINE_S)
        run.phase("import app")
        app = importlib.import_module(
            "benchmark.apps." + cell["traffic_data"]["app"])
        try:
            record = attempt(run, app)
        except BenchFailure as e:
            if not e.before_window:
                raise
            # Allowed once, and never for the window itself: the lease or
            # rt.init() raised before any measured work. Its time stays
            # inside setup_s.
            log(f"SECOND ATTEMPT: {e} (phase {run.current_phase!r}); "
                "runtime torn down, trying once more")
            left = world.children_gone()
            if left:
                log(f"processes of the first attempt still alive: {left}")
            record = attempt(run, app)
        gone = world.children_gone()
        if gone:
            raise BenchFailure(f"processes would not die: {gone}")
        if not hermetic.wait_until(
                lambda: not os.path.exists(f"/proc/{record['facts']['pid']}"),
                30.0):
            raise BenchFailure("the chip-owning worker is still alive")
        run.phase("metrics")
        line = result_line(run, mf, record)
        if args.rehearse:
            log("REHEARSAL result (not printed to stdout): "
                + json.dumps(line))
        else:
            sys.stdout.write(json.dumps(line) + "\n")
            sys.stdout.flush()
        report(run, record, line)
        code = REHEARSAL_EXIT if args.rehearse else 0
    except hermetic.Terminated as e:
        explain(run, args, e)
        code = 128 + e.signum
    except BaseException as e:          # noqa: BLE001 - explained, re-exited
        explain(run, args, e)
        code = 1
    finally:
        signal.alarm(0)
        run.teardown()
        world.leave()
    return code


if __name__ == "__main__":
    _code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Everything this run started is gone and waited for (world.leave());
    # no thread the runtime may have left can hold the exit up.
    os._exit(_code)
