"""The ``ouro`` family's side of the benchmark: its arithmetic pinned to the
published model, the configuration against the catalog's keys, its readers
on a record with hand-worked answers, its limits against the sweep they
were read from, and the cell's rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, CHECKOUT, config, manifest_data

from benchmark import manifest as manifest_mod
from benchmark import ops, ops_ouro as family
from benchmark.apps import serve_ouro as app
from benchmark.testdata.sweep_ouro import FAULTS

CELL = "ouro2.6b-serve-closed16"
CONFIG = config("ouro-2.6b")
MF = manifest_mod.Manifest()
TRAFFIC = MF.cell(CELL)["traffic_data"]
# The published config.json, as the guide's catalog holds it.
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro",
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152, "layer_types": ["full_attention"] * 48}


def test_parameter_counts_are_the_published_models():
    p = family.param_counts(CONFIG)
    assert p["layer"] == 51_388_416
    assert p["embed"] == p["head"] == 100_663_296
    assert p["gate"] == 2_049
    assert p["total"] == 2_667_974_657 == \
        48 * 51_388_416 + 2 * 100_663_296 + 2_048 + 2_049
    # the loop adds passes, not parameters
    assert family.param_counts({**CONFIG, "total_ut_steps": 1}) == p


def test_the_program_holds_what_the_arithmetic_counts():
    from ray_tpu.models.transformer import transformer_num_params
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 384, "auto"),
                                 remat=False)
    assert (cfg.loop_steps, cfg.sandwich_norm, cfg.n_layers,
            cfg.head_dim, cfg.kv_heads) == (4, True, 48, 128, 16)
    assert transformer_num_params(cfg) == \
        family.param_counts(CONFIG)["total"]


def test_operations_and_bytes_a_token_are_pinned():
    once = ops.forward_ops_per_token(CONFIG, 128)
    fwd = family.forward_ops_per_token(CONFIG, 128)
    assert fwd["layers"] == 4 * once["layers"] == 4 * 2 * 48 * 51_380_224
    assert fwd["attention"] == 4 * once["attention"]
    assert fwd["head"] == once["head"] == 2 * 2048 * 49152
    assert fwd["gate"] == 4 * 2 * 2048
    assert fwd["total"] == sum(fwd[k] for k in ("layers", "attention",
                                                "head", "gate"))
    # a cached position: 4 loop steps x 48 layers x (K + V) x 16 x 128 x 2 B
    assert family.kv_bytes_a_position(CONFIG) == 1_572_864
    assert family.kv_bytes_a_position({**CONFIG, "total_ut_steps": 1}) \
        == 393_216


def test_the_calls_least_time_follows_its_shapes():
    least = family.generate_least_seconds(
        CONFIG, 16, 128, 256, "bfloat16", "TPU v5 lite")
    # the layers' 4.93 GB four times and the head once, a decode step
    assert least["weight_bytes_a_step"] == \
        (4 * 48 * 51_380_224 + 2048 * 49152) * 2
    assert least["cache_bytes"] == 16 * 384 * 1_572_864
    assert least["bound"] == "prefill compute, decode memory"
    assert least["prefill_seconds"] == pytest.approx(
        least["prefill_ops"] / 197e12)
    assert least["decode_seconds"] == pytest.approx(
        least["decode_bytes"] / 819e9)
    assert least["decode_bytes"] == \
        256 * least["weight_bytes_a_step"] + least["kv_bytes_read"]
    assert least["kv_bytes_read"] == \
        16 * 1_572_864 * sum(range(129, 385))
    assert 8.4 < least["seconds"] < 8.5
    # without the loop the same widths stream a quarter of the layers'
    # bytes a step
    plain = ops.generate_least_seconds(CONFIG, 16, 128, 256, "bfloat16",
                                       "TPU v5 lite")
    assert plain["weight_bytes"] == (48 * 51_380_224 + 2048 * 49152) * 2
    with pytest.raises(ops.UnknownDevice):
        family.generate_least_seconds(CONFIG, 16, 128, 256, "bfloat16",
                                      "cpu")


def test_the_configuration_keeps_every_published_key():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == {}
    entry = next(c for c in manifest_data()["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == []
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert CONFIG["torch_dtype"] == CONFIG["param_dtype"] == "bfloat16"
    assert CONFIG["family"] == "ouro"
    recalled = " ".join(CONFIG["assumed"])
    for point in ("four RMSNorms", "final norm", "exit gate",
                  "(loop step, layer)", "no network"):
        assert point in recalled, point


def test_the_traffic_is_the_issues():
    want = {"app": "serve_ouro", "clients": 16, "prompt_tokens": 128,
            "new_tokens": 256, "max_batch_size": 16,
            "batch_wait_timeout_s": 0.1, "max_ongoing_requests": 16,
            "request_timeout_s": 60.0}
    assert {k: TRAFFIC[k] for k in want} == want
    cell = MF.cell(CELL)
    assert (cell["chips"], cell["traffic"]) == \
        (1, "serve-closed16-p128-n256")


def record_of_a_traced_run() -> dict:
    return {
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "batches": [{"start": 0.0, "end": 11.0}],
        "trace": {"busy_s": 20.0, "window_s": 20.5, "module_s": 19.0,
                  "periods": 2, "scopes": {
                      "periods": 2,
                      "seconds": {"": 0.5, "rt.generate.prefill": 0.5,
                                  "rt.generate.decode": 12.0,
                                  "rt.loop.cache": 7.0}}}}


def read(name, record):
    return MF.reader(name)(record, MF.cell(CELL))


def test_the_new_readers_on_a_record_with_hand_worked_answers():
    record = record_of_a_traced_run()
    least = family.generate_least_seconds(
        CONFIG, 16, 128, 256, "bfloat16", "TPU v5 lite")["seconds"]
    assert read("generate_roofline.family", record) == \
        pytest.approx(100 * least * 2 / 19.0)
    assert read("loop.cache_share", record) == pytest.approx(35.0)


def test_the_span_readers_on_a_hand_made_session(monkeypatch):
    from benchmark import spans as spans_mod
    calls = [{"kind": "generate.call", "ts": 10.0 + 11 * i, "value": v,
              "attrs": {"rows": 16, "exit_steps_mean": e}}
             for i, (v, e) in enumerate([(10.9, 1.8), (11.0, 1.9),
                                         (11.3, 2.0), (99.0, 3.0)])]
    calls[3]["ts"] = 5.0                      # before the window: warm-up
    other = [{"kind": "serve.batch.flush", "ts": 12.0, "value": 11.1,
              "attrs": {"rows": 16}}]
    monkeypatch.setattr(spans_mod, "load", lambda record, cell:
                        calls + other)
    monkeypatch.setattr(spans_mod, "in_window", lambda record, spans:
                        [s for s in spans if s["ts"] >= 10.0])
    assert read("loop.call_s", {}) == pytest.approx(11.0)
    assert read("loop.exit_steps_mean", {}) == pytest.approx(1.9)
    for name in ("loop.call_s", "loop.exit_steps_mean"):
        assert MF.reader_module(name).NEEDS == ("generate.call",)
    # a program whose spans carry no such counter: no number, no raise
    for c in calls:
        del c["attrs"]["exit_steps_mean"]
    assert read("loop.exit_steps_mean", {}) is None


@pytest.mark.parametrize("name", ["generate_roofline.family",
                                  "loop.cache_share", "loop.call_s",
                                  "loop.exit_steps_mean"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    """A program without the scope or the span (the parent of the PR that
    added them), an untraced run, a rehearsal: no number, no raise."""
    bare = {"facts": {"platform": "cpu", "kind": "cpu", "count": 1},
            "batches": [], "trace": {}}
    assert read(name, bare) is None
    traced = record_of_a_traced_run()
    traced["trace"].pop("scopes")             # the parent's program
    traced["facts"]["kind"] = "cpu"           # and a rehearsal's device
    assert read(name, traced) is None


def test_the_trace_is_reduced_once_in_a_process_of_its_own():
    """``reduce_apart`` on the recorded v5e trace: what ``trace.reduce_file``
    reads of it, and the seconds by scope beside it."""
    from benchmark import trace as trace_mod
    path = os.path.join(BENCH, "testdata", "train-v5e-3steps.xplane.pb")
    want = json.loads(json.dumps(trace_mod.reduce_file(path)))
    got = app.reduce_apart(path, {})
    scopes = got.pop("scopes")
    assert got == want and want["periods"] == 2
    # no instruction of that program lies under a scope of this one
    assert set(scopes["seconds"]) == {""}
    assert scopes["periods"] == want["periods"]
    assert scopes["seconds"][""] == pytest.approx(want["busy_s"], rel=0.01)


def test_the_typical_positions_error_over_its_floor_is_hand_worked():
    """Two positions of four logits: at the first the system is 2 x as far
    off as the rounded reference, at the second, where both are a hundred
    times farther off, 8 x: the typical position reads 4, the two rms
    errors over both positions together read what the worse one does."""
    import numpy as np

    from benchmark.reference import ouro as reference
    ref = np.zeros((1, 2, 4), np.float32)
    floor = np.array([[[0.01, -0.01, 0.01, -0.01], [1, -1, 1, -1]]])
    system = floor * np.array([2.0, 8.0])[None, :, None]
    np.testing.assert_allclose(reference.errors_a_position(system, ref),
                               [0.02, 8.0], rtol=1e-6)
    over = reference.over_floor(reference.errors_a_position(system, ref),
                                reference.errors_a_position(floor, ref))
    assert over == {"typical": pytest.approx(4.0, rel=1e-5),
                    "worst": pytest.approx(8.0, rel=1e-5)}
    together = reference.compare_logits(system, floor + 1e-9)  # std > 0
    assert np.sqrt((system ** 2).mean() / (floor ** 2).mean()) == \
        pytest.approx(8.0, rel=1e-3) and together["n_logits"] == 8
    exits = reference.compare_exits(
        np.array([[0.5, 0.25, 0.25]]), np.array([[0.5, 0.3, 0.2]]))
    assert exits["exit_rows_off_one"] == 0.0
    assert exits["exit_gap"] == pytest.approx(0.05)
    assert exits["exit_rms"] == pytest.approx(0.05 * (2 / 3) ** 0.5)
    # the worst position is held beside the typical one: one position of
    # 32 off a thousandfold moves the geometric mean 1.24 x
    over = reference.over_floor([1.0] * 31 + [1000.0], [1.0] * 32)
    assert over["typical"] == pytest.approx(1000 ** (1 / 32), rel=1e-5)
    assert over["typical"] < 1.25 and over["worst"] == 1000.0


def test_the_caches_errors_and_the_tokens_gaps_are_hand_worked():
    """Two slots, one row, three positions of which two are the prompt's,
    one head of two numbers: keys off by 0.1 at the prompt's positions of
    slot 0, values off by 0.4 at the decoded position of slot 1. Then two
    served tokens: one is the reference's best, the other lies 0.6 under
    it where rounding alone moves the logits by 0.2 (rms): 3 floors."""
    import numpy as np

    from benchmark.reference import ouro as reference
    want = {n: np.zeros((2, 1, 3, 1, 2), np.float32) for n in ("k", "v")}
    got = {n: a.copy() for n, a in want.items()}
    got["k"][0, :, :2] = 0.1
    got["v"][1, :, 2:] = -0.4
    errors = np.asarray(reference.cache_errors(got, want, 2))
    np.testing.assert_allclose(
        errors, [[[0.1, 0.0], [0.0, 0.0]], [[0.0, 0.0], [0.0, 0.4]]],
        atol=1e-7)
    # a cache of more slots and positions than the reference kept
    longer = {n: np.pad(a, ((0, 1), (0, 0), (0, 4), (0, 0), (0, 0)))
              for n, a in got.items()}
    np.testing.assert_allclose(reference.cache_errors(longer, want, 2),
                               errors, atol=1e-7)
    logits = np.array([[[1.0, 0.0, -1.0, 0.0], [0.4, 1.0, 0.0, -1.0]]])
    floor = logits + np.array([[[0.0] * 4, [0.2, -0.2, 0.2, -0.2]]])
    floor[0, 0] += [1e-3, -1e-3, 1e-3, -1e-3]
    np.testing.assert_allclose(
        reference.token_deficits(logits, [[0, 0]]), [[0.0, 0.6]], atol=1e-7)
    assert reference.token_deficit_over_floor(logits, floor, [[0, 0]]) == \
        pytest.approx(3.0, rel=1e-5)
    assert reference.token_deficit_over_floor(logits, floor, [[0, 1]]) == 0


def record_of(checks: dict, **over) -> dict:
    """What ``judge`` reads of a run, from a sweep's row."""
    tokens = list(range(TRAFFIC["new_tokens"]))
    row = {"ok": True, "rid": 0, "extra": {"tokens": tokens}}
    return {"checks": {
        "rms_norm_eps": {"published": 1e-6, "program": 1e-6},
        "param_dtypes": ["bfloat16"], "compute_dtype": "bfloat16",
        **checks, **over},
        "warmup": [row, dict(row, rid=1)], "window": {"rows": [row]}}


def failed_checks(why) -> set:
    return {w.split(":")[0] for w in why}


def test_the_judgement_names_what_failed():
    slots = [[[0.011, 0.012], [0.013, 0.010]]] * 48
    floors = [[[0.010, 0.010], [0.010, 0.010]]] * 48
    sound = {"rms_over_std": 0.015, "floor_rms_over_std": 0.01,
             "rms_over_floor_a_position": 1.3,
             "rms_over_floor_worst_position": 1.9,
             "cache_errors": slots, "floor_cache_errors": floors,
             "token_deficit_over_std": 0.05, "token_deficit_over_floor": 2.0,
             "exit_rows_off_one": 1e-7, "exit_over_floor_a_position": 1.2}
    record = record_of(sound)
    assert app.judge(record, CONFIG, TRAFFIC) == []
    assert set(record["judged"]) == set(app.WHAT_EACH_CHECK_SAYS)
    assert record["judged"]["rms_over_floor"] == [1.3, app.RMS_OVER_FLOOR]
    assert record["judged"]["cache_over_floor"] == [
        pytest.approx((1.1 * 1.2 * 1.3 * 1.0) ** 0.25, rel=1e-5),
        app.CACHE_OVER_FLOOR]
    assert record["judged"]["cache_over_floor_worst"] == [
        pytest.approx(1.3, rel=1e-5), app.CACHE_OVER_FLOOR_WORST]
    # the gap over the logits' spread is reported, not judged
    assert "token_deficit_over_std" not in record["judged"]
    one_slot_off = [s for s in slots]
    one_slot_off[7] = [[0.011, 0.012], [0.013, 0.5]]
    for over, names in (
            (dict(rms_over_floor_a_position=1.01 * app.RMS_OVER_FLOOR),
             {"rms_over_floor"}),
            (dict(rms_over_floor_a_position=float("nan")),
             {"rms_over_floor"}),
            (dict(rms_over_floor_worst_position=1.01
                  * app.RMS_OVER_FLOOR_WORST), {"rms_over_floor_worst"}),
            (dict(cache_errors=[[[3 * x for x in kv] for kv in slot]
                                for slot in slots]),
             {"cache_over_floor", "cache_over_floor_worst"}),
            (dict(cache_errors=one_slot_off), {"cache_over_floor_worst"}),
            (dict(token_deficit_over_floor=1.01 * app.TOKEN_OVER_FLOOR),
             {"token_deficit_over_floor"}),
            (dict(token_deficit_over_std=9.0), set()),
            (dict(exit_rows_off_one=1e-3), {"exits_off"}),
            (dict(exit_over_floor_a_position=1.01 * app.EXIT_OVER_FLOOR),
             {"exits_off"}),
            (dict(exit_over_floor_a_position=float("inf")), {"exits_off"}),
            (dict(compute_dtype="float32"),
             {"compute_dtype_not_as_configured"})):
        why = app.judge(record_of(sound, **over), CONFIG, TRAFFIC)
        assert failed_checks(why) == names, (over, why)


def test_the_judgement_opens_no_backend():
    """``judge`` runs in the benchmark's own process, beside a replica that
    owns the chip: there any use of JAX's arrays fails (``Unable to
    initialize backend 'tpu'``: all eight runs of one chip call, PR 39).
    Here: a process whose only platform cannot start judges a sweep's
    row."""
    code = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "from benchmark.apps import serve_ouro as app\n"
        "row = json.load(open(%r))['seeds'][0]\n"
        "tokens = list(range(256))\n"
        "r = {'ok': True, 'rid': 0, 'extra': {'tokens': tokens}}\n"
        "record = {'checks': dict(row, rms_norm_eps={'published': 1e-6, "
        "'program': 1e-6}, param_dtypes=['bfloat16']), "
        "'warmup': [r, dict(r, rid=1)], 'window': {'rows': [r]}}\n"
        "print(app.judge(record, json.load(open(%r)), "
        "{'new_tokens': 256}))\n" % (
            CHECKOUT, os.path.join(BENCH, "testdata",
                                   "ouro_checks_sweep.json"),
            os.path.join(BENCH, "configs", "ouro-2.6b.json")))
    env = dict(os.environ, JAX_PLATFORMS="tpu", TPU_SKIP_MDS_QUERY="1")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "[]"


def test_a_program_without_the_mechanisms_is_refused_before_anything_starts(
        monkeypatch):
    """The parent's ``TransformerConfig``: ``drive`` raises in words in the
    benchmark's own process, before ``rt.init()``."""
    import dataclasses

    import ray_tpu.models as models

    @dataclasses.dataclass(frozen=True)
    class ParentsConfig:
        vocab_size: int = 1
        d_model: int = 1
    monkeypatch.setattr(models, "TransformerConfig", ParentsConfig)
    with pytest.raises(ValueError, match="cannot run a looped stack"):
        app.transformer_config(app.model_kwargs(CONFIG, 384, "auto"),
                               remat=False)


def test_the_cell_rehearses_clean_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(TMPDIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 39), "--seconds", "2", "--trace", "1",
         "--rehearse"], env=env, cwd=CHECKOUT, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == ""
    line = next(ln for ln in proc.stderr.splitlines()
                if "REHEARSAL result" in ln)
    result = json.loads(line.split("stdout): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["exits_off"] == [0, 0]
    assert 0 < result["checks"]["cache_over_floor"][0] \
        <= result["checks"]["cache_over_floor_worst"][0]
    assert "lease.worker_ready_s" in result["metrics"]
    assert "generate.call x" in proc.stderr or \
        "generate.call " in proc.stderr        # the program's own span
    assert "'loop_steps': 3, 'cache_slots': 6" in proc.stderr
    assert not os.listdir(tmp_path)             # nothing left behind


# --- the limits and the sweep they were read from ---------------------------

def sweep() -> dict:
    with open(os.path.join(BENCH, "testdata",
                           "ouro_checks_sweep.json")) as f:
        return json.load(f)


def sound_row(data: dict, seed: int) -> dict:
    return next(r for r in data["seeds"] if r["seed"] == seed)


def not_correct_by(planted: dict, row: dict) -> set:
    """The checks a sweep's reading fails: ``planted`` in the program's
    place, on the seed whose sound reading (and floors) ``row`` is."""
    planted = dict(row, **planted)
    if planted["exit_over_floor_a_position"] is None:   # a step short:
        planted["exit_over_floor_a_position"] = float("inf")  # no such row
    return failed_checks(app.judge(record_of(planted), CONFIG, TRAFFIC))


def altered_tokens(row: dict) -> float:
    """What the sweep's altered token reads: the next id in the place of
    the served token a third of the way into the first checked reply,
    over that position's floor."""
    at = TRAFFIC["new_tokens"] // 3
    served = row["served"]["a_position"]
    return served["deficit_of_the_next_id"][at] / served["floor"][at]


def test_the_limits_come_from_their_sweep():
    """Every limit lies between its two readings with room on both sides:
    the worst of the sound seeds under it, the least of what it is there
    to tell apart over it."""
    data = sweep()
    assert data["cell"] == CELL and not data["tiny"]
    assert data["device"]["platform"] == "tpu"
    assert data["compiled_generate"]["peak_memory_in_bytes"] >= 12.5e9
    assert data["cache_passes"] == app.CACHE_PASSES == 1
    rows = data["seeds"]
    assert len(rows) >= 12 and len({r["seed"] for r in rows}) == len(rows)
    assert sum(1 for r in rows if r["seed"] >= 2 ** 31) >= len(rows) // 2

    def readings(name, told_apart):
        sound = max(r[name] for r in rows)
        control = min(r["control_int8_reference"][name] for r in rows)
        faults = {f: min(row[f][name] for row in data["faults"]
                         if row[f][name] is not None) for f in told_apart}
        return sound, control, faults

    # the first loop step's keys and values: the control on every seed,
    # and the two faults that touch the first loop step's slots
    for name, limit, room in (
            ("cache_over_floor", app.CACHE_OVER_FLOOR, 1.5),
            ("cache_over_floor_worst", app.CACHE_OVER_FLOOR_WORST, 1.5)):
        sound, control, faults = readings(
            name, ("one_slot_a_layer", "no_output_norms"))
        assert room * sound <= limit <= control / room, name
        assert limit < 0.1 * min(faults.values()), name
    # the logits: 1.5 x the worst sound seed, under 0.7 x the least of the
    # faults they tell apart; the control and one slot a layer are the
    # cache's and the exit distribution's to catch
    three = ("one_loop_step_short", "no_output_norms",
             "final_norm_at_the_end_only")
    for name, limit in (
            ("rms_over_floor_a_position", app.RMS_OVER_FLOOR),
            ("rms_over_floor_worst_position", app.RMS_OVER_FLOOR_WORST)):
        sound, control, faults = readings(name, three)
        assert limit == pytest.approx(1.5 * sound, rel=0.02), name
        assert limit < 0.7 * min(faults.values()), name
        assert control < limit, name        # why the cache is read
    exits = max(r["exit_over_floor_a_position"] for r in rows)
    exit_faults = min(f[name]["exit_over_floor_a_position"]
                      for f in data["faults"] for name in FAULTS
                      if f[name]["exit_over_floor_a_position"] is not None)
    assert app.EXIT_OVER_FLOOR == pytest.approx(1.5 * exits, rel=0.02)
    assert app.EXIT_OVER_FLOOR < 0.45 * exit_faults
    # the served tokens, each over its own position's floor: 2.5 x the
    # worst sound reading, under 0.8 x what an altered token reads
    deficit = max(r["token_deficit_over_floor"] for r in rows)
    altered = min(altered_tokens(r) for r in rows)
    assert 2.5 * deficit <= app.TOKEN_OVER_FLOOR <= 0.8 * altered
    for r in rows:
        assert r["served"]["token_deficit_over_floor"] == \
            r["token_deficit_over_floor"]
        assert app.judge(record_of(r), CONFIG, TRAFFIC) == [], r["seed"]
        assert r["exit_rows_off_one"] <= app.EXIT_ROWS_SUM_WITHIN / 10


def test_the_ratio_of_all_positions_together_does_not_separate():
    """Why the ratio is read a position at a time: ``serve_lm``'s r / f over
    the sound seeds reaches past what planted faults read."""
    data = sweep()
    together = [r["rms_over_std"] / r["floor_rms_over_std"]
                for r in data["seeds"]]
    planted = [f[name]["rms_over_std"]
               / sound_row(data, f["seed"])["floor_rms_over_std"]
               for f in data["faults"] for name in FAULTS]
    assert max(together) > 2.6 and min(together) < 0.8
    assert min(planted) < max(together)
    assert sum(1 for x in planted if x < max(together)) >= 3


def test_the_control_is_not_correct_on_any_seed():
    """int8 weights through the reference, in the program's place: over
    both limits on the first loop step's keys and values on every seed,
    where the logits (192 layer passes on) tell it apart on 3 of 12. A
    seed's control reads at least 2.5 x its own sound run."""
    rows = sweep()["seeds"]
    for r in rows:
        why = not_correct_by(r["control_int8_reference"], r)
        assert {"cache_over_floor", "cache_over_floor_worst"} <= why, \
            r["seed"]
    by_logits = [bool(not_correct_by(
        {n: v for n, v in r["control_int8_reference"].items()
         if n != "cache_errors"}, r)) for r in rows]
    assert 0 < sum(by_logits) < len(rows)
    nearest = min(r["control_int8_reference"]["cache_over_floor"]
                  / r["cache_over_floor"] for r in rows)
    assert nearest > 2.5


def test_an_altered_token_is_not_correct_on_any_seed():
    for r in sweep()["seeds"]:
        why = not_correct_by(
            {"token_deficit_over_floor": altered_tokens(r)}, r)
        assert why == {"token_deficit_over_floor"}, r["seed"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct_on_any_seed(fault):
    data = sweep()
    assert len(data["faults"]) >= 4
    for row in data["faults"]:
        why = not_correct_by(row[fault], sound_row(data, row["seed"]))
        assert "exits_off" in why, (fault, row["seed"])
        if fault != "one_slot_a_layer":
            assert {"rms_over_floor", "rms_over_floor_worst"} <= why, \
                (fault, row["seed"])
        if fault in ("one_slot_a_layer", "no_output_norms"):
            assert {"cache_over_floor", "cache_over_floor_worst"} <= why, \
                (fault, row["seed"])
