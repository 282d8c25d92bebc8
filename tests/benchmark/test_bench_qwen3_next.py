"""The ``qwen3_next`` family's side of the benchmark: its arithmetic pinned
to the published model, its readers on a record with hand-worked answers,
the scope reducer on a small module, its limits against the sweep they were
read from, and the cell's rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, CHECKOUT, config

from benchmark import manifest as manifest_mod
from benchmark import ops_qwen3_next as family
from benchmark import trace_scopes

CELL = "qwen3next-train-s8192-ep16share"
CONFIG = config("qwen3-next-80b-a3b-l4-e32")
MF = manifest_mod.Manifest()


def test_parameter_counts_are_the_published_models():
    p = family.param_counts(CONFIG)
    assert p["linear_mixer"] == 33_718_464
    assert p["full_mixer"] == 27_263_488
    assert p["layer_rest"] == 4_200_448
    assert p["expert"] == 3_145_728
    assert p["linear_layer"] == 138_582_208
    assert p["full_layer"] == 132_127_232
    assert p["total"] == 625_667_136                  # the cut, 10.01 GB
    assert p["total"] * 16 / 1e9 == pytest.approx(10.01, abs=0.005)
    assert p["whole_model"] / 1e9 == pytest.approx(79.67, abs=0.005)
    assert family.layer_kinds(CONFIG) == ["linear", "linear", "linear",
                                          "full"]


def test_the_program_holds_what_the_arithmetic_counts():
    from benchmark.apps import train_qwen3_next as app
    from ray_tpu.models.transformer import transformer_num_params
    cfg = app.transformer_config(
        app.model_kwargs(CONFIG, 8192, "flash"), remat=True)
    assert transformer_num_params(cfg) == \
        family.param_counts(CONFIG)["total"]
    assert (cfg.head_dim, cfg.rotary_dim, cfg.held, cfg.num_experts) == \
        (256, 64, 32, 512)


def test_operations_a_token_are_pinned():
    fwd = family.forward_ops_per_token(CONFIG, 8192)
    assert fwd["linear_projections"] == 3 * (67_371_008 + 65_536)
    assert fwd["linear_scan"] == 3 * 32 * 139_264
    assert fwd["full_projections"] == 54_525_952
    assert fwd["full_scores"] == 67_108_864
    assert fwd["head"] == 77_791_232
    # router + shared expert and its gate + 10 x 32 / 512 routed experts
    assert fwd["experts"] == 4 * (2_097_152 + 6_291_456 + 4_096
                                  + 0.625 * 6_291_456)
    assert fwd["total"] == pytest.approx(464.4e6, rel=1e-3)
    assert family.train_ops_per_token(CONFIG, 8192) == 3 * fwd["total"]
    shares = {k: v / fwd["total"] for k, v in fwd.items()}
    assert shares["linear_projections"] + shares["linear_scan"] == \
        pytest.approx(0.465, abs=0.005)
    assert shares["experts"] == pytest.approx(0.106, abs=0.003)


def test_least_times_follow_their_shapes():
    kind = "TPU v5 lite"
    scan = family.gdn_scan_step_least_seconds(CONFIG, 8192, 2, True, kind)
    assert scan["layers"] == 3
    assert scan["ops"] == 3 * 4 * 16384 * 32 * 139_264
    # q, k of 16 key heads, v, o of 32 value heads, g and beta: forward
    # twice, backward with do for o and five gradients out
    assert scan["bytes"] == 3 * 16384 * (2 * 24_832 + 24_832 + 16_640)
    assert scan["seconds"] == pytest.approx(
        max(scan["ops"] / 197e12, scan["bytes"] / 819e9))
    assert scan["bound"] == "memory"
    few = family.moe_experts_step_least_seconds(CONFIG, 40_960, True, kind)
    many = family.moe_experts_step_least_seconds(CONFIG, 655_360, True, kind)
    assert few["bound"] == "memory" and many["bound"] == "compute"
    assert few["ops"] == 40_960 * 2 * 3 * 2048 * 512 * 4
    assert few["seconds"] < many["seconds"]


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if "Qwen3-Next-80B-A3B-Instruct" in line)
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CONFIG["source"] == row["source_url"]
    assert (CONFIG["num_experts"], CONFIG["num_experts_published"]) == \
        (32, 512)
    assert CONFIG["vocab_size"] * 8 == CONFIG["vocab_size_published"]
    assert "16 chips" in CONFIG["deployment"]


HLO = '''
HloModule jit_step_fn

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(rt.gdn.scan))/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(step_fn)/rt.attn.gated/checkpoint/rt.moe.route/dot_general"}
  %ragged-dot-metadata.3 = (s32[3]{0}, s32[1]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element.4 = s32[3]{0} get-tuple-element(%ragged-dot-metadata.3), index=0
  %ragged-dot-none.5 = f32[8]{0} custom-call(%get-tuple-element.4, %a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply.6 = f32[8]{0} multiply(%ragged-dot-none.5, %a), metadata={op_name="jit(step_fn)/rt.moe.experts/mul"}
  ROOT %add.7 = f32[8]{0} add(%multiply.6, %fusion.1)
}
'''


def test_scope_map_finds_the_innermost_scope_and_what_has_none():
    scopes = trace_scopes.scope_map(HLO)
    assert scopes["fusion.1"] == "rt.gdn.scan"      # from what it fuses
    assert scopes["dot.2"] == "rt.moe.route"        # the innermost
    # the compiler's own kernels take their users' scope, through the
    # tuple elements between them
    assert scopes["ragged-dot-none.5"] == "rt.moe.experts"
    assert scopes["get-tuple-element.4"] == "rt.moe.experts"
    assert scopes["ragged-dot-metadata.3"] == "rt.moe.experts"
    assert "add.7" not in scopes and "a" not in scopes


def test_scope_seconds_over_a_hand_made_stream():
    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    ops = [("%while.9 = while()", 1.0, 1.0),            # encloses the next
           ("%fusion.1 = fusion()", 1.0, 0.25),
           ("%ragged-dot-none.5 =" + mosaic, 1.25, 0.5),
           ("%add.7 = add()", 1.75, 0.125),
           ("%fusion.1 = fusion()", 2.5, 0.25)]         # past the window
    modules = [("jit_step_fn(1)", 1.0, 1.0), ("jit_step_fn(1)", 2.0, 1.0)]
    got = trace_scopes.reduce_device(ops, modules,
                                     trace_scopes.scope_map(HLO))
    assert got["periods"] == 1
    assert got["seconds"] == {"": 0.25, "rt.gdn.scan": 0.25,
                              "rt.moe.experts": 0.5}
    assert got["mosaic_seconds"] == {"rt.moe.experts": 0.5}
    assert got["top"]["rt.gdn.scan"] == [("fusion.1", 0.25)]
    assert trace_scopes.reduce_device(ops, modules[:1], {}) == {}
    record = {"trace": {"busy_s": 1.0, "scopes": got}}
    assert trace_scopes.scope_seconds(record, "rt.moe.") == 0.5
    assert trace_scopes.scope_seconds(record, "rt.attn.") is None
    assert trace_scopes.scope_seconds({}, "rt.") is None


def record_of_a_traced_run() -> dict:
    names = ["moe_rows_here", "moe_rows_dropped", "moe_load_max",
             "moe_load_mean"]
    return {
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "window": {"steps": [[0.0, 0.9, 9.8], [1.0, 1.9, 9.8],
                             [2.0, 2.9, 9.8], [3.0, 3.9, 9.8],
                             [4.0, 4.9, 9.8]],
                   "profiler": [], "tokens_per_step": 16384,
                   "traced_steps": [1, 5],
                   "counters": {"names": names, "steps": [
                       [99999, 0, 480, 320], [40960, 0, 640, 320],
                       [50000, 0, 512, 320], [30000, 0, 400, 320],
                       [99999, 0, 480, 320]]}},
        "trace": {"busy_s": 3.0, "window_s": 3.0, "scopes": {
            "periods": 3,
            "seconds": {"": 0.6, "rt.gdn.scan": 0.9, "rt.gdn.proj": 0.3,
                        "rt.moe.experts": 0.45, "rt.moe.route": 0.15,
                        "rt.attn.gated": 0.6},
            "mosaic_seconds": {"rt.moe.experts": 0.3,
                               "rt.attn.gated": 0.2}}}}


def read(name, record):
    return MF.reader(name)(record, MF.cell(CELL))


def test_the_new_readers_on_a_record_with_hand_worked_answers():
    record = record_of_a_traced_run()
    assert read("gdn.step_share", record) == pytest.approx(40.0)
    assert read("moe.step_share", record) == pytest.approx(20.0)
    scan = family.gdn_scan_step_least_seconds(CONFIG, 8192, 2, True,
                                              "TPU v5 lite")
    assert read("gdn.scan_roofline", record) == \
        pytest.approx(100 * scan["seconds"] * 3 / 0.9)
    # the three traced periods' own rows, not the window's
    experts = sum(family.moe_experts_step_least_seconds(
        CONFIG, rows, True, "TPU v5 lite")["seconds"]
        for rows in (40960, 50000, 30000))
    assert read("moe.experts_roofline", record) == \
        pytest.approx(100 * experts / 0.3)
    # no spans in this process: the loop's own copy of the counters
    assert read("moe.load_max_over_mean", record) == pytest.approx(480 / 320)
    tokens_per_s = 16384 / 1.0
    assert read("train.mfu.family", record) == pytest.approx(
        100 * family.train_ops_per_token(CONFIG, 8192) * tokens_per_s
        / 197e12)
    assert read("train.tokens_per_s", record) == \
        pytest.approx(5 * 16384 / 4.9)


@pytest.mark.parametrize("name", ["gdn.scan_roofline", "gdn.step_share",
                                  "moe.experts_roofline", "moe.step_share",
                                  "moe.load_max_over_mean",
                                  "train.mfu.family"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    """A program without the scopes or the counters (the parent of the PR
    that added them), an untraced run, a rehearsal: no number, no raise."""
    bare = {"facts": {"platform": "cpu", "kind": "cpu", "count": 1},
            "window": {"steps": [], "profiler": [], "tokens_per_step": 1},
            "trace": {}}
    assert read(name, bare) is None
    traced = record_of_a_traced_run()
    traced["trace"] = {"busy_s": 1.0, "window_s": 1.0}     # no scopes
    del traced["window"]["counters"]
    if name != "train.mfu.family":
        assert read(name, traced) is None


def test_the_judgement_names_what_failed():
    from benchmark.apps import train_qwen3_next as app
    record = record_of_a_traced_run()
    record.update(param_dtype="float32")
    record["window"]["warmup_counters"] = [[40960, 0, 480, 320]] * 2
    record["checks"] = {
        "system_loss": 9.9, "reference_loss": 9.9005,
        "loss_tolerance": app.LOSS_TOLERANCE,
        **app.gradient_checks({"embed": 0.5 * app.GRAD_GAP_LIMIT,
                               "layer0.router": app.GRAD_GAP_LEAF_LIMIT,
                               "all": 0.5 * app.GRAD_GAP_LIMIT}),
        "warmup_losses": [9.9, 9.0], "first_update_fall": 0.9,
        "first_update_fall_expected": {"about": 0.9, "within": 0.1},
        "param_dtypes": ["float32"], "state_device_sets": [1]}
    assert app.judge(record) == []
    assert set(record["judged"]) == set(app.WHAT_EACH_CHECK_SAYS)
    record["window"]["counters"]["steps"][2][1] = 3       # three rows lost
    assert record["checks"]["grad_gap_worst_leaf"] == "layer0.router"
    record["checks"]["grad_gap_worst"] = 1.01 * app.GRAD_GAP_LEAF_LIMIT
    record["checks"]["param_dtypes"] = ["bfloat16"]
    why = app.judge(record)
    assert [w.split(":")[0] for w in why] == [
        "grad_gap_worst_leaf", "params_not_as_configured",
        "moe_rows_dropped"]


def test_the_cell_rehearses_clean_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(TMPDIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 37), "--seconds", "2", "--trace", "1",
         "--rehearse"], env=env, cwd=CHECKOUT, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == ""
    line = next(ln for ln in proc.stderr.splitlines()
                if "REHEARSAL result" in ln)
    result = json.loads(line.split("stdout): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["moe_rows_dropped"] == [0.0, 0]
    assert "moe.load_max_over_mean" in result["metrics"]
    assert "train.step x" in proc.stderr        # the program's own spans
    assert not os.listdir(tmp_path)             # nothing left behind


def sweep_and_expected():
    with open(os.path.join(BENCH, "testdata",
                           "qwen3_next_checks_sweep.json")) as f:
        sweep = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           MF.cell(CELL)["traffic"] + ".json")) as f:
        return sweep, json.load(f)["first_update_fall"]


def record_of_row(row, expected, **over):
    """A sweep's row as the record ``judge`` reads."""
    from benchmark.apps import train_qwen3_next as app
    names = list(app.COUNTERS)
    counters = [row["counters"][k] for k in names]
    checks = {
        "system_loss": row["system_loss"],
        "reference_loss": row["reference_loss"],
        "loss_tolerance": app.LOSS_TOLERANCE,
        **app.gradient_checks(row["grad_gaps"]),
        "first_update_fall": row["first_update_fall"],
        "first_update_fall_expected": expected,
        "warmup_losses": [row["system_loss"]],
        "param_dtypes": row["param_dtypes"], "state_device_sets": [1]}
    checks.update(over)
    return {"checks": checks,
            "window": {"steps": [], "warmup_counters": [counters],
                       "counters": {"names": names, "steps": []}},
            "param_dtype": "float32", "facts": {"count": 1}}


def failed_checks(why) -> set:
    return {w.split(":")[0] for w in why}


def test_the_limits_come_from_their_sweep():
    """The committed limits against the chip's readings they were set from:
    every sound seed is correct with room on every number."""
    from benchmark.apps import train_qwen3_next as app
    sweep, expected = sweep_and_expected()
    assert sweep["cell"] == CELL and not sweep["tiny"]
    assert sweep["device"]["platform"] == "tpu"
    rows = sweep["seeds"]
    assert len(rows) >= 12
    falls = [row["first_update_fall"] for row in rows]
    # ``about`` and ``within`` are set over these and 13 more sound runs
    # (the traffic file's doc): a sweep's seed has room twice over
    assert min(falls) < expected["about"] < max(falls)
    farthest = max(abs(fall - expected["about"]) for fall in falls)
    assert 2 * farthest <= expected["within"] <= expected["about"] / 3
    assert 2 * max(row["loss_gap"] for row in rows) <= app.LOSS_TOLERANCE
    for row in rows:
        record = record_of_row(row, expected)
        assert app.judge(record) == [], row["seed"]
        for name in ("grad_gap", "grad_gap_worst_leaf"):
            value, limit = record["judged"][name]
            assert ROOM * value <= limit, (row["seed"], name)
        assert not record["checks"]["grad_gap_worst_leaf"].endswith(
            app.TINY_LEAVES)


ROOM = 1.4           # a limit over the worst sound seed, and under a fault
FAULTS = ("topk_not_renormalised", "decay_left_out",
          "shared_expert_left_out", "output_gate_left_out",
          "half_of_the_batch_left_out", "bf16_parameters")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct_on_any_seed(fault):
    from benchmark.apps import train_qwen3_next as app
    sweep, expected = sweep_and_expected()
    assert set(sweep["faults"]) == set(FAULTS)
    planted = sweep["faults"][fault]
    assert len(planted) >= (12 if fault == "bf16_parameters" else 4)
    for row in planted:
        failed = failed_checks(app.judge(record_of_row(row, expected)))
        if fault == "bf16_parameters":
            # the same arithmetic on what the parameters round to: the
            # gradient is as close and the fall overlaps the sound runs';
            # the dtype is compared exactly
            assert "params_not_as_configured" in failed, row["seed"]
            assert "grad_gap" not in failed
        else:
            assert {"grad_gap", "grad_gap_worst_leaf"} <= failed, \
                (row["seed"], failed)
            assert row["grad_gap"] >= ROOM * app.GRAD_GAP_LIMIT


def test_a_state_handed_back_unchanged_is_not_correct():
    """Its moments are still zero and its loss has not fallen: 1 on both
    gradient gaps, a fall of 0. And the control, the reference's own
    gradient over int8 weights, against the limits."""
    from benchmark.apps import train_qwen3_next as app
    sweep, expected = sweep_and_expected()
    for row in sweep["seeds"]:
        unchanged = record_of_row(
            {**row, "grad_gaps": {k: 1.0 for k in row["grad_gaps"]},
             "first_update_fall": 0.0}, expected)
        assert failed_checks(app.judge(unchanged)) == {
            "grad_gap", "grad_gap_worst_leaf", "first_update_fall_off"}
    control = [row["control_int8"] for row in sweep["seeds"]
               if "control_int8" in row]
    assert len(control) >= 4
    for read in control:          # not correct, by the first of the two
        assert read["grad_gap"] >= ROOM * app.GRAD_GAP_LIMIT
