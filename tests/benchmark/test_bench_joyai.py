"""The ``joyai`` family's side of the benchmark: its arithmetic pinned to
the published model, its readers on a record with hand-worked answers, the
module's device time found in a small module's text, its limits against the
sweep they were read from, and the cell's rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, CHECKOUT, config

from benchmark import manifest as manifest_mod
from benchmark import ops_joyai as family
from benchmark import trace_scopes

CELL = "joyai-train-s8192-ep16share"
CONFIG = config("joyai-llm-flash-l5-e16-mtp1")
MF = manifest_mod.Manifest()
NEW_READERS = ["train.mfu.joyai", "mla.attend_roofline.train",
               "mla.step_share", "mtp.step_share", "moe.step_share.joyai",
               "moe.experts_roofline.joyai", "moe.count_max_over_mean"]


def test_parameter_counts_are_the_published_models():
    p = family.param_counts(CONFIG)
    assert family.mixer_matmul_params(CONFIG) == 26_345_472
    assert p["mixer"] == 26_347_520               # and its two latent norms
    assert p["expert"] == 4_718_592
    assert p["router"] == 524_544
    assert p["expert_layer"] == 107_092_224
    assert p["dense_layer"] == 70_391_808
    assert p["module"] == 115_486_976
    assert p["embed_and_head"] == 66_191_360
    assert p["total"] == 680_441_088               # the cut, 10.89 GB
    assert p["total"] * 16 / 1e9 == pytest.approx(10.89, abs=0.005)
    # a fifth expert layer leaves no room for a step
    assert (p["total"] + p["expert_layer"]) * 16 / 1e9 == \
        pytest.approx(12.60, abs=0.005)
    assert p["whole_model"] / 1e9 == pytest.approx(50.19, abs=0.005)
    assert str(p["total"]) in \
        CONFIG["arithmetic"]["parameters"].replace(",", "")


def test_the_program_holds_what_the_arithmetic_counts():
    from benchmark.apps import train_joyai as app
    from ray_tpu.models.transformer import transformer_num_params
    cfg = app.transformer_config(
        app.model_kwargs(CONFIG, 8192, "flash"), remat=True)
    assert transformer_num_params(cfg) == \
        family.param_counts(CONFIG)["total"]
    assert (cfg.held, cfg.num_experts, cfg.expert_top_k, cfg.mtp_layers,
            cfg.first_dense_layers, cfg.router_scoring) == \
        (16, 256, 8, 1, 1, "sigmoid")
    assert (cfg.mtp_loss_weight, cfg.router_bias_update_rate,
            cfg.routed_scaling_factor, cfg.norm_eps) == \
        (0.3, 0.001, 2.5, 1e-6)
    assert not cfg.index_topk and not cfg.window and not cfg.attn_gate


def test_operations_a_token_are_pinned():
    fwd = family.forward_ops_per_token(CONFIG, 8192)
    assert fwd["projections"] == 6 * 2 * 26_345_472
    assert fwd["scores"] == 6 * 8192 * 32 * 320
    assert fwd["heads"] == 2 * 2 * 2048 * 16160
    assert fwd["dense_ffn"] == 2 * 3 * 2048 * 7168
    # router + shared expert + 8 x 16 / 256 routed experts, five times, and
    # the module's W_eh
    assert fwd["experts"] == 5 * (1_048_576 + 9_437_184 + 0.5 * 9_437_184) \
        + 16_777_216
    assert fwd["total"] == pytest.approx(1.1327e9, rel=1e-4)
    assert family.train_ops_per_token(CONFIG, 8192) == 3 * fwd["total"]
    shares = {k: v / fwd["total"] for k, v in fwd.items()}
    assert shares["scores"] == pytest.approx(0.444, abs=0.001)
    assert shares["projections"] == pytest.approx(0.279, abs=0.001)
    assert shares["heads"] == pytest.approx(0.117, abs=0.001)


def test_least_times_follow_their_shapes():
    kind = "TPU v5 lite"
    attend = family.mla_attend_step_least_seconds(CONFIG, 8192, 2, kind)
    assert attend["layers"] == 6
    # 32 heads x (192 + 128) multiply-adds a causal pair, forward and
    # backward; the module's block over 8,191 positions
    pairs = 5 * 2 * 8192 * 8193 // 2 + 2 * 8191 * 8192 // 2
    assert attend["ops"] == 3 * 2 * pairs * 32 * 320
    assert attend["bound"] == "compute"
    assert attend["seconds"] == pytest.approx(attend["ops"] / 197e12)
    assert attend["seconds"] == pytest.approx(0.1256, abs=0.0005)
    few = family.moe_experts_step_least_seconds(CONFIG, 4_096, True, kind)
    many = family.moe_experts_step_least_seconds(CONFIG, 655_360, True, kind)
    assert few["bound"] == "memory" and many["bound"] == "compute"
    assert many["ops"] == 655_360 * 2 * 3 * 2048 * 768 * 4
    assert few["seconds"] < many["seconds"]


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"JoyAI-LLM-Flash"' in line)
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CONFIG["source"] == row["source_url"]
    assert (CONFIG["n_routed_experts"],
            CONFIG["n_routed_experts_published"]) == (16, 256)
    assert CONFIG["vocab_size"] * 8 == CONFIG["vocab_size_published"]
    assert CONFIG["num_nextn_predict_layers"] == 1
    assert "16 chips" in CONFIG["deployment"]
    assumed = " ".join(CONFIG["assumed"])
    for word in ("mtp_loss_weight 0.3", "router_bias_update_rate 0.001",
                 "rotate-half", "enorm", "normal(0, 0.01)"):
        assert word in assumed, word


HLO = '''
HloModule jit_step_fn

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step_fn)/jit(main)/transpose(jvp(rt.mtp))/rt.mla.dense/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(step_fn)/rt.mtp/rt.mtp.combine/dot_general"}
  %dot.3 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(step_fn)/checkpoint/rt.mla.dense/dot_general"}
  %ragged-dot-none.5 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply.6 = f32[8]{0} multiply(%ragged-dot-none.5, %a), metadata={op_name="jit(step_fn)/jvp(rt.mtp)/checkpoint/rt.moe.experts/mul"}
  ROOT %add.7 = f32[8]{0} add(%multiply.6, %fusion.1)
}
'''


def test_the_modules_instructions_are_found_under_its_outer_scope():
    from benchmark.apps import train_joyai as app
    inner = trace_scopes.scope_map(HLO)
    assert inner["fusion.1"] == "rt.mla.dense"
    assert inner["dot.2"] == "rt.mtp.combine"
    assert inner["dot.3"] == "rt.mla.dense"
    outer = trace_scopes.scope_map(app.module_scope_text(HLO))
    # the module's own: its attention, its combine, its experts and the
    # compiler's kernel they use; the main stack's attention is not
    assert outer == {name: app.MODULE_SCOPE for name in (
        "m", "fusion.1", "dot.2", "multiply.6", "ragged-dot-none.5")}


def record_of_a_traced_run() -> dict:
    from benchmark.apps import train_joyai as app
    names = list(app.COUNTERS)

    def row(rows_here, ratio):
        return [10.1, 10.1, rows_here, 0, 900, 512, ratio, 0.008]

    return {
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "window": {"steps": [[0.0, 0.9, 9.8], [1.0, 1.9, 9.8],
                             [2.0, 2.9, 9.8], [3.0, 3.9, 9.8],
                             [4.0, 4.9, 9.8]],
                   "profiler": [], "tokens_per_step": 16384,
                   "traced_steps": [1, 5],
                   "counters": {"names": names, "steps": [
                       row(99999, 7.5), row(40960, 7.0), row(50000, 6.5),
                       row(30000, 6.0), row(99999, 5.5)]}},
        "trace": {"busy_s": 3.0, "window_s": 3.0,
                  "scopes": {
                      "periods": 3,
                      "seconds": {"": 0.45, "rt.mla.dense": 1.5,
                                  "rt.mla.project": 0.3,
                                  "rt.moe.experts": 0.3,
                                  "rt.moe.route": 0.15,
                                  "rt.moe.shared": 0.15,
                                  "rt.mtp.combine": 0.15},
                      "mosaic_seconds": {"rt.moe.experts": 0.2,
                                         "rt.mla.dense": 1.2}},
                  "module_scopes": {
                      "periods": 3,
                      "seconds": {"": 2.4, "rt.mtp.module": 0.6}}}}


def read(name, record):
    return MF.reader(name)(record, MF.cell(CELL))


def test_the_new_readers_on_a_record_with_hand_worked_answers():
    record = record_of_a_traced_run()
    assert read("mla.step_share", record) == pytest.approx(60.0)
    assert read("mtp.step_share", record) == pytest.approx(20.0)
    assert read("moe.step_share.joyai", record) == pytest.approx(20.0)
    attend = family.mla_attend_step_least_seconds(CONFIG, 8192, 2,
                                                  "TPU v5 lite")
    assert read("mla.attend_roofline.train", record) == \
        pytest.approx(100 * attend["seconds"] * 3 / 1.5)
    # the three traced periods' own rows, not the window's
    experts = sum(family.moe_experts_step_least_seconds(
        CONFIG, rows, True, "TPU v5 lite")["seconds"]
        for rows in (40960, 50000, 30000))
    assert read("moe.experts_roofline.joyai", record) == \
        pytest.approx(100 * experts / 0.2)
    # no spans in this process: the loop's own copy of the counter
    assert read("moe.count_max_over_mean", record) == pytest.approx(6.5)
    tokens_per_s = 16384 / 1.0
    assert read("train.mfu.joyai", record) == pytest.approx(
        100 * family.train_ops_per_token(CONFIG, 8192) * tokens_per_s
        / 197e12)
    assert read("train.tokens_per_s", record) == \
        pytest.approx(5 * 16384 / 4.9)
    # no share of a roofline or of the peak reads over 100 %
    for name in NEW_READERS[:6]:
        assert 0 < read(name, record) < 100, name


@pytest.mark.parametrize("name", NEW_READERS)
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
    if name != "train.mfu.joyai":
        assert read(name, traced) is None
    # another family's record: its counters, none of this one's
    other = record_of_a_traced_run()
    other["window"]["counters"] = {
        "names": ["moe_rows_here", "moe_rows_dropped"],
        "steps": [[1, 0]] * 5}
    other["trace"]["scopes"]["seconds"] = {"": 1.0, "rt.gdn.scan": 1.0}
    other["trace"]["scopes"]["mosaic_seconds"] = {}
    del other["trace"]["module_scopes"]
    if name != "train.mfu.joyai":
        assert read(name, other) is None


def test_the_metric_files_say_what_is_information():
    for name in ("mla.step_share", "mtp.step_share", "moe.step_share.joyai"):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert f.read().count("Information, not a target") == 1
    cells = {m["name"]: m.get("workloads")
             for m in MF.data["per_layer"] if m["name"] in NEW_READERS}
    assert cells == {name: [CELL] for name in NEW_READERS}
    assert CELL in next(m for m in MF.data["end_to_end"]
                        if m["name"] == "train.tokens_per_s")["workloads"]


def checks_of(**over) -> dict:
    from benchmark.apps import train_joyai as app
    checks = {
        "system_loss": 13.1, "reference_loss": 13.1,
        "system_loss_main": 10.09, "reference_loss_main": 10.0905,
        "system_loss_mtp": 10.1, "reference_loss_mtp": 10.0995,
        "loss_tolerance": app.LOSS_TOLERANCE,
        **app.gradient_checks({"embed": 0.5 * app.GRAD_GAP_LIMIT,
                               "mtp.block.router": app.GRAD_GAP_LEAF_LIMIT,
                               "all": 0.5 * app.GRAD_GAP_LIMIT}),
        "router_bias_off": 0.5 * app.ROUTER_BIAS_OFF_LIMIT,
        "router_bias_step_off": 0.5 * app.ROUTER_BIAS_STEP_LIMIT,
        "router_bias_off_limit": app.ROUTER_BIAS_OFF_LIMIT,
        "router_bias_step_limit": app.ROUTER_BIAS_STEP_LIMIT,
        "warmup_losses": [13.1, 13.0], "first_update_fall": 0.1,
        "first_update_fall_expected": {"about": 0.1, "within": 0.05},
        "param_dtypes": ["float32"], "state_device_sets": [1]}
    checks.update(over)
    return checks


def test_the_judgement_names_what_failed():
    from benchmark.apps import train_joyai as app
    record = record_of_a_traced_run()
    record.update(param_dtype="float32")
    record["window"]["warmup_counters"] = \
        record["window"]["counters"]["steps"][:2]
    record["checks"] = checks_of()
    assert app.judge(record) == []
    assert set(record["judged"]) == set(app.WHAT_EACH_CHECK_SAYS)
    assert record["checks"]["grad_gap_worst_leaf"] == "mtp.block.router"
    dropped = app.COUNTERS.index("moe_rows_dropped")
    record["window"]["counters"]["steps"][2][dropped] = 3     # three lost
    record["checks"] = checks_of(
        system_loss_mtp=10.2, router_bias_off=1.0,
        router_bias_step_off=2 * app.ROUTER_BIAS_STEP_LIMIT)
    why = app.judge(record)
    assert [w.split(":")[0] for w in why] == [
        "mtp_loss_gap", "router_bias_off", "router_bias_step_off",
        "moe_rows_dropped"]


def test_the_cell_rehearses_clean_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(TMPDIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 47), "--seconds", "2", "--trace", "1",
         "--rehearse"], env=env, cwd=CHECKOUT, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == ""
    line = next(ln for ln in proc.stderr.splitlines()
                if "REHEARSAL result" in ln)
    result = json.loads(line.split("stdout): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["moe_rows_dropped"] == [0.0, 0]
    assert result["checks"]["mtp_loss_gap"][0] < 1e-3
    assert result["checks"]["router_bias_step_off"][0] < 1e-5
    assert "moe.count_max_over_mean" in result["metrics"]
    assert "train.step x" in proc.stderr        # the program's own spans
    assert "loss_mtp" in proc.stderr and "router_bias_abs_mean" in proc.stderr
    assert not os.listdir(tmp_path)             # nothing left behind


def sweep_and_expected():
    with open(os.path.join(BENCH, "testdata",
                           "joyai_checks_sweep.json")) as f:
        sweep = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           MF.cell(CELL)["traffic"] + ".json")) as f:
        return sweep, json.load(f)["first_update_fall"]


def record_of_row(row, expected, **over):
    """A sweep's row as the record ``judge`` reads."""
    from benchmark.apps import train_joyai as app
    names = list(app.COUNTERS)
    counters = [row["counters"][k] for k in names]
    checks = {
        "system_loss": row["system_loss"],
        "reference_loss": row["reference_loss"],
        "system_loss_main": row["system_loss_main"],
        "reference_loss_main": row["reference_loss_main"],
        "system_loss_mtp": row["system_loss_mtp"],
        "reference_loss_mtp": row["reference_loss_mtp"],
        "loss_tolerance": app.LOSS_TOLERANCE,
        **app.gradient_checks(row["grad_gaps"]),
        "router_bias_off": row["router_bias_off"],
        "router_bias_step_off": row["router_bias_step_off"],
        "router_bias_off_limit": app.ROUTER_BIAS_OFF_LIMIT,
        "router_bias_step_limit": app.ROUTER_BIAS_STEP_LIMIT,
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


ROOM = 1.2           # a limit over the worst sound seed, and under a fault


def test_the_limits_come_from_their_sweep():
    """The committed limits against the chip's readings they were set from:
    every sound seed is correct with room on every number."""
    from benchmark.apps import train_joyai as app
    sweep, expected = sweep_and_expected()
    assert sweep["cell"] == CELL and not sweep["tiny"]
    assert sweep["device"]["platform"] == "tpu"
    rows = sweep["seeds"]
    assert len(rows) >= 12
    falls = [row["first_update_fall"] for row in rows]
    assert min(falls) <= expected["about"] <= max(falls)
    farthest = max(abs(fall - expected["about"]) for fall in falls)
    # room twice over, and a state handed back unchanged (a fall of 0) out
    assert 2 * farthest <= expected["within"] < expected["about"]
    for name in ("loss_gap", "mtp_loss_gap"):
        assert 2 * max(row[name] for row in rows) <= app.LOSS_TOLERANCE
    for row in rows:
        record = record_of_row(row, expected)
        assert app.judge(record) == [], row["seed"]
        for name in ("grad_gap", "grad_gap_worst_leaf", "router_bias_off",
                     "router_bias_step_off"):
            value, limit = record["judged"][name]
            assert ROOM * value <= limit, (row["seed"], name)


# each planted fault and the checks that must refuse it on every seed
FAULTS = {
    "module_loss_left_out": {"grad_gap", "grad_gap_worst_leaf"},
    "targets_not_shifted": {"grad_gap", "grad_gap_worst_leaf"},
    "embedding_not_shifted": {"grad_gap", "grad_gap_worst_leaf"},
    "hnorm_left_out": {"grad_gap_worst_leaf"},
    "bias_update_left_out": {"router_bias_off"},
    "scaling_factor_one": {"grad_gap", "grad_gap_worst_leaf"},
    "k_rope_not_rotated": {"grad_gap", "grad_gap_worst_leaf"},
}
# planted, read, and told from a sound run by no number of the chip run
# (tests/test_joyai.py holds the program to both on the CPU): what this
# cell's learning rate lets the optimizer's weight decay move a bias by lies
# under float32's rounding of the bias; and with the seeded norm scales of 1
# an RMSNorm of an RMSNorm'd vector is that vector, so h taken after the
# final norm is the same forward pass and moves the gradient of one leaf,
# ``final_norm``, tenfold under the routers' own gaps
NOT_SEPARATED = {"bias_inside_the_weights", "h_after_the_final_norm"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct_on_any_seed(fault):
    from benchmark.apps import train_joyai as app
    sweep, expected = sweep_and_expected()
    assert set(sweep["faults"]) == set(FAULTS) | NOT_SEPARATED
    planted = sweep["faults"][fault]
    assert len(planted) >= 3
    for row in planted:
        failed = failed_checks(app.judge(record_of_row(row, expected)))
        assert FAULTS[fault] <= failed, (row["seed"], failed)
        judged = app.judged(record_of_row(row, expected))
        for name in FAULTS[fault]:
            value, limit = judged[name]
            assert value >= ROOM * limit, (row["seed"], name, value)


def test_what_no_number_of_a_chip_run_separates_is_said():
    from benchmark.apps import train_joyai as app
    sweep, expected = sweep_and_expected()
    for fault in NOT_SEPARATED:
        for row in sweep["faults"][fault]:
            assert app.judge(record_of_row(row, expected)) == []
    # the one leaf that h after the final norm does move
    sound = [row["grad_gaps"]["final_norm"] for row in sweep["seeds"]]
    moved = [row["grad_gaps"]["final_norm"]
             for row in sweep["faults"]["h_after_the_final_norm"]]
    assert 5 * max(sound) < min(moved) < 0.5 * app.GRAD_GAP_LEAF_LIMIT


def test_a_state_handed_back_unchanged_is_not_correct():
    """Its moments are still zero, its biases have not moved and its loss
    has not fallen: 1 on both gradient gaps and on the biases, a fall of 0.
    And the control, the reference's own readings over int8 weights,
    against the limits."""
    from benchmark.apps import train_joyai as app
    sweep, expected = sweep_and_expected()
    for row in sweep["seeds"]:
        unchanged = record_of_row(
            {**row, "grad_gaps": {k: 1.0 for k in row["grad_gaps"]},
             "router_bias_off": 1.0, "router_bias_step_off": 0.0,
             "first_update_fall": 0.0}, expected)
        assert failed_checks(app.judge(unchanged)) == {
            "grad_gap", "grad_gap_worst_leaf", "router_bias_off",
            "first_update_fall_off"}
    control = [row["control_int8"] for row in sweep["seeds"]
               if "control_int8" in row]
    assert len(control) >= 3
    for got in control:          # not correct, by the first of the two
        assert got["grad_gap"] >= ROOM * app.GRAD_GAP_LIMIT
        assert got["grad_gap_worst"] >= app.GRAD_GAP_LEAF_LIMIT
