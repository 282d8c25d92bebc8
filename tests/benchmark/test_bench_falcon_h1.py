"""The ``falcon_h1`` family's side of the benchmark: its arithmetic pinned to
the published model and to ISSUE 55's own sums, the configuration against
the catalog's keys, its files found by the manifest, its readers on records
with hand-worked answers, its limits against the sweep they were read from,
the control through the plain reference at a toy size, and the cell's
rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, CHECKOUT, config, manifest_data

from benchmark import manifest as manifest_mod
from benchmark import ops_falcon_h1 as family
from benchmark.apps import serve_falcon_h1 as app
from benchmark.testdata.sweep_falcon_h1 import FAULTS

CELL = "falconh1-serve-closed64-p128-n384"
NAME = "falcon-h1-34b-l9"
OLMO = "olmohybrid-serve-closed48-p128-n384"
CONFIG = config(NAME)
MF = manifest_mod.Manifest()
TRAFFIC = MF.cell(CELL)["traffic_data"]
# The published config.json, as the guide's catalog holds it.
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}
NEW_METRICS = ("generate_roofline.falcon_h1", "ssd.step_roofline",
               "ssd.call_share", "ssd.cache_share", "falcon_h1.call_s",
               "falcon_h1.prefill_share", "falcon_h1.rows_share")
V5E = "TPU v5 lite"


# --- the arithmetic -------------------------------------------------------------

def test_parameter_counts_are_the_issues_sums():
    p = family.param_counts(CONFIG)
    assert p["ssm_mixer"] == 68_351_072 == (
        5120 * 9_248 + 5_120 * 4 + 5_120 + 3 * 32 + 4_096 + 4_096 * 5120)
    assert p["attention"] == 31_457_280 == \
        2 * 5120 * 2_560 + 2 * 5120 * 512
    assert p["mlp"] == 330_301_440 == 3 * 5120 * 21_504
    assert p["layer"] == 430_120_032
    assert p["embed"] + p["head"] == 334_233_600 == 2 * 32_640 * 5_120
    assert p["total"] == 4_205_319_008 == \
        9 * 430_120_032 + 334_233_600 + 5_120
    whole = family.param_counts({**CONFIG, "num_hidden_layers": 72,
                                 "vocab_size": 261_120})
    assert whole["total"] == 72 * 430_120_032 + 2 * 261_120 * 5120 + 5120
    # the head's share of what a decode step reads: the published model's
    assert whole["head_matmul"] / whole["total"] == \
        pytest.approx(0.040, abs=0.002)


def test_the_program_holds_what_the_arithmetic_counts():
    from ray_tpu.models.transformer import transformer_num_params
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 512, "auto"),
                                 remat=False)
    assert (cfg.kinds, cfg.periods, cfg.head_dim, cfg.kv_heads) == (
        ("parallel",), 9, 128, 4)
    assert (cfg.linear_transition, cfg.linear_key_heads,
            cfg.linear_value_heads, cfg.linear_key_dim,
            cfg.linear_value_dim, cfg.norm_eps, cfg.rope_theta) == (
        "ssd", 2, 32, 256, 128, 1e-5, 1e11)
    assert transformer_num_params(cfg) == 4_205_319_008 == \
        family.param_counts(CONFIG)["total"]


def test_the_programs_cache_holds_what_the_arithmetic_counts():
    import importlib

    import numpy as np
    gen = importlib.import_module("ray_tpu.models.generate")
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 512, "auto"),
                                 remat=False)
    shapes = gen.cache_shapes(cfg, 64, 512)
    want = family.cache_bytes(CONFIG, 64, 512)
    assert shapes["state"] == (9, 64, 32, 256, 128)     # 128 lanes: no pack
    assert shapes["tail"] == (9, 3, 64, 5_120)
    assert shapes["k"] == shapes["v"] == (9, 64, 512, 4, 128)
    assert 4 * int(np.prod(shapes["state"])) == want["state"] \
        == 2_415_919_104 == 64 * 9 * 4_194_304
    assert 2 * int(np.prod(shapes["tail"])) == want["tail"] == 17_694_720
    assert 2 * 2 * int(np.prod(shapes["k"])) == want["kv"] == 603_979_776 \
        == 64 * 512 * 18_432
    with gen.call_span(cfg, 64, 128, 384) as sp:
        pass
    assert sp.attrs["cache_bytes"] == want["total"] == 3_037_593_600
    assert sp.attrs["mixers_a_layer"] == 2
    assert (sp.attrs["linear_slots"], sp.attrs["full_slots"]) == (9, 9)


def test_bytes_a_row_and_a_step_are_pinned():
    assert family.state_bytes_a_row(CONFIG) == 4_194_304 == 32 * 256 * 128 * 4
    assert family.tail_bytes_a_row(CONFIG) == 30_720
    assert family.kv_bytes_a_position(CONFIG) == 18_432
    assert family.scan_ops_a_position(CONFIG) == 5 * 32 * 256 * 128
    a_row = 2 * 4_194_304 + 2 * 30_720 + 5_120 * 2 + 32 * 4 + 4_096 * 2
    assert family.ssd_step_bytes(CONFIG, 64) == 64 * a_row + 5_120 * 5 * 2
    fwd = family.forward_ops_per_token(CONFIG, 128)
    # ISSUE 55's: the scan under 1 % of a prompt token's operations
    assert fwd["scan"] == 9 * 5_242_880
    assert fwd["scan"] / fwd["total"] < 0.01
    assert fwd["attention"] == 2 * 128 * 20 * 128 * 9
    assert fwd["total"] == sum(fwd[k] for k in (
        "layers", "conv", "scan", "attention", "head"))


def test_the_calls_least_time_follows_its_shapes():
    least = family.generate_least_seconds(CONFIG, 64, 128, 384, "bfloat16",
                                          V5E)
    # weights once a step: every matrix, the taps and the bias, not the
    # embedding (a lookup) nor the norms: the issue's 8.08 GB
    assert least["weight_bytes_a_step"] == 2 * (
        9 * (68_321_280 + 31_457_280 + 330_301_440 + 25_600)
        + 167_116_800) == 8_076_134_400
    assert least["state_bytes_a_step"] == 2 * (2_415_919_104 + 17_694_720)
    assert least["cache_bytes"] == 3_037_593_600
    assert least["seconds"] == pytest.approx(
        least["prefill_seconds"] + least["decode_seconds"])
    assert least["bound"] == "prefill compute, decode memory"
    assert least["ssd_seconds"] == pytest.approx(
        384 * 9 * family.ssd_step_bytes(CONFIG, 64) / 819e9)
    # the issue's step at the mean position: 13.3 GB, 16.2 ms
    assert least["decode_seconds"] / 384 == pytest.approx(0.0162, rel=0.02)
    # the state and its step's operands: over a third of a step; with the
    # mixer's 1.23 GB of projections the issue's 45 %
    assert 0.35 < least["ssd_seconds"] / least["decode_seconds"] < 0.38
    mixer = least["ssd_bytes_a_step"] + 2 * 9 * 68_321_280
    assert mixer / (least["decode_bytes"] / 384) == \
        pytest.approx(0.45, abs=0.02)
    twice = family.generate_least_seconds(CONFIG, 128, 128, 384, "bfloat16",
                                          V5E)
    assert twice["state_bytes_a_step"] == 2 * least["state_bytes_a_step"]
    assert twice["weight_bytes_a_step"] == least["weight_bytes_a_step"]
    from benchmark import ops
    with pytest.raises(ops.UnknownDevice):
        family.generate_least_seconds(CONFIG, 64, 128, 384, "bfloat16", "cpu")


# --- the files --------------------------------------------------------------------

def test_the_configuration_keeps_every_published_key():
    for key, value in PUBLISHED.items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 9 == 72 // 8
    assert CONFIG["vocab_size"] == 32_640 == 261_120 // 8
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["vocab_size_published"]) == (72, 261_120)
    assert list(CONFIG["reduced"]) == ["num_hidden_layers", "vocab_size"]
    entry = next(c for c in manifest_data()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/" \
        "config.json"
    assert CONFIG["torch_dtype"] == CONFIG["param_dtype"] == "bfloat16"
    assert CONFIG["family"] == "falcon_h1"
    assert "4,205,319,008" in CONFIG["arithmetic"]["parameters"]
    assert "eight pipeline stages" in CONFIG["deployment"]
    recalled = " ".join(CONFIG["assumed"])
    for point in ("no network", "[z | x | B | C | dt]", "mamba_use_mlp",
                  "no clamp", "rotate-half", "not fetched", "folds",
                  "U(-0.5, 0.5)", "262,144"):
        assert point in recalled, point


def test_the_cells_files_are_found_by_the_manifest():
    cell = MF.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == \
        (1, "serve-closed64-p128-n384", NAME)
    assert cell["config_data"] == CONFIG and cell["traffic_data"] == TRAFFIC
    want = {"app": "serve_falcon_h1", "clients": 64, "prompt_tokens": 128,
            "new_tokens": 384, "max_batch_size": 64,
            "batch_wait_timeout_s": 0.5, "max_ongoing_requests": 64,
            "request_timeout_s": 60.0}
    assert {k: TRAFFIC[k] for k in want} == want
    assert set(TRAFFIC["rehearse"]) <= set(want)
    for path in ("apps/serve_falcon_h1.py", "ops_falcon_h1.py",
                 "reference/falcon_h1.py", "rehearse/falcon_h1.json",
                 "testdata/sweep_falcon_h1.py",
                 "testdata/falcon_h1_checks_sweep.json"):
        assert os.path.isfile(os.path.join(BENCH, path)), path
    from benchmark.apps import lm
    assert lm.reference_module(CONFIG).__name__ == \
        "benchmark.reference.falcon_h1"
    toy = lm.effective_config(CONFIG, True)
    assert toy["mamba_d_ssm"] == toy["mamba_n_heads"] * toy["mamba_d_head"]
    assert toy["ssm_multipliers"] == CONFIG["ssm_multipliers"]


def test_the_manifest_gained_entries_and_three_appended_names():
    """Appended entries only: the configuration, the cell, its name at the
    end of the three serving metrics' lists, and the seven layer metrics,
    each with a file pair that says what the manifest says. Nine cells, one
    of four chips."""
    data = manifest_data()
    assert data["workloads"][-1]["name"] == CELL
    assert data["configs"][-1]["name"] == NAME
    assert len(data["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    for m in data["end_to_end"]:
        if m["name"].startswith("serve."):
            assert m["workloads"][-1] == CELL, m["name"]
        elif "workloads" in m:
            assert CELL not in m["workloads"]
    reported = {m["name"] for m in MF.metrics("end_to_end", CELL)}
    assert reported == {"serve.tokens_per_s", "serve.request_p95_s",
                        "serve.ttft_p95_s", "setup_s"}
    assert [m["name"] for m in data["per_layer"][-7:]] == list(NEW_METRICS)
    for m in data["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] in reported
            with open(os.path.join(BENCH, "metrics",
                                   m["name"] + ".json")) as f:
                beside = json.load(f)
            assert {k: beside[k] for k in m} == m
            assert len(beside["definition"]) > 80
        else:
            assert CELL not in m.get("workloads", ())
    assert [m["name"] for m in MF.metrics("per_layer", CELL)
            if m["name"] in NEW_METRICS] == list(NEW_METRICS)


OLMO_METRICS = ("generate_roofline.olmo_hybrid", "gdn.step_roofline",
                "gdn.call_share", "state.cache_share", "olmo_hybrid.call_s",
                "olmo_hybrid.prefill_share")


def test_the_cells_before_this_one_are_still_on_their_lists():
    """Every assertion of the two olmo_hybrid tests that
    ``tests/conftest.py`` marks ``xfail(strict)`` (each holds its own cell,
    or dots3's, to the LAST place of a list that this cell was appended
    to), with the places counted one before this cell's: only the ``[-1]``
    is lost."""
    data = manifest_data()
    dots3 = "dots3-serve-closed2-p32k-n128"
    # test_the_manifest_gained_entries_and_two_appended_names
    assert [w["name"] for w in data["workloads"]][-2] == OLMO
    assert data["configs"][-2]["name"] == "olmo-hybrid-7b-l20"
    before = ["mistral7b-serve-closed32", "ouro2.6b-serve-closed16", dots3]
    for m in data["end_to_end"]:
        if m["name"] == "serve.tokens_per_s":
            assert m["workloads"] == before + [CELL]
        elif m["name"].startswith("serve."):
            assert m["workloads"] == before + [OLMO, CELL]
        elif "workloads" in m:
            assert OLMO not in m["workloads"]
    reported = {m["name"] for m in MF.metrics("end_to_end", OLMO)}
    assert reported == {"serve.request_p95_s", "serve.ttft_p95_s", "setup_s"}
    assert {m["moves"] for m in data["per_layer"]
            if m["name"] in OLMO_METRICS} <= reported
    assert [m["name"] for m in data["per_layer"][-13:-7]] == \
        list(OLMO_METRICS)
    for m in data["per_layer"]:
        if m["name"] in OLMO_METRICS:
            assert m["workloads"] == [OLMO]
            with open(os.path.join(BENCH, "metrics",
                                   m["name"] + ".json")) as f:
                beside = json.load(f)
            assert {k: beside[k] for k in m} == m
            assert len(beside["definition"]) > 80
        else:
            assert OLMO not in m.get("workloads", ())
    assert [m["name"] for m in MF.metrics("per_layer", OLMO)
            if m["name"] in OLMO_METRICS] == list(OLMO_METRICS)
    # test_dots3s_traffic_is_still_its_issues
    cell = MF.cell(dots3)
    want = {"app": "serve_dots3", "clients": 2, "prompt_tokens": 32768,
            "new_tokens": 128, "max_batch_size": 2,
            "batch_wait_timeout_s": 0.1, "max_ongoing_requests": 2,
            "request_timeout_s": 60.0}
    assert {k: cell["traffic_data"][k] for k in want} == want
    assert (cell["chips"], cell["traffic"]) == \
        (1, "serve-closed2-p32768-n128")


# --- the readers ------------------------------------------------------------------

def record_of_a_traced_run() -> dict:
    seconds = {"": 0.4, "rt.generate.prefill": 0.2,
               "rt.generate.decode": 9.0, "rt.ssd.step": 4.0,
               "rt.ssd.conv": 0.5, "rt.ssd.proj": 2.5, "rt.ssd.scan": 0.1,
               "rt.loop.cache": 1.0}
    return {
        "facts": {"platform": "tpu", "kind": V5E, "count": 1},
        "batches": [{"start": 0.0, "end": 8.4}],
        "trace": {"busy_s": 18.0, "window_s": 18.2, "module_s": 17.0,
                  "periods": 2,
                  "scopes": {"periods": 2, "seconds": seconds},
                  "phases": {"periods": 2, "seconds": {
                      "rt.generate.prefill": 0.9,
                      "rt.generate.decode": 17.1}},
                  "decode_scopes": {"periods": 2, "seconds": {
                      "rt.ssd.step": 4.0, "rt.ssd.conv": 0.4,
                      "rt.ssd.proj": 2.4, "rt.loop.cache": 1.0}}}}


def read(name, record):
    return MF.reader(name)(record, MF.cell(CELL))


def test_the_new_readers_on_a_record_with_hand_worked_answers():
    record = record_of_a_traced_run()
    least = family.generate_least_seconds(CONFIG, 64, 128, 384, "bfloat16",
                                          V5E)
    assert read("generate_roofline.falcon_h1", record) == \
        pytest.approx(100 * least["seconds"] * 2 / 17.0)
    # the token loop's recurrence and convolution, not the prefill's
    assert read("ssd.step_roofline", record) == \
        pytest.approx(100 * least["ssd_seconds"] * 2 / 4.4)
    assert read("ssd.call_share", record) == \
        pytest.approx(100 * (4.0 + 0.5 + 2.5 + 0.1) / 18.0)
    assert read("falcon_h1.prefill_share", record) == pytest.approx(5.0)


def test_the_span_readers_on_a_hand_made_session(monkeypatch):
    from benchmark import spans as spans_mod
    attrs = {"rows": 64, "cache_bytes": 1000, "cache_bytes_state": 780,
             "cache_bytes_tail": 20, "cache_bytes_kv": 200}
    calls = [{"kind": "generate.call", "ts": 10.0 + 9 * i, "value": v,
              "attrs": dict(attrs)}
             for i, v in enumerate([8.3, 8.4, 8.6, 99.0])]
    calls[3]["ts"] = 5.0                      # before the window: warm-up
    flushes = [{"kind": "serve.batch.flush", "ts": 10.0 + 9 * i, "value": 0.0,
                "node_id": "n", "pid": 1,
                "attrs": {"rows": rows, "max_batch_size": 64}}
               for i, rows in enumerate([64, 64, 32])]
    monkeypatch.setattr(spans_mod, "load",
                        lambda record, cell: calls + flushes)
    monkeypatch.setattr(spans_mod, "in_window", lambda record, spans:
                        [s for s in spans if s["ts"] >= 10.0])
    assert read("falcon_h1.call_s", {}) == pytest.approx(8.4)
    assert read("ssd.cache_share", {}) == pytest.approx(80.0)
    assert read("falcon_h1.rows_share", {}) == \
        pytest.approx(100 * 160 / 192)
    for name in ("falcon_h1.call_s", "ssd.cache_share"):
        assert MF.reader_module(name).NEEDS == ("generate.call",)
    assert MF.reader_module("falcon_h1.rows_share").NEEDS == \
        ("serve.batch.flush",)
    assert set(MF.span_needs(CELL)) >= {"generate.call",
                                        "serve.batch.flush"}
    # the parent's spans carry no such counter: no number, no raise
    for c in calls:
        del c["attrs"]["cache_bytes_state"]
    assert read("ssd.cache_share", {}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_with_nothing_to_read_returns_none(name, monkeypatch):
    """A program without the scope or the span (the parent of the PR that
    added them), an untraced run, a rehearsal: no number, no raise."""
    from benchmark import spans as spans_mod
    monkeypatch.setattr(spans_mod, "load", lambda record, cell: [])
    bare = {"facts": {"platform": "cpu", "kind": "cpu", "count": 1},
            "batches": [], "trace": {}}
    assert read(name, bare) is None
    traced = record_of_a_traced_run()
    for reduction in ("scopes", "phases", "decode_scopes"):
        traced["trace"].pop(reduction)        # the parent's program
    traced["facts"]["kind"] = "cpu"           # and a rehearsal's device
    assert read(name, traced) is None


# --- the judgement ----------------------------------------------------------------

def record_of(checks: dict, **over) -> dict:
    tokens = list(range(TRAFFIC["new_tokens"]))
    row = {"ok": True, "rid": 0, "extra": {"tokens": tokens}}
    checks = dict(
        {"rms_over_std": 0.2, "floor_rms_over_std": 0.15,
         "token_deficit_over_std": 0.5,
         "rms_norm_eps": {"published": 1e-5, "program": 1e-5},
         "param_dtypes": ["bfloat16"], "compute_dtype": "bfloat16",
         "cache_dtypes": {"state": "float32", "tail": "bfloat16"}},
        **checks)
    checks.update(over)
    return {"checks": checks, "warmup": [row, dict(row, rid=1)],
            "window": {"rows": [dict(row, rid=2)]}}


def failed_checks(why: list) -> set:
    return {reason.split(":", 1)[0] for reason in why}


SOUND = {name: 0.6 * limit for name, limit in app.LIMITS.items()}


def test_the_judgement_names_what_failed():
    record = record_of(SOUND)
    assert app.judge(record, CONFIG, TRAFFIC) == []
    assert set(record["judged"]) == set(app.WHAT_EACH_CHECK_SAYS)
    assert set(app.LIMITS) < set(record["judged"])
    for name, limit in app.LIMITS.items():
        assert record["judged"][name] == [SOUND[name], limit]
        why = app.judge(record_of(SOUND, **{name: 1.01 * limit}), CONFIG,
                        TRAFFIC)
        assert failed_checks(why) == {name}
        assert f"{1.01 * limit:.6g}" in why[0]
    for over, names in (
            (dict(kv_over_floor=float("nan")), {"kv_over_floor"}),
            (dict(cache_dtypes={"state": "bfloat16"}),
             {"state_not_float32"}),
            (dict(token_deficit_over_std=9.0), set()),
            (dict(compute_dtype="float32"),
             {"compute_dtype_not_as_configured"}),
            (dict(param_dtypes=["float32"]), {"weights_not_as_configured"}),
            (dict(rms_norm_eps={"published": 1e-5, "program": 1e-4}),
             {"eps_off_known"})):
        why = app.judge(record_of(SOUND, **over), CONFIG, TRAFFIC)
        assert failed_checks(why) == names, (over, why)


def test_the_judgement_opens_no_backend():
    """``judge`` runs in the benchmark's own process, beside a replica that
    owns the chip: a process whose only platform cannot start judges a
    record."""
    code = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "sys.path.insert(0, %r)\n"
        "import test_bench_falcon_h1 as t\n"
        "print(t.app.judge(t.record_of(t.SOUND), t.CONFIG, t.TRAFFIC))\n"
        % (CHECKOUT, os.path.dirname(os.path.abspath(__file__))))
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
        linear_beta_scale: float = 1.0
    monkeypatch.setattr(models, "TransformerConfig", ParentsConfig)
    with pytest.raises(ValueError, match=r"no \['linear_transition'\].*"
                       "state-space"):
        app.transformer_config(app.model_kwargs(CONFIG, 512, "auto"),
                               remat=False)


def test_a_configuration_the_block_does_not_cover_is_refused_in_words():
    with pytest.raises(ValueError, match="attention in every layer"):
        app.model_kwargs({**CONFIG, "attn_layer_indices": [0, 4]}, 512,
                         "auto")
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        app.model_kwargs({**CONFIG, "mamba_d_ssm": 8192}, 512, "auto")
    with pytest.raises(ValueError, match="gates before"):
        app.model_kwargs({**CONFIG, "mamba_norm_before_gate": True}, 512,
                         "auto")


# --- the control and the seeded weights, at a toy size ---------------------------

def toy_program():
    from benchmark.apps import lm
    toy = lm.effective_config(CONFIG, True)
    cfg = app.transformer_config(app.model_kwargs(toy, 32, "reference"),
                                 remat=False)
    return toy, cfg


def test_int8_weights_through_the_reference_read_over_the_floor():
    """The sweep's control on the CPU at the rehearsal's sizes: the rounded
    reference is the floor (1 by construction), the sound program near it,
    int8 weights over it in logits, tails, keys and values (a state of 16
    positions over 3 layers reads what its operands' rounding does)."""
    import jax.numpy as jnp

    from benchmark.apps import lm
    toy, cfg = toy_program()
    params = app.seeded_params(cfg, toy, 7)
    tokens = jnp.asarray(app.check_tokens(7, cfg.vocab_size, 16 + 7))
    weights = app.reference_weights(cfg, toy, 7)
    reference = lm.reference_module(toy)
    full = app.reference_pass(weights, toy, tokens, 16, 1e-5)
    rounded = app.reference_pass(weights, toy, tokens, 16, 1e-5,
                                 jnp.bfloat16)
    floor = app.errors(rounded, full, toy, 16)
    assert len(floor["state"]) == len(floor["tail"]) == 2 * 3
    assert len(floor["kv"]) == 3 * 2 * 2
    assert len(floor["logits"]) == 2 * 8
    sound = app.over_floors(
        app.errors(app.Program(cfg, 16, 32).run(params, tokens), full, toy,
                   16), floor, toy)
    control = app.over_floors(app.errors(app.reference_pass(
        reference.int8_weights(weights), toy, tokens, 16, 1e-5,
        jnp.bfloat16), full, toy, 16), floor, toy)
    for name in ("rms_over_floor", "tail_over_floor", "kv_over_floor"):
        assert 0.7 < sound[name] < 1.5, (name, sound)
        assert control[name] > 1.6 > sound[name], (name, control, sound)
    assert 0.7 < sound["state_over_floor"] < 1.5


def test_the_seeded_weights_are_mamba2s_and_the_multipliers_are_folded():
    """``seeded_params`` is ``published_params`` with every multiplier
    folded in: the step sizes log-uniform in [DT_MIN, DT_MAX], A in [1,
    16], D = 1, the convolution's taps and bias within 1 / sqrt(K); the
    app's ``reference_weights`` is the published tree, drawn again from the
    seed and not read back from the folded one."""
    import jax
    import numpy as np

    toy, cfg = toy_program()
    published = jax.jit(lambda key: app.published_params(cfg, key))(
        app.seed_key(11))
    folded = app.seeded_params(cfg, toy, 11)
    ssm = published["layers"][0]["ssm"]
    dt = np.log1p(np.exp(np.asarray(ssm["dt_bias"], np.float64)))
    assert app.DT_MIN * 0.98 <= dt.min() and dt.max() <= app.DT_MAX * 1.02
    a = np.exp(np.asarray(ssm["A_log"], np.float64))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert np.all(np.asarray(ssm["D"], np.float32) == 1.0)
    for name in ("conv", "conv_bias"):
        w = np.abs(np.asarray(ssm[name], np.float32))
        assert 0.3 < w.max() <= 0.5, name
    m = app.multipliers(toy)
    got, was = folded["layers"][0], published["layers"][0]
    f32 = lambda x: np.asarray(x, np.float32)
    assert np.allclose(f32(got["attn"]["wk"]),
                       f32(was["attn"]["wk"]) * m["key"], rtol=1e-2)
    assert np.allclose(f32(folded["embed"]),
                       f32(published["embed"]) * m["embedding"], rtol=1e-2)
    assert np.array_equal(f32(got["mlp"]["w3"]), f32(was["mlp"]["w3"]))
    assert np.array_equal(f32(got["ssm"]["conv"]), f32(was["ssm"]["conv"]))
    drawn = app.reference_weights(cfg, toy, 11)
    for i in (0, cfg.n_layers - 1):
        layer = drawn.layer(i)
        assert np.array_equal(f32(layer["w_in"]),
                              f32(was["ssm"]["in_proj"][i]))
        assert np.array_equal(f32(layer["w2"]), f32(was["mlp"]["w2"][i]))
        assert np.array_equal(
            f32(layer["wk"]), f32(was["attn"]["wk"][i]).reshape(
                cfg.d_model, -1))
    assert np.array_equal(f32(drawn.lm_head), f32(published["lm_head"]))
    # another seed is another tree
    other = app.reference_weights(cfg, toy, 12).layer(0)["w2"]
    assert not np.array_equal(f32(other), f32(was["mlp"]["w2"][0]))


def test_the_served_call_that_is_checked_is_a_full_one_of_the_window():
    """``served_call``: a full call of the window whose first and last
    rows' replies came back whole, drawn from the seed; the warm-up's
    where the window has none; the first row of any call where none is
    full; and in words where nothing came back."""
    call = lambda rids, padded=4: {"rids": rids, "rows": len(rids),
                                   "padded_rows": padded}
    warmup = [call([0, 1, 2, 3])]
    window = [call([4, 5, 6]), call([7, 8, 9, 10]), call([11, 12, 13, 14])]
    whole = set(range(15)) - {14}
    assert app.kept_rows(4) == (0, 3) and app.kept_rows(1) == (0,)
    for seed in range(8):       # the one full call with both ends whole
        assert app.served_call([window, warmup], whole, seed) == \
            (7, [0, 1], [7, 10])
    picked = {app.served_call([window, warmup], set(range(15)), seed)[0]
              for seed in range(16)}
    assert picked == {7, 11}
    assert app.served_call([window[:1], warmup], whole, 0) == \
        (0, [0, 1], [0, 3])
    assert app.served_call([window[:1], []], whole, 0) == (4, [0], [4])
    with pytest.raises(ValueError, match="came back whole"):
        app.served_call([window, warmup], set(), 0)


def test_the_timed_program_hands_back_the_rows_its_check_reads():
    """``generate_and_keep`` is ``generate_with_stats`` and the first and
    last row of the cache its last step left: the same tokens, each of the
    cache's four arrays cut along its batch dimension (the tail's is its
    third), and ``served_numbers`` of a sound call near the floor where
    int8 weights through the reference read over it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.apps import lm
    from ray_tpu.models import generate_and_cache, generate_with_stats
    toy, cfg = toy_program()
    params = app.seeded_params(cfg, toy, 7)
    prompts = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)
    tokens, _, kept = jax.jit(lambda p, t: app.generate_and_keep(
        p, t, cfg, 8))(params, prompts)
    want, _ = generate_with_stats(params, jnp.asarray(prompts), cfg,
                                  max_new_tokens=8)
    assert np.array_equal(np.asarray(tokens), np.asarray(want))
    _, _, cache = generate_and_cache(params, jnp.asarray(prompts), cfg,
                                     max_new_tokens=8)
    assert set(kept) == set(cache) == {"k", "v", "state", "tail"}
    for name, a in cache.items():
        rows = np.asarray(a)[:, :, [0, 3]] if name == "tail" \
            else np.asarray(a)[:, [0, 3]]
        assert np.array_equal(np.asarray(kept[name]), rows), name
    weights = app.reference_weights(cfg, toy, 7)
    tokens = np.asarray(tokens)
    pairs = [(prompts[r].tolist(), tokens[r].tolist()) for r in (0, 3)]
    passes = app.served_passes(weights, toy, pairs, 16, 1e-5)
    assert passes["exact"][0].shape == (2, 8, cfg.vocab_size)
    served = [tokens[r].tolist() for r in (0, 3)]
    sound = app.served_numbers(passes, served, app.cache_view(kept, 24),
                               toy, 16)
    reference = lm.reference_module(toy)
    logits, cache = reference.forward_and_cache(
        reference.int8_weights(weights), passes["fed"], toy, eps=1e-5,
        dtype=jnp.bfloat16)
    control = app.served_numbers(
        passes, np.asarray(jnp.argmax(logits[:, 15:-1], -1)).tolist(),
        {k: np.asarray(v) for k, v in cache.items()}, toy, 16)
    assert sound["token_deficit_over_floor"] < 5
    for name in ("served_tail_over_floor", "served_kv_over_floor"):
        assert 0.7 < sound[name] < 1.5 < 1.6 < control[name], (
            name, sound, control)
    assert 0.7 < sound["served_state_over_floor"] < 1.5
    # the rows are the call's own: the other row's cache is another's
    swapped = app.cache_view(app.rows_of(kept, [1, 0]), 24)
    assert app.served_numbers(passes, served, swapped, toy,
                              16)["served_state_over_floor"] > 10


def test_handing_back_two_rows_keeps_one_state_stack_on_v5e(monkeypatch):
    """The timed program compiled for a described v5e at the cell's widths
    and 64 rows x (128 + 384), two layers: ``generate_and_keep`` takes no
    more temporary memory than ``generate_with_stats`` plus what it hands
    back, and copies no array of the state's shape (a gather by an array of
    row indices kept a second stack: 2.25 GiB more at the cell's nine
    layers)."""
    import re
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import generate_with_stats, transformer_init
    from ray_tpu.ops import flash, gated_delta, ssd
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this install
        pytest.skip(f"no TPU compiler here: {e!r}")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = app.transformer_config(app.model_kwargs(
        dict(CONFIG, num_hidden_layers=2, vocab_size=4096), 512, "auto"),
        remat=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(partial(transformer_init, cfg=cfg),
                       jax.random.PRNGKey(0)))
    prompts = jax.ShapeDtypeStruct((64, 128), jnp.int32, sharding=one)
    for module in (flash, gated_delta, ssd):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash, "generation", lambda: "v5e")
    kept = jax.jit(partial(app.generate_and_keep, cfg=cfg, new=384)).lower(
        params, prompts).compile()
    plain = jax.jit(partial(generate_with_stats, cfg=cfg, temperature=0.0,
                            max_new_tokens=384)).lower(
        params, prompts).compile()
    handed = kept.memory_analysis().output_size_in_bytes
    assert 2 * 2 * 32 * 256 * 128 * 4 < handed < (64 << 20)
    assert kept.memory_analysis().temp_size_in_bytes \
        <= plain.memory_analysis().temp_size_in_bytes + handed
    assert not re.search(r"f32\[2,64,32,256,128\]\S* copy\(",
                         kept.as_text())


# --- the rehearsal --------------------------------------------------------------

@pytest.fixture
def checkout_of_its_own():
    """As ``test_bench_olmo_hybrid.py``'s: the benchmark's files copied
    beside links to the program, so that this rehearsal's ``.rt`` and
    ``benchmark/out`` are not the ones ``test_bench_harness.py``'s soak test
    lists while other tests run."""
    import shutil
    import tempfile
    root = tempfile.mkdtemp(prefix="fh")
    copy, tmp = os.path.join(root, "co"), os.path.join(root, "t")
    os.makedirs(tmp)
    shutil.copytree(BENCH, os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "*.pb", "*_sweep.json"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), copy)
    for name in ("ray_tpu", "native"):  # the program and its daemon's source
        os.symlink(os.path.join(CHECKOUT, name), os.path.join(copy, name))
    yield copy, tmp
    shutil.rmtree(root, ignore_errors=True)


def test_the_cell_rehearses_clean_on_the_cpu(checkout_of_its_own):
    copy, tmp_path = checkout_of_its_own
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(TMPDIR=str(tmp_path), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"),
         # at the toy sizes one seed in four reads its worst position of
         # 16 over the limit set on the chip for 384: this one does not
         "--workload", CELL, "--seed", str(2 ** 31 + 57), "--seconds", "2",
         "--trace", "1", "--rehearse"], env=env, cwd=copy,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == ""
    line = next(ln for ln in proc.stderr.splitlines()
                if "REHEARSAL result" in ln)
    result = json.loads(line.split("stdout): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["state_not_float32"] == [0, 0]
    for part in ("state", "tail", "kv", "served_state", "served_tail",
                 "served_kv"):
        assert 0 < result["checks"][part + "_over_floor"][0] \
            <= result["checks"][part + "_over_floor_worst"][0]
    assert "lease.worker_ready_s" in result["metrics"]
    # the spans its readers need are there (read on a TPU only)
    assert "generate.call x" in proc.stderr
    assert "serve.batch.flush x" in proc.stderr
    assert "'linear_slots': 3, 'full_slots': 3, 'mixers_a_layer': 2" \
        in proc.stderr
    assert not os.listdir(tmp_path)             # nothing left behind


# --- the limits and the sweep they were read from ---------------------------

def sweep() -> dict:
    with open(os.path.join(BENCH, "testdata",
                           "falcon_h1_checks_sweep.json")) as f:
        return json.load(f)


def not_correct_by(numbers: dict) -> set:
    """The checks a sweep's reading fails in the program's place."""
    checks = {k: numbers[k] for k in app.LIMITS if k in numbers}
    for name in app.LIMITS:     # what a reading was not taken of
        checks.setdefault(name, 0.0)
    record = record_of(checks, cache_dtypes=numbers["cache_dtypes"]
                       or {"state": "float32"})
    return failed_checks(app.judge(record, CONFIG, TRAFFIC))


STATE_LIMITS = tuple(name for name in app.LIMITS if "state" in name)
FOLD_FAULTS = ("key_multiplier_left_out", "ssm_multiplier_wrong")
SERVED_FAULTS = ("state_in_bfloat16",) + FOLD_FAULTS


def test_the_limits_come_from_their_sweep():
    """Every limit has 1.2 x of room or more over every sound reading (the
    sweep's seeds and the cell's own runs). The control has to fail one
    limit, not each: the logits', tails', keys' and values' limits lie 0.85
    x under its least reading or lower; the state's do not try (the control
    reads inside the sound seeds' range at its worst place) and lie a fifth
    or less of what the state's own faults read."""
    data = sweep()
    assert data["device"]["kind"] == V5E and not data["rehearsal"]
    assert data["positions"] == [128, 383] and len(data["seeds"]) >= 6
    assert data["served_rows"] == TRAFFIC["max_batch_size"] == 64
    assert len(data["runs"]) >= 6
    for name, limit in app.LIMITS.items():
        sound = [r["sound"][name] for r in data["seeds"]] \
            + [r[name] for r in data["runs"]]
        control = [r["control"][name] for r in data["seeds"]]
        assert 1.2 * max(sound) <= limit, (name, max(sound))
        if name == "token_deficit_over_floor":
            altered = [r["altered_token_over_floor"] for r in data["runs"]]
            assert 1.5 * max(sound) <= limit <= 0.8 * min(altered), (
                name, max(sound), min(altered))
            # a lower precision is not this number's to see
            assert max(control) < limit
        elif name in STATE_LIMITS:
            faults = [r["faults"][f][name] for r in data["seeds"]
                      for f in SERVED_FAULTS if f in r["faults"]]
            assert len(faults) >= 9 and limit <= 0.25 * min(faults), (
                name, min(faults))
        else:
            assert limit <= 0.85 * min(control), (name, min(control))
    for row in data["runs"]:                # the cell's own runs
        assert row["correct"] and set(row["rows_a_call"]) == {64}
        assert not_correct_by(dict(row, cache_dtypes=None)) == set(), row


def test_a_sound_seed_is_correct_and_the_control_is_not_on_any():
    for row in sweep()["seeds"]:
        assert not_correct_by(row["sound"]) == set(), row["seed"]
        failed = not_correct_by(row["control"])
        assert len(failed) >= 10, (row["seed"], failed)
        # the timed program's own cache sees the lower precision too
        assert {"served_tail_over_floor", "served_kv_over_floor"} <= failed


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct_on_any_seed(fault):
    rows = [r for r in sweep()["seeds"] if fault in r["faults"]]
    assert len(rows) >= 3
    for row in rows:
        failed = not_correct_by(row["faults"][fault])
        assert failed, (fault, row["seed"])
        served = {name for name in failed if name.startswith("served_")}
        if fault in SERVED_FAULTS:
            # read through the cell's own call of 64 rows as well
            assert served, (fault, row["seed"], failed)
            assert failed - served - {"state_not_float32"}
        if fault == "state_in_bfloat16":
            # a rounding of the state a step adds up where heads remember:
            # a numeric limit reads it on every seed, through the check's
            # programs and through the timed one, and the exact check of
            # the dtype besides
            assert "state_not_float32" in failed
            assert failed & {"state_over_floor", "state_over_floor_worst"}
            assert served >= {"served_state_over_floor",
                              "served_state_over_floor_worst"}
        if fault == "key_multiplier_left_out":
            # planted in ``fold_multipliers``: the reference's tree is the
            # published one, so the fold itself is held
            assert {"kv_over_floor", "served_kv_over_floor",
                    "token_deficit_over_floor"} <= failed
