"""The ``dots3`` family's side of the benchmark: its arithmetic pinned to the
published widths, the configuration against the catalog's keys, its readers
on a record with hand-worked answers, its limits against the sweep they
were read from, and the cell's rehearsal on the CPU. (Named to be collected
after ``test_bench_trace.py``: PERF.md section 7 (g).)"""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, CHECKOUT, config, manifest_data

from benchmark import manifest as manifest_mod
from benchmark import ops, ops_dots3 as family
from benchmark.apps import serve_dots3 as app

CELL = "dots3-serve-closed2-p32k-n128"
NAME = "dots3-note-prev-l5-e32"
CONFIG = config(NAME)
MF = manifest_mod.Manifest()
TRAFFIC = MF.cell(CELL)["traffic_data"]
PATTERN = ["full_attention"] + ["full_attention", "sliding_attention",
                                "sliding_attention",
                                "sliding_attention"] * 11 + ["full_attention"]
# The published config.json, as the guide's catalog holds it.
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "layer_types": PATTERN,
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 32,
           "vocab_size": 19008, "layer_types": PATTERN[:5]}


def test_the_configuration_keeps_every_published_key():
    assert len(PATTERN) == 46 and PATTERN.count("full_attention") == 13
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == REDUCED.get(key, value), key
    assert set(CONFIG["reduced"]) == {"num_hidden_layers",
                                      "n_routed_experts", "vocab_size"}
    entry = next(c for c in manifest_data()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/" \
        "config.json"
    assert (CONFIG["n_routed_experts_published"],
            CONFIG["num_hidden_layers_published"],
            CONFIG["vocab_size_published"]) == (256, 46, 152064)
    assert CONFIG["torch_dtype"] == CONFIG["param_dtype"] == "bfloat16"
    assert CONFIG["family"] == "dots3"
    assumed = " ".join(CONFIG["assumed"])
    for point in ("nothing fetched", "apply_mla_qkv_lora_rescale",
                  "rotate-half", "headwise", "counts the query's own",
                  "Hadamard", "group-limited", "normal(0, 0.01)",
                  "towers", "multi-token-prediction"):
        assert point in assumed, point
    assert "expert parallelism 8" in CONFIG["deployment"]
    assert "4,087,154,176 parameters = 8.17 GB" in \
        CONFIG["arithmetic"]["parameters"]


def test_a_published_pattern_maps_or_is_refused_in_words():
    assert app.layer_pattern(CONFIG) == (
        1, ("latent", "window", "window", "window"))
    lead, period = app.layer_pattern(dict(CONFIG, **{
        k: v for k, v in PUBLISHED.items()
        if k in ("num_hidden_layers", "layer_types")}))
    # all 46: the last full layer closes no period of four
    assert (lead, len(period), period[0]) == (1, 45, "latent")
    # the program has the leading dense layers of the period's first kind
    with pytest.raises(ValueError, match="period's first kind, window"):
        app.layer_pattern(dict(CONFIG, num_hidden_layers=3, layer_types=[
            "full_attention", "sliding_attention", "sliding_attention"]))
    with pytest.raises(ValueError, match="does not list"):
        app.layer_pattern(dict(CONFIG, num_hidden_layers=4))


def test_the_traffic_is_the_issues():
    want = {"app": "serve_dots3", "clients": 2, "prompt_tokens": 32768,
            "new_tokens": 128, "max_batch_size": 2,
            "batch_wait_timeout_s": 0.1, "max_ongoing_requests": 2,
            "request_timeout_s": 60.0}
    assert {k: TRAFFIC[k] for k in want} == want
    cell = MF.cell(CELL)
    assert (cell["chips"], cell["traffic"]) == \
        (1, "serve-closed2-p32768-n128")
    for metric in manifest_data()["end_to_end"]:
        if metric["name"].startswith("serve."):
            assert metric["workloads"][-1] == CELL


def test_parameter_counts_are_the_files_arithmetic():
    p = family.param_counts(CONFIG)
    assert p["mixer"] == {"full_attention": 144_048_128,
                          "sliding_attention": 90_832_896}
    assert (p["expert"], p["routed_held"], p["dense_ffn"]) == \
        (23_592_960, 754_974_720, 212_336_640)
    assert p["embed"] + p["head"] == 194_641_920
    assert p["total"] == 4_087_154_176


def test_the_program_holds_what_the_arithmetic_counts():
    from ray_tpu.models.transformer import transformer_num_params
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 32896, "auto"),
                                 remat=False)
    assert transformer_num_params(cfg) == \
        family.param_counts(CONFIG)["total"]


def test_operations_and_bytes_are_pinned():
    assert family.experts_a_token(CONFIG) == 1.0
    # 966.9 M parameters a token passes without the head, 97.3 M with it
    assert family.matmul_ops_a_token(CONFIG, head=False) == 2 * 966_918_144
    assert family.matmul_ops_a_token(CONFIG) == 2 * 1_064_239_104
    assert family.pair_ops(CONFIG) == {
        "index": 2 * 64 * 128, "sparse": 2 * 128 * (512 + 576),
        "window": 2 * 64 * (192 + 64 + 128)}
    assert family.causal_keys(0, 4) == 10
    assert family.causal_keys(0, 4, 2) == 1 + 2 + 2 + 2
    assert family.causal_keys(32768, 1, 2048) == 2048
    assert family.causal_keys(0, 32768) == 32768 * 32769 // 2
    assert family.cached_bytes_a_position(CONFIG) == 2 * 1408
    assert family.ring_bytes(CONFIG) == 3 * 520 * 1088 * 2
    step = family.decode_step_bytes(CONFIG, 2, 32768, 2)
    assert step["index"] == 2 * 2 * 32769 * 128 * 2
    assert step["sparse"] == 2 * 2 * 2048 * 576 * 2
    assert step["window"] == 2 * 3 * 520 * 1088 * 2
    # every weight but the routed experts, the head's 97.3 M included (the
    # embedding is a lookup), and one routed expert a row and expert layer
    assert step["weights"] == 2 * (
        97_320_960 + 2 * 144_048_128 + 3 * 90_832_896 + 212_336_640
        + 4 * (23_592_960 + 5120 * 256) + 4 * 2 * 23_592_960)
    ops_ = family.phase_ops(CONFIG, 2, 0, 32768, logits=1)
    assert ops_["index"] == 2 * 2 * 16384 * (32768 * 32769 // 2)
    assert ops_["matmul"] == 2 * (32768 * 2 * 966_918_144
                                  + 2 * 97_320_960)


def test_the_calls_least_time_follows_its_shapes():
    pk = ops.peaks("TPU v5 lite")
    least = family.generate_least_seconds(CONFIG, 2, 32768, 128, "bfloat16",
                                          "TPU v5 lite")
    assert least["prefill_seconds"] == pytest.approx(
        sum(least["prefill_ops"].values()) / pk["bf16_flops_per_s"])
    assert least["seconds"] == pytest.approx(
        least["prefill_seconds"] + least["decode_seconds"])
    # a decode step is bound by its bytes: 2.3 GB of weights and 50 MB of
    # caches over 819 GB/s
    assert least["decode_seconds"] / 128 == pytest.approx(
        sum(family.decode_step_bytes(CONFIG, 2, 32768 + 64, 2).values())
        / pk["hbm_bytes_per_s"], rel=0.01)
    assert least["cache_bytes"] == 2 * (32896 * 2816 + 3 * 520 * 1088 * 2)
    for part in ("index", "sparse", "window"):
        assert 0 < least[part + "_seconds"] < least["seconds"]
    half = family.generate_least_seconds(CONFIG, 1, 32768, 128, "bfloat16",
                                         "TPU v5 lite")
    assert half["prefill_seconds"] == pytest.approx(
        least["prefill_seconds"] / 2)
    with pytest.raises(ops.UnknownDevice):
        family.generate_least_seconds(CONFIG, 2, 64, 8, "bfloat16", "cpu")


def record_of_a_traced_run() -> dict:
    return {
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "batches": [{"start": 0.0, "end": 15.8}],
        "trace": {"busy_s": 16.0, "window_s": 16.4, "module_s": 15.0,
                  "periods": 1,
                  "scopes": {"periods": 1, "seconds": {
                      "": 1.0, "rt.dsa.index": 6.0, "rt.mla.sparse": 4.0,
                      "rt.mla.window": 0.4, "rt.mla.project": 2.0,
                      "rt.moe.experts": 1.5}},
                  "phases": {"periods": 1, "seconds": {
                      "": 0.1, "rt.generate.prefill": 14.4,
                      "rt.generate.decode": 1.5}}}}


def read(name, record):
    return MF.reader(name)(record, MF.cell(CELL))


def test_the_new_readers_on_a_record_with_hand_worked_answers():
    record = record_of_a_traced_run()
    least = family.generate_least_seconds(
        CONFIG, 2, 32768, 128, "bfloat16", "TPU v5 lite")
    assert read("generate_roofline.dots3", record) == \
        pytest.approx(100 * least["seconds"] / 15.0)
    assert read("dsa.index_roofline", record) == \
        pytest.approx(100 * least["index_seconds"] / 6.0)
    assert read("dsa.attend_roofline", record) == \
        pytest.approx(100 * least["sparse_seconds"] / 4.0)
    assert read("dsa.call_share", record) == pytest.approx(62.5)
    assert read("mla.window_share", record) == pytest.approx(2.5)
    assert read("dots3.prefill_share", record) == pytest.approx(90.0)
    for name in ("generate_roofline.dots3", "dsa.index_roofline",
                 "dsa.attend_roofline"):
        assert 0 < read(name, record) < 100


def test_the_span_reader_on_a_hand_made_session(monkeypatch):
    from benchmark import spans as spans_mod
    calls = [{"kind": "generate.call", "ts": 10.0 + 16 * i, "value": v,
              "attrs": {"rows": 2}}
             for i, v in enumerate([15.7, 15.8, 16.1, 99.0])]
    calls[3]["ts"] = 5.0                      # before the window: warm-up
    monkeypatch.setattr(spans_mod, "load", lambda record, cell: calls)
    monkeypatch.setattr(spans_mod, "in_window", lambda record, spans:
                        [s for s in spans if s["ts"] >= 10.0])
    assert read("dots3.call_s", {}) == pytest.approx(15.8)
    assert MF.reader_module("dots3.call_s").NEEDS == ("generate.call",)


NEW_METRICS = ["generate_roofline.dots3", "dsa.index_roofline",
               "dsa.attend_roofline", "dsa.call_share", "mla.window_share",
               "dots3.prefill_share", "dots3.call_s"]


# One test each, not one a metric or a fault: how many tests this directory
# collects decides which share a worker's first batch with
# ``test_bench_harness.py``'s soak (PERF.md section 7 (g)).
def test_a_reader_with_nothing_to_read_returns_none():
    """A program without the scopes or the span, an untraced run, a
    rehearsal: no number, no raise."""
    for name in NEW_METRICS:
        bare = {"facts": {"platform": "cpu", "kind": "cpu", "count": 1},
                "batches": [], "trace": {}}
        assert read(name, bare) is None, name
        traced = record_of_a_traced_run()
        traced["trace"].pop("scopes")
        traced["trace"].pop("phases")
        traced["facts"]["kind"] = "cpu"
        assert read(name, traced) is None, name


def test_each_new_metric_is_listed_for_the_cell_alone():
    for name in NEW_METRICS:
        entry = next(m for m in manifest_data()["per_layer"]
                     if m["name"] == name)
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            own = json.load(f)
        assert entry["workloads"] == own["workloads"] == [CELL], name
        assert {k: own[k] for k in entry} == entry, name
        assert own["kind"] == "per_layer" and len(own["definition"]) > 80


def test_the_trace_is_reduced_once_in_a_process_of_its_own():
    """``reduce_apart`` on the recorded v5e trace: what ``trace.reduce_file``
    reads of it, and the seconds by scope and by phase beside it."""
    from benchmark import trace as trace_mod
    path = os.path.join(BENCH, "testdata", "train-v5e-3steps.xplane.pb")
    want = json.loads(json.dumps(trace_mod.reduce_file(path)))
    got = app.reduce_apart(path, {}, {})
    scopes, phases = got.pop("scopes"), got.pop("phases")
    assert got == want and want["periods"] == 2
    assert set(scopes["seconds"]) == set(phases["seconds"]) == {""}


def test_the_phase_map_keeps_the_calls_two_phases_alone():
    text = "\n".join([
        "HloModule jit_generate",
        "%fused (p: f32[8]) -> f32[8] {",
        '  %a.1 = f32[8] add(%p, %p), metadata={op_name="jit(generate)/'
        'rt.generate.prefill/while/body/rt.mla.project/dot_general"}',
        "}",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        '  %b.2 = f32[8] multiply(%x, %x), metadata={op_name="jit(generate)/'
        'rt.generate.decode/while/body/rt.dsa.index/top_k"}',
        '  %c.3 = f32[8] negate(%x), metadata={op_name="jit(generate)/neg"}',
        "}"])
    from benchmark import trace_scopes
    assert trace_scopes.scope_map(text) == {"a.1": "rt.mla.project",
                                            "b.2": "rt.dsa.index"}
    assert app.phase_map(text) == {"a.1": "rt.generate.prefill",
                                   "b.2": "rt.generate.decode"}


def test_the_served_tokens_are_read_as_far_as_they_are_the_checks():
    import numpy as np
    logits = np.zeros((4, 5), np.float32)    # a row's [positions, vocab]
    logits[:, 2] = 3.0                       # the reference's best: 2
    logits[2, 4] = 2.5                       # a near-tie at position 2
    same = app.served_deficit([2, 2, 2, 2], [2, 2, 2, 2], logits, 0.5)
    assert same == {"token_deficit_over_floor": 0.0, "tokens_checked": 4,
                    "tokens_as_the_check": 4}
    # the served path took the near-tie the other way: read, and what it
    # served after that belongs to another sequence and is not
    tie = app.served_deficit([2, 2, 4, 0], [2, 2, 2, 2], logits, 0.25)
    assert tie == {"token_deficit_over_floor": 2.0, "tokens_checked": 3,
                   "tokens_as_the_check": 2}
    wrong = app.served_deficit([0, 2, 2, 2], [2, 2, 2, 2], logits, 0.5)
    assert wrong["token_deficit_over_floor"] == 6.0
    assert app.token_gaps([2, 4, 4, 0, 1], logits, 0.5) == [0, 6, 1, 6]


@pytest.fixture(scope="module")
def a_served_call():
    """The rehearsal's toy sizes: (cfg, params, config, what one call of the
    compiled ``generate`` served the check's two prompts)."""
    from benchmark.apps import lm
    config = lm.effective_config(MF.cell(CELL)["config_data"], True)
    traffic = lm.effective_traffic(TRAFFIC, True)
    p, new = traffic["prompt_tokens"], traffic["new_tokens"]
    cfg = app.transformer_config(app.model_kwargs(config, p + new, "auto"),
                                 remat=False)
    params = app.seeded_params(cfg, 45)
    rows = traffic["max_batch_size"]
    served = app.served_by(
        cfg, params, app.check_prompts(45, cfg.vocab_size, p, rows), new)
    return cfg, params, config, served, p, new


def held(a_served_call, served: dict) -> dict:
    cfg, params, config, _, p, new = a_served_call
    out, kept = app.held_to_the_reference(
        cfg, params, config, served, app.Program(cfg, p, new), 4)
    assert len(kept["tokens"]) == new and kept["logits"].shape[0] == new + 1
    return dict(out, **app.over_floors(out["errors"], out["floor_errors"]))


def test_every_token_and_cache_of_the_served_call_is_held_to_the_reference(
        a_served_call):
    """The compared object is the timed one: a wrong token or a wrong cache
    entry of either row of the compiled ``generate``'s own call is read."""
    import copy

    import numpy as np
    cfg, _, _, served, p, new = a_served_call
    assert served["fed"].shape == (2, p + new)
    assert not (served["fed"][0, :p] == served["fed"][1, :p]).all()
    sound = held(a_served_call, served)
    assert sound["tokens_checked"] == 2 * new
    # what a row that served the other's tokens, or one token altered,
    # would have read: reported with every run
    assert sound["token_deficit_rows_swapped"] > \
        sound["token_deficit_over_floor"]
    assert sound["token_deficit_one_altered"] > 0
    assert sound["token_gaps"]["p99"] <= sound["token_deficit_over_floor"]
    assert set(sound["errors"]["cache"]) == {
        f"{kind}.{slot}{row}" for row in ("", "@1")
        for kind, slots in (("latent", 2), ("index", 2), ("window", 3))
        for slot in range(slots)}
    assert sound["moe_rows_dropped"] == served["moe_rows_dropped"] == 0
    # toy widths in bfloat16: loose, the sweep holds the published widths
    assert sound["token_deficit_over_floor"] < 6
    assert sound["cache_over_floor_worst"] < 3

    wrong = copy.deepcopy(served)       # row 1, the last decode step's token
    wrong["fed"][1, -1] = (wrong["fed"][1, -1] + 97) % cfg.vocab_size
    assert held(a_served_call, wrong)["token_deficit_over_floor"] > \
        4 * max(sound["token_deficit_over_floor"], 1.0)

    wrong = copy.deepcopy(served)       # row 1 reads row 0's latents
    wrong["views"][1]["latent"] = served["views"][0]["latent"]
    got = held(a_served_call, wrong)
    assert got["cache_over_floor_worst"] > 10
    assert max(got["errors"]["cache"], key=got["errors"]["cache"].get) \
        .endswith("@1")

    wrong = copy.deepcopy(served)       # a decode step's ring entry lost
    ring = wrong["views"][0]["window"]
    ring[2, :, -1] = np.zeros_like(ring[2, :, -1])
    assert held(a_served_call, wrong)["errors"]["cache"]["window.2"] > \
        3 * sound["errors"]["cache"]["window.2"]


def test_a_cache_is_cut_to_the_positions_written():
    import numpy as np
    from ray_tpu.models.generate import window_rows
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 32896, "auto"),
                                 remat=False)
    rows = window_rows(cfg)
    details = {"latent": np.zeros((2, 1, 40000, 1)), "index": None,
               "window": np.arange(40000.0)[None, None, :, None],
               "logits": "as it was"}
    cut = app.cut_to(cfg, details, 32896)
    assert cut["latent"].shape[2] == 32896 and cut["index"] is None
    assert cut["window"][0, 0, :, 0].tolist() == \
        list(range(32896 - rows, 32896))
    assert app.cut_to(cfg, details, 100)["window"].shape[2] == 100
    assert cut["logits"] == "as it was" and details["window"].shape[2] == 40000


def checks_of_a_sound_run(**over) -> dict:
    floor = {"logits": [0.01, 0.02, 0.02], "cache": {"latent.0": 0.001,
                                                     "window.0": 0.002}}
    errs = {"logits": [0.01, 0.02],     # the side program's positions
            "cache": dict(floor["cache"], **{"latent.0@1": 0.001,
                                             "window.0@1": 0.002})}
    checks = {
        "rms_over_std": 0.02, "floor_rms_over_std": 0.015,
        "errors": errs, "floor_errors": floor,
        "twin_token_deficit_over_floor": 0.5,
        "selection_overlap": 0.97, "routing_weights_off": 0.0005,
        "routing_missed": 0.002, "moe_rows_dropped": 0,
        "window_keys_off": 0,
        "token_deficit_over_floor": 1.0,
        "rms_norm_eps": {"published": 1e-5, "program": 1e-5},
        "param_dtypes": ["bfloat16"], "compute_dtype": "bfloat16"}
    checks.update(over)
    return checks


def record_of(checks: dict, dropped_in_a_call: int = 0) -> dict:
    reply = {"ok": True, "rid": 0, "extra": {"tokens": [1] * 128}}
    return {"checks": checks,
            "warmup": [reply, dict(reply, rid=1)],
            "window": {"rows": [dict(reply, rid=2)]},
            "batches": [{"moe_rows_dropped": dropped_in_a_call}]}


def failed_checks(why) -> set:
    return {reason.split(":", 1)[0] for reason in why}


def test_the_judgement_names_what_failed():
    assert app.judge(record_of(checks_of_a_sound_run()), CONFIG,
                     TRAFFIC) == []
    for planted, names in [
            ({"selection_overlap": 0.5}, {"selection_missed"}),
            ({"routing_weights_off": 0.012}, {"routing_weights_off"}),
            ({"routing_missed": 0.04}, {"routing_missed"}),
            ({"moe_rows_dropped": 3}, {"moe_rows_dropped"}),
            ({"window_keys_off": 1}, {"window_keys_off"}),
            ({"token_deficit_over_floor": 150.0},
             {"token_deficit_over_floor"}),
            ({"twin_token_deficit_over_floor": 150.0},
             {"token_deficit_over_floor"}),
            ({"compute_dtype": "float32"},
             {"compute_dtype_not_as_configured"}),
            ({"rms_norm_eps": {"published": 1e-5, "program": 1e-4}},
             {"eps_off_known"})]:
        why = app.judge(record_of(checks_of_a_sound_run(**planted)), CONFIG,
                        TRAFFIC)
        assert failed_checks(why) == names, planted
    assert failed_checks(app.judge(
        record_of(checks_of_a_sound_run(), dropped_in_a_call=1), CONFIG,
        TRAFFIC)) == {"moe_rows_dropped"}
    worse = checks_of_a_sound_run()
    worse["errors"] = {"logits": [0.05, 0.1],
                       "cache": {"latent.0": 0.004, "window.0": 0.002}}
    assert failed_checks(app.judge(record_of(worse), CONFIG, TRAFFIC)) >= \
        {"rms_over_floor", "cache_over_floor_worst"}
    # a later row of the served call is read over row 0's floors
    row_1 = checks_of_a_sound_run()
    row_1["errors"]["cache"]["window.0@1"] = 0.008
    assert failed_checks(app.judge(record_of(row_1), CONFIG, TRAFFIC)) == \
        {"cache_over_floor_worst"}
    assert set(app.WHAT_EACH_CHECK_SAYS) == \
        set(app.judged(record_of(checks_of_a_sound_run()), CONFIG, TRAFFIC))


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
    with pytest.raises(ValueError, match="cannot run latent attention"):
        app.transformer_config(app.model_kwargs(CONFIG, 32896, "auto"),
                               remat=False)


@pytest.fixture
def checkout_of_its_own():
    """(a copy of the benchmark beside a link to the program, a temporary
    directory), both under one short path: the run keeps its runtime
    directory and its record there, and not under this checkout's ``.rt``
    and ``benchmark/out/runs``, which ``test_bench_harness.py``'s soak
    lists while other tests run (PERF.md section 7 (g))."""
    import shutil
    import tempfile
    root = tempfile.mkdtemp(prefix="d3")
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
         "--workload", CELL, "--seed", str(2 ** 31 + 45), "--seconds", "2",
         "--trace", "1", "--rehearse"], env=env, cwd=copy,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == ""
    line = next(ln for ln in proc.stderr.splitlines()
                if "REHEARSAL result" in ln)
    result = json.loads(line.split("stdout): ", 1)[1])
    assert result["failed"] == 0 and result["attempted"] > 0
    checks = result["checks"]
    # at toy widths in bfloat16 one flipped selection of 16 moves a
    # position's logits severalfold: the exact checks and the routing are
    # held here, the sweep holds the rest at the published widths
    for name in ("moe_rows_dropped", "replies_malformed",
                 "twin_replies_differ", "eps_off_known",
                 "weights_not_as_configured",
                 "compute_dtype_not_as_configured"):
        assert checks[name] == [0, 0], name
    for name in ("routing_weights_off", "routing_missed",
                 "token_deficit_over_floor", "cache_over_floor"):
        assert checks[name][0] <= checks[name][1], name
    assert checks["selection_missed"][0] < 0.1
    assert "lease.worker_ready_s" in result["metrics"]
    assert "'prefill_chunks': 1, 'index_topk': 16" in proc.stderr
    assert not os.listdir(tmp_path)             # nothing left behind


# --- the limits and the sweep they were read from ---------------------------

def sweep() -> dict:
    with open(app.SWEEP) as f:
        return json.load(f)


def rows_of(case: str) -> list:
    return [r for r in sweep()["rows"] if r["case"] == case]


def not_correct_by(row: dict) -> set:
    """The family's checks a sweep's row fails."""
    checks = checks_of_a_sound_run(
        **{k: v for k, v in row.items()
           if k in ("selection_overlap", "routing_weights_off",
                    "routing_missed", "moe_rows_dropped", "window_keys_off",
                    "token_deficit_over_floor")})
    # the row holds its numbers over their floors already
    failed = failed_checks(app.judge(record_of(checks), CONFIG, TRAFFIC))
    failed |= {name for name in ("rms_over_floor", "rms_over_floor_worst",
                                 "cache_over_floor", "cache_over_floor_worst")
               if name in app.LIMITS and name in row
               and not row[name] <= app.LIMITS[name]}
    return failed


FAULTS = ["recent_2048_not_top", "index_relu_dropped",
          "index_head_weights_dropped", "rescale_left_out", "gate_left_out",
          "window_one_short", "softmax_routing", "bias_in_the_weights",
          "bias_ignored_in_selection", "shared_expert_gated"]


def test_the_sweep_is_of_the_cells_sizes():
    data = sweep()
    assert data["sizes"] == {"prompt_tokens": 32768, "new_tokens": 128,
                             "rows": 2, "decoded": app.CHECK_DECODED}
    assert data["config"] == NAME and data["device"].startswith("TPU v5")
    # 19, not the 24 that PERF.md section 7 asks of a sweep: a seed costs
    # the chip six minutes here (PERF.md section 7, PR 45); of them the 6 of
    # the second round read the served generate's own call
    sound = rows_of("sound")
    assert len({r["seed"] for r in sound}) == len(sound) >= 19
    assert len([r for r in sound if r["round"] == 2]) >= 6


def test_every_sound_seed_is_correct_with_room():
    for row in rows_of("sound"):
        assert not_correct_by(row) == set(), row["seed"]
        assert row["moe_rows_dropped"] == 0
    for name, limit in app.LIMITS.items():
        if name in ("moe_rows_dropped", "window_keys_off"):
            continue
        key = {"selection_missed": "selection_overlap"}.get(name, name)
        values = [1 - r[key] if name == "selection_missed" else r[key]
                  for r in rows_of("sound") if key in r]
        assert len(values) >= 5, name
        assert max(values) <= limit / 1.25, (name, max(values), limit)


def test_the_control_is_not_correct_on_any_seed():
    rows = rows_of("control_int8")
    assert len(rows) >= 6
    for row in rows:
        assert not_correct_by(row), row["seed"]


def test_a_row_that_served_anothers_tokens_is_not_correct_on_any_seed():
    rows = rows_of("rows_swapped")
    assert len(rows) >= 5
    for row in rows:
        assert row["token_deficit_over_floor"] > \
            2 * app.LIMITS["token_deficit_over_floor"], row["seed"]


def test_one_altered_token_of_256_is_refused_on_most_seeds_only():
    """A random token in a served one's place lies 11-49 floors under the
    reference's best, a sound run's widest gap 4-8: a flipped selection
    moves one position's logits severalfold, and no limit tells every
    single wrong token from that (PERF.md section 2)."""
    over = [row["token_deficit_over_floor"]
            > app.LIMITS["token_deficit_over_floor"]
            for row in rows_of("altered_token")]
    assert len(over) >= 11 and 0.6 * len(over) <= sum(over) < len(over)


def test_each_planted_fault_is_not_correct_on_any_seed():
    for fault in FAULTS:
        rows = rows_of("fault:" + fault)
        assert len(rows) >= 2, fault
        for row in rows:
            assert not_correct_by(row), (fault, row["seed"])
