"""The ``olmo_hybrid`` family's side of the benchmark: its arithmetic pinned
to the published model and to the issue's own sums, the configuration
against the catalog's keys, its readers on records with hand-worked
answers, its limits against the sweep they were read from, the control
through the plain reference at a toy size, and the cell's rehearsal on the
CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, CHECKOUT, config, manifest_data

from benchmark import manifest as manifest_mod
from benchmark import ops_olmo_hybrid as family
from benchmark.apps import serve_olmo_hybrid as app
from benchmark.testdata.sweep_olmo_hybrid import FAULTS

CELL = "olmohybrid-serve-closed48-p128-n384"
NAME = "olmo-hybrid-7b-l20"
CONFIG = config(NAME)
MF = manifest_mod.Manifest()
TRAFFIC = MF.cell(CELL)["traffic_data"]
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# The published config.json, as the guide's catalog holds it.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
NEW_METRICS = ("generate_roofline.olmo_hybrid", "gdn.step_roofline",
               "gdn.call_share", "state.cache_share", "olmo_hybrid.call_s",
               "olmo_hybrid.prefill_share")
V5E = "TPU v5 lite"


# --- the arithmetic -------------------------------------------------------------

def test_parameter_counts_are_the_issues_sums():
    p = family.param_counts(CONFIG)
    assert p["linear_mixer"] == 88_750_332 == (
        3840 * 17_280 + 3840 * 60 + 11_520 * 4 + 30 + 30 + 192
        + 5760 * 3840)
    assert p["mlp"] == 126_812_160 == 3 * 3840 * 11008
    assert p["linear_layer"] == 215_570_172
    assert p["full_layer"] == 185_809_920
    assert 3 * p["linear_layer"] + p["full_layer"] == 832_520_436
    assert p["embed"] + p["head"] == 770_703_360
    assert p["total"] == 4_933_309_380 == \
        5 * 832_520_436 + 770_703_360 + 3840
    whole = family.param_counts(
        {**CONFIG, "num_hidden_layers": 32, "layer_types": PERIOD * 8})
    assert whole["total"] == 8 * 832_520_436 + 770_703_360 + 3840


def test_the_program_holds_what_the_arithmetic_counts():
    from ray_tpu.models.transformer import transformer_num_params
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 512, "auto"),
                                 remat=False)
    assert (cfg.kinds, cfg.periods, cfg.head_dim, cfg.kv_heads) == (
        ("linear", "linear", "linear", "full"), 5, 128, 30)
    assert (cfg.linear_beta_scale, cfg.post_norm_only, cfg.qk_norm_whole,
            cfg.rotary_dim) == (2.0, True, True, 0)
    assert transformer_num_params(cfg) == \
        family.param_counts(CONFIG)["total"]


def test_the_programs_cache_holds_what_the_arithmetic_counts():
    import importlib

    import numpy as np
    gen = importlib.import_module("ray_tpu.models.generate")
    cfg = app.transformer_config(app.model_kwargs(CONFIG, 512, "auto"),
                                 remat=False)
    shapes = gen.cache_shapes(cfg, 48, 512)
    want = family.cache_bytes(CONFIG, 48, 512)
    assert shapes["state"] == (15, 48, 15, 96, 384)     # nothing padded
    assert shapes["tail"] == (15, 3, 48, 11_520)
    assert 4 * int(np.prod(shapes["state"])) == want["state"] \
        == 1_592_524_800
    assert 2 * int(np.prod(shapes["tail"])) == want["tail"] == 49_766_400
    assert 2 * 2 * int(np.prod(shapes["k"])) == want["kv"] == 1_887_436_800
    with gen.call_span(cfg, 48, 128, 384) as sp:
        pass
    assert sp.attrs["cache_bytes"] == want["total"] == 3_529_728_000


def test_bytes_a_row_and_a_step_are_pinned():
    assert family.state_bytes_a_row(CONFIG) == 2_211_840
    assert family.tail_bytes_a_row(CONFIG) == 69_120
    assert family.kv_bytes_a_position(CONFIG) == 76_800 == 5 * 15_360
    assert family.rule_ops_a_position(CONFIG) == 7 * 30 * 96 * 192
    a_row = 2 * 2_211_840 + 2 * 69_120 + 11_520 * 2 + 2 * 30 * 4 \
        + 30 * 192 * 2
    assert family.rule_step_bytes(CONFIG, 48) == 48 * a_row + 11_520 * 4 * 2
    fwd = family.forward_ops_per_token(CONFIG, 128)
    assert fwd["rule"] == 15 * 7 * 30 * 96 * 192
    assert fwd["attention"] == 2 * 128 * 30 * 128 * 5
    assert fwd["total"] == sum(fwd[k] for k in (
        "layers", "conv", "rule", "attention", "head"))


def test_the_calls_least_time_follows_its_shapes():
    least = family.generate_least_seconds(CONFIG, 48, 128, 384, "bfloat16",
                                          V5E)
    # weights once a step: every matrix and the convolutions, not the
    # embedding (a lookup) nor the norms
    assert least["weight_bytes_a_step"] == 2 * (
        15 * (88_750_332 - 252 - 46_080 + 126_812_160)
        + 5 * (58_982_400 + 126_812_160) + 15 * 46_080 + 385_351_680)
    assert least["state_bytes_a_step"] == 2 * (1_592_524_800 + 49_766_400)
    assert least["cache_bytes"] == 3_529_728_000
    assert least["seconds"] == pytest.approx(
        least["prefill_seconds"] + least["decode_seconds"])
    assert least["bound"] == "prefill compute, decode memory"
    assert least["rule_seconds"] == pytest.approx(
        384 * 15 * family.rule_step_bytes(CONFIG, 48) / 819e9)
    # the issue's step at the mean position: 13.5 GB, 16.4 ms
    assert least["decode_seconds"] / 384 == pytest.approx(0.0164, rel=0.02)
    assert 0.22 < least["rule_seconds"] / least["decode_seconds"] < 0.26
    twice = family.generate_least_seconds(CONFIG, 96, 128, 384, "bfloat16",
                                          V5E)
    assert twice["state_bytes_a_step"] == 2 * least["state_bytes_a_step"]
    assert twice["weight_bytes_a_step"] == least["weight_bytes_a_step"]
    from benchmark import ops
    with pytest.raises(ops.UnknownDevice):
        family.generate_least_seconds(CONFIG, 48, 128, 384, "bfloat16", "cpu")


# --- the files --------------------------------------------------------------------

def test_the_configuration_keeps_every_published_key():
    for key, value in PUBLISHED.items():
        if key not in ("num_hidden_layers", "layer_types"):
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 20
    assert CONFIG["layer_types"] == PERIOD * 5          # five whole periods
    assert CONFIG["num_hidden_layers_published"] == 32
    assert list(CONFIG["reduced"]) == ["num_hidden_layers"]
    entry = next(c for c in manifest_data()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    assert CONFIG["torch_dtype"] == CONFIG["param_dtype"] == "bfloat16"
    assert CONFIG["family"] == "olmo_hybrid"
    assert "4,933,309,380" in CONFIG["arithmetic"]["parameters"]
    assert "second stage" in CONFIG["deployment"]
    recalled = " ".join(CONFIG["assumed"])
    for point in ("no network", "OUTPUT only", "whole projection",
                  "no rotation", "2 sigmoid(b)", "not fetched"):
        assert point in recalled, point


def test_the_traffic_is_the_issues():
    want = {"app": "serve_olmo_hybrid", "clients": 48, "prompt_tokens": 128,
            "new_tokens": 384, "max_batch_size": 48,
            "batch_wait_timeout_s": 0.1, "max_ongoing_requests": 48,
            "request_timeout_s": 60.0}
    assert {k: TRAFFIC[k] for k in want} == want
    assert set(TRAFFIC["rehearse"]) <= set(want)
    cell = MF.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == \
        (1, "serve-closed48-p128-n384", NAME)


def test_dots3s_traffic_is_still_its_issues():
    """What ``test_bench_zdots3.py::test_the_traffic_is_the_issues`` held
    before its last assert (its cell in the LAST place of each serving
    metric's ``workloads``) went under ``tests/conftest.py``'s
    ``xfail(strict)`` when this family's cell was appended: the dots3 cell's
    traffic, chips and traffic file, and that the cell is still on every
    serving metric's list: the last of ``serve.tokens_per_s``'s, which this
    family's cell is not on (below), and right before this family's on the
    two tails'."""
    dots3 = "dots3-serve-closed2-p32k-n128"
    cell = MF.cell(dots3)
    want = {"app": "serve_dots3", "clients": 2, "prompt_tokens": 32768,
            "new_tokens": 128, "max_batch_size": 2,
            "batch_wait_timeout_s": 0.1, "max_ongoing_requests": 2,
            "request_timeout_s": 60.0}
    assert {k: cell["traffic_data"][k] for k in want} == want
    assert (cell["chips"], cell["traffic"]) == \
        (1, "serve-closed2-p32768-n128")
    for metric in manifest_data()["end_to_end"]:
        if metric["name"].startswith("serve."):
            want = [dots3] if metric["name"] == "serve.tokens_per_s" \
                else [dots3, CELL]
            assert metric["workloads"][-len(want):] == want, metric["name"]


def test_the_manifest_gained_entries_and_two_appended_names():
    """ISSUE 51 asked for three appended names. The cell is on the two
    tails' lists and NOT on ``serve.tokens_per_s``'s: at the issue's 0.1 s
    window a round leaves 2 to 12 of the 48 callers behind, a window ends
    on a stragglers' call or does not, and the driver's check of PR 51 read
    that metric's spread at 25.8 and 11.4 tokens/s against a bound of 16.8
    and refused the cell for it; the tails spread 0.011 % (``PERF.md``
    section 6, PR 51 (4)). So the four layer metrics that would move
    tokens a second name the request's p95, which they move as well: it is
    two calls and their gaps."""
    data = manifest_data()
    assert data["workloads"][-1]["name"] == CELL
    assert data["configs"][-1]["name"] == NAME
    before = ["mistral7b-serve-closed32", "ouro2.6b-serve-closed16",
              "dots3-serve-closed2-p32k-n128"]
    for m in data["end_to_end"]:
        if m["name"] == "serve.tokens_per_s":
            assert m["workloads"] == before
        elif m["name"].startswith("serve."):
            assert m["workloads"] == before + [CELL]
        elif "workloads" in m:
            assert CELL not in m["workloads"]
    reported = {m["name"] for m in MF.metrics("end_to_end", CELL)}
    assert reported == {"serve.request_p95_s", "serve.ttft_p95_s", "setup_s"}
    assert {m["moves"] for m in data["per_layer"]
            if m["name"] in NEW_METRICS} <= reported
    assert [m["name"] for m in data["per_layer"][-6:]] == list(NEW_METRICS)
    for m in data["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            with open(os.path.join(BENCH, "metrics",
                                   m["name"] + ".json")) as f:
                beside = json.load(f)
            assert {k: beside[k] for k in m} == m
            assert len(beside["definition"]) > 80
        else:
            assert CELL not in m.get("workloads", ())
    assert [m["name"] for m in MF.metrics("per_layer", CELL)
            if m["name"] in NEW_METRICS] == list(NEW_METRICS)


# --- the readers ------------------------------------------------------------------

def record_of_a_traced_run() -> dict:
    seconds = {"": 0.4, "rt.generate.prefill": 0.2,
               "rt.generate.decode": 9.0, "rt.gdn.step": 4.0,
               "rt.gdn.conv": 0.5, "rt.gdn.proj": 2.5, "rt.gdn.scan": 0.4,
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
                      "rt.gdn.step": 4.0, "rt.gdn.conv": 0.4,
                      "rt.gdn.proj": 2.4, "rt.loop.cache": 1.0}}}}


def read(name, record):
    return MF.reader(name)(record, MF.cell(CELL))


def test_the_new_readers_on_a_record_with_hand_worked_answers():
    record = record_of_a_traced_run()
    least = family.generate_least_seconds(CONFIG, 48, 128, 384, "bfloat16",
                                          V5E)
    assert read("generate_roofline.olmo_hybrid", record) == \
        pytest.approx(100 * least["seconds"] * 2 / 17.0)
    # the token loop's rule and convolution, not the prefill's convolution
    assert read("gdn.step_roofline", record) == \
        pytest.approx(100 * least["rule_seconds"] * 2 / 4.4)
    assert read("gdn.call_share", record) == \
        pytest.approx(100 * (4.0 + 0.5 + 2.5 + 0.4) / 18.0)
    assert read("olmo_hybrid.prefill_share", record) == pytest.approx(5.0)


def test_the_span_readers_on_a_hand_made_session(monkeypatch):
    from benchmark import spans as spans_mod
    attrs = {"rows": 48, "cache_bytes": 1000, "cache_bytes_state": 400,
             "cache_bytes_tail": 50, "cache_bytes_kv": 550}
    calls = [{"kind": "generate.call", "ts": 10.0 + 9 * i, "value": v,
              "attrs": dict(attrs)}
             for i, v in enumerate([8.3, 8.4, 8.6, 99.0])]
    calls[3]["ts"] = 5.0                      # before the window: warm-up
    monkeypatch.setattr(spans_mod, "load", lambda record, cell: calls)
    monkeypatch.setattr(spans_mod, "in_window", lambda record, spans:
                        [s for s in spans if s["ts"] >= 10.0])
    assert read("olmo_hybrid.call_s", {}) == pytest.approx(8.4)
    assert read("state.cache_share", {}) == pytest.approx(45.0)
    for name in ("olmo_hybrid.call_s", "state.cache_share"):
        assert MF.reader_module(name).NEEDS == ("generate.call",)
    # the parent's spans carry no such counter: no number, no raise
    for c in calls:
        del c["attrs"]["cache_bytes_state"]
    assert read("state.cache_share", {}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_with_nothing_to_read_returns_none(name):
    """A program without the scope or the span (the parent of the PR that
    added them), an untraced run, a rehearsal: no number, no raise."""
    bare = {"facts": {"platform": "cpu", "kind": "cpu", "count": 1},
            "batches": [], "trace": {}}
    assert read(name, bare) is None
    traced = record_of_a_traced_run()
    for reduction in ("scopes", "phases", "decode_scopes"):
        traced["trace"].pop(reduction)        # the parent's program
    traced["facts"]["kind"] = "cpu"           # and a rehearsal's device
    assert read(name, traced) is None


def test_the_trace_is_reduced_once_in_a_process_of_its_own():
    """``reduce_apart`` on the recorded v5e trace: what ``trace.reduce_file``
    reads of it, and the three reductions by scope beside it."""
    from benchmark import trace as trace_mod
    path = os.path.join(BENCH, "testdata", "train-v5e-3steps.xplane.pb")
    want = json.loads(json.dumps(trace_mod.reduce_file(path)))
    got = app.reduce_apart(path, {}, {})
    for name in ("scopes", "phases", "decode_scopes"):
        reduced = got.pop(name)
        assert set(reduced["seconds"]) == {""}
        assert reduced["periods"] == want["periods"] == 2
    assert got == want


# --- the judgement ----------------------------------------------------------------

def record_of(checks: dict, **over) -> dict:
    tokens = list(range(TRAFFIC["new_tokens"]))
    row = {"ok": True, "rid": 0, "extra": {"tokens": tokens}}
    checks = dict(
        {"rms_over_std": 0.2, "floor_rms_over_std": 0.15,
         "token_deficit_over_std": 0.5,
         "rms_norm_eps": {"published": 1e-6, "program": 1e-6},
         "param_dtypes": ["bfloat16"], "compute_dtype": "bfloat16",
         "cache_dtypes": {"state": "float32", "tail": "bfloat16"}},
        **checks)
    checks.update(over)
    return {"checks": checks, "warmup": [row, dict(row, rid=1)],
            "window": {"rows": [dict(row, rid=2)]}}


def failed_checks(why: list) -> set:
    return {reason.split(":", 1)[0] for reason in why}


SOUND = {"rms_over_floor": 1.3, "rms_over_floor_worst": 1.6,
         "state_over_floor": 1.4, "state_over_floor_worst": 1.8,
         "tail_over_floor": 1.35, "tail_over_floor_worst": 1.5,
         "token_deficit_over_floor": 5.0}


def test_the_judgement_names_what_failed():
    record = record_of(SOUND)
    assert app.judge(record, CONFIG, TRAFFIC) == []
    assert set(record["judged"]) == set(app.WHAT_EACH_CHECK_SAYS)
    assert set(app.LIMITS) < set(record["judged"])
    # serve_lm's two ratios over all positions together are reported only
    assert "token_deficit_over_std" not in record["judged"]
    for name, limit in app.LIMITS.items():
        assert record["judged"][name] == [SOUND[name], limit]
        why = app.judge(record_of(SOUND, **{name: 1.01 * limit}), CONFIG,
                        TRAFFIC)
        assert failed_checks(why) == {name}
        assert f"{1.01 * limit:.6g}" in why[0]
    for over, names in (
            (dict(rms_over_floor=float("nan")), {"rms_over_floor"}),
            (dict(cache_dtypes={"state": "bfloat16"}),
             {"state_not_float32"}),
            (dict(token_deficit_over_std=9.0), set()),
            (dict(compute_dtype="float32"),
             {"compute_dtype_not_as_configured"}),
            (dict(param_dtypes=["float32"]), {"weights_not_as_configured"}),
            (dict(rms_norm_eps={"published": 1e-6, "program": 1e-5}),
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
        "import test_bench_olmo_hybrid as t\n"
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
        sandwich_norm: bool = False
    monkeypatch.setattr(models, "TransformerConfig", ParentsConfig)
    with pytest.raises(ValueError, match="cannot run a post-normed stack"):
        app.transformer_config(app.model_kwargs(CONFIG, 512, "auto"),
                               remat=False)


def test_a_pattern_that_is_no_period_is_refused_in_words():
    with pytest.raises(ValueError, match="no repeated period"):
        app.layer_pattern({**CONFIG, "layer_types":
                           PERIOD * 4 + ["linear_attention"] * 4})
    with pytest.raises(ValueError, match="19 layer_types for 20 layers"):
        app.layer_pattern({**CONFIG, "layer_types": (PERIOD * 5)[:19]})
    with pytest.raises(ValueError, match="no rotation"):
        app.model_kwargs({**CONFIG, "rope_parameters":
                          {"rope_theta": 500000}}, 512, "auto")
    assert app.layer_pattern(CONFIG) == ("linear", "linear", "linear", "full")


# --- the control, at a toy size ------------------------------------------------

def test_int8_weights_through_the_reference_read_over_the_floor():
    """The sweep's control on the CPU at the rehearsal's sizes: the rounded
    reference is the floor (1 by construction), the sound program near it,
    int8 weights over it in logits, states and tails."""
    import jax.numpy as jnp

    from benchmark.apps import lm
    toy = lm.effective_config(CONFIG, True)
    cfg = app.transformer_config(app.model_kwargs(toy, 32, "reference"),
                                 remat=False)
    params = app.seeded_params(cfg, 7)
    tokens = jnp.asarray(app.check_tokens(7, cfg.vocab_size, 16 + 7))
    weights = app.reference_weights(params, toy)
    reference = lm.reference_module(toy)
    full = app.reference_pass(weights, toy, tokens, 16, 1e-6)
    rounded = app.reference_pass(weights, toy, tokens, 16, 1e-6,
                                 jnp.bfloat16)
    floor = app.errors(rounded, full, toy)
    assert len(floor["state"]) == len(floor["tail"]) == 2 * 6
    assert len(floor["logits"]) == 2 * 8
    sound = app.over_floors(
        app.errors(app.Program(cfg, 16, 32).run(params, tokens), full, toy),
        floor, toy)
    control = app.over_floors(app.errors(app.reference_pass(
        reference.int8_weights(weights), toy, tokens, 16, 1e-6,
        jnp.bfloat16), full, toy), floor, toy)
    for name in ("rms_over_floor", "state_over_floor", "tail_over_floor"):
        assert 0.7 < sound[name] < 1.5, (name, sound)
        assert control[name] > 1.6 > sound[name], (name, control, sound)


def test_the_seeded_step_sizes_are_the_authors_and_nothing_else_moves():
    """``seeded_params`` is ``transformer_init``'s tree with every linear
    stack's ``dt_bias`` drawn so that softplus of it lies in [DT_MIN,
    DT_MAX] (heads that remember), from the seed; every other leaf is the
    initialiser's own."""
    import jax
    import numpy as np

    from benchmark.apps import lm
    from ray_tpu.models import transformer_init
    toy = lm.effective_config(CONFIG, True)
    cfg = app.transformer_config(app.model_kwargs(toy, 32, "reference"),
                                 remat=False)
    params = app.seeded_params(cfg, 7)
    plain = transformer_init(jax.random.PRNGKey(lm.fold_seed(7)), cfg=cfg)
    drawn = []
    for mine, theirs in zip(params["layers"], plain["layers"]):
        if "gdn" in mine:
            bias = np.asarray(mine["gdn"]["dt_bias"], np.float64)
            assert bias.shape == theirs["gdn"]["dt_bias"].shape
            assert mine["gdn"]["dt_bias"].dtype == \
                theirs["gdn"]["dt_bias"].dtype
            dt = np.log1p(np.exp(bias))
            # the parameters' dtype rounds the bias: 1 % of room
            assert (dt > 0.99 * app.DT_MIN).all() \
                and (dt < 1.01 * app.DT_MAX).all(), dt
            drawn.append(bias)
            mine = dict(mine, gdn=dict(mine["gdn"], dt_bias=None))
            theirs = dict(theirs, gdn=dict(theirs["gdn"], dt_bias=None))
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(drawn) == 3 and not np.array_equal(drawn[0], drawn[1])
    again = app.seeded_params(cfg, 7)["layers"][0]["gdn"]["dt_bias"]
    np.testing.assert_array_equal(
        np.asarray(again), np.asarray(params["layers"][0]["gdn"]["dt_bias"]))
    other = app.seeded_params(cfg, 8)["layers"][0]["gdn"]["dt_bias"]
    assert not np.array_equal(np.asarray(other), np.asarray(again))


# --- the rehearsal --------------------------------------------------------------

@pytest.fixture
def checkout_of_its_own():
    """As ``test_bench_zdots3.py``'s: the benchmark's files copied beside
    links to the program, so that this rehearsal's ``.rt`` and
    ``benchmark/out`` are not the ones ``test_bench_harness.py``'s soak
    test lists while other tests run (it found this run's ``rtb-*``
    directory there in the driver's run of PR 51's tree and in mine)."""
    import shutil
    import tempfile
    root = tempfile.mkdtemp(prefix="oh")
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
         "--workload", CELL, "--seed", str(2 ** 31 + 51), "--seconds", "2",
         "--trace", "1", "--rehearse"], env=env, cwd=copy,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert proc.stdout == ""
    line = next(ln for ln in proc.stderr.splitlines()
                if "REHEARSAL result" in ln)
    result = json.loads(line.split("stdout): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["state_not_float32"] == [0, 0]
    assert 0 < result["checks"]["state_over_floor"][0] \
        <= result["checks"]["state_over_floor_worst"][0]
    assert "lease.worker_ready_s" in result["metrics"]
    assert "'linear_slots': 6, 'full_slots': 2" in proc.stderr
    assert not os.listdir(tmp_path)             # nothing left behind


# --- the limits and the sweep they were read from ---------------------------

def sweep() -> dict:
    with open(os.path.join(BENCH, "testdata",
                           "olmo_hybrid_checks_sweep.json")) as f:
        return json.load(f)


def not_correct_by(numbers: dict) -> set:
    """The checks a sweep's reading fails in the program's place."""
    checks = {k: numbers[k] for k in app.LIMITS if k in numbers}
    checks.setdefault("token_deficit_over_floor", 0.0)
    record = record_of(checks, cache_dtypes=numbers["cache_dtypes"]
                       or {"state": "float32"})
    return failed_checks(app.judge(record, CONFIG, TRAFFIC))


def test_the_limits_come_from_their_sweep():
    data = sweep()
    assert data["device"]["kind"] == V5E and not data["rehearsal"]
    assert data["positions"] == [128, 383] and len(data["seeds"]) >= 12
    for name, limit in app.LIMITS.items():
        if name == "token_deficit_over_floor":
            read = [r[name] for r in data["runs"]]
            altered = [r["altered_token_over_floor"] for r in data["runs"]]
            assert len(read) >= 8
            assert 1.5 * max(read) <= limit <= 0.8 * min(altered), (
                name, max(read), min(altered))
            continue
        sound = [r["sound"][name] for r in data["seeds"]]
        control = [r["control"][name] for r in data["seeds"]]
        # room on both sides: over every sound seed, under every control
        assert 1.3 * max(sound) <= limit, (name, max(sound))
        assert limit <= 0.7 * min(control), (name, min(control))
    for row in data["runs"]:                # the cell's own runs
        assert not_correct_by(dict(row, cache_dtypes=None)) == set(), row


def test_a_sound_seed_is_correct_and_the_control_is_not_on_any():
    for row in sweep()["seeds"]:
        assert not_correct_by(row["sound"]) == set(), row["seed"]
        failed = not_correct_by(row["control"])
        assert len(failed) >= 4, (row["seed"], failed)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct_on_any_seed(fault):
    rows = [r for r in sweep()["seeds"] if fault in r["faults"]]
    assert len(rows) >= 12
    for row in rows:
        failed = not_correct_by(row["faults"][fault])
        assert failed, (fault, row["seed"])
        if fault == "state_in_bfloat16":
            # a rounding of the state a step adds up where heads remember
            # and nothing upstream has amplified the floor: the first slot
            # after the 383 decoded positions. A numeric limit reads it on
            # every seed, and the exact check of the dtype besides
            assert {"state_over_floor_worst", "state_not_float32"} \
                <= failed, (row["seed"], failed)
            assert row["faults"][fault]["state_over_floor_worst"] \
                >= 1.25 * app.LIMITS["state_over_floor_worst"], row["seed"]
        else:
            assert len(failed) >= 3, (fault, row["seed"], failed)
