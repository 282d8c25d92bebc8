"""benchmark/ops.py against values worked by hand from the published
sizes (the arithmetic is written out so that a reader can redo it)."""

import pytest

from bench_paths import config
from benchmark import ops

M2 = config("mistral-7b-v0.3-l2")
M24 = config("mistral-7b-v0.3-l24")
I2 = config("internlm2-1.8b")


def test_parameter_counts():
    # Mistral layer: q,o 4096x4096 each, k,v 4096x1024 each = 41,943,040;
    # FFN 3 x 4096 x 14336 = 176,160,768; two norms 8,192.
    p = ops.param_counts(M2)
    assert p["attn"] == 41_943_040 and p["mlp"] == 176_160_768
    assert p["embed"] == p["head"] == 32768 * 4096 == 134_217_728
    assert p["total"] == 2 * 134_217_728 + 2 * (218_103_808 + 8192) + 4096 \
        == 704_663_552
    assert ops.param_counts(M24)["total"] == 5_503_127_552
    # InternLM2-1.8B: q,o 2048x2048, k,v 2048x1024 = 12,582,912; FFN
    # 3 x 2048 x 8192 = 50,331,648; embed = head = 92544 x 2048.
    q = ops.param_counts(I2)
    assert q["layer_matmul"] == 62_914_560
    assert q["embed"] == 189_530_112
    assert q["total"] == 1_889_110_016


def test_training_operations_per_token():
    # forward, S = 2048: layers 2 x 2 x 218,103,808 = 872,415,232;
    # causal attention 2 x 2048 x 32 x 128 x 2 layers = 33,554,432 (half of
    # the full square's 67,108,864); head 2 x 134,217,728 = 268,435,456.
    f = ops.forward_ops_per_token(M2, 2048)
    assert f == {"layers": 872_415_232, "attention": 33_554_432,
                 "head": 268_435_456, "total": 1_174_405_120}
    assert ops.train_ops_per_token(M2, 2048) == 3 * 1_174_405_120
    # the head is ~23% of the work at 2 layers and ~2% at 32
    assert round(100 * f["head"] / f["total"]) == 23
    full = ops.forward_ops_per_token(dict(M2, num_hidden_layers=32), 2048)
    assert round(100 * full["head"] / full["total"]) == 2
    g = ops.forward_ops_per_token(I2, 2048)
    assert g["layers"] == 2 * 62_914_560 * 24 == 3_019_898_880
    assert g["attention"] == 2 * 2048 * 16 * 128 * 24 == 201_326_592
    assert g["head"] == 2 * 189_530_112
    assert ops.train_ops_per_token(I2, 2048) == 10_800_857_088
    # bench.py's 6N + 12 L d S would have credited a causal kernel with
    # twice its attention work
    assert 12 * 2 * 4096 * 2048 == 2 * 3 * f["attention"]


def test_mfu_at_a_known_rate():
    # 32,450 tokens/s x 3,523,215,360 over 197e12 = 58.03%
    mfu = 100 * 32450 * ops.train_ops_per_token(M2, 2048) / 197e12
    assert mfu == pytest.approx(58.03, abs=0.01)


def test_flash_step_least_time():
    # one unit = 2 x 2048^2 x 128 / 2 per head x 32 heads x 8 rows
    #          = 137,438,953,472; per layer 2 forward calls (remat) x 2 +
    # dkv 4 + dq 3 = 11 units; 2 layers
    least = ops.flash_step_least_seconds(M2, 2048, 8, True, "TPU v5 lite")
    assert least["ops"] == 22 * 137_438_953_472
    assert least["calls"] == 8 and least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(22 * 137_438_953_472 / 197e12)
    no_remat = ops.flash_step_least_seconds(M2, 2048, 8, False,
                                            "TPU v5 lite")
    assert no_remat["calls"] == 6
    assert no_remat["ops"] == 18 * 137_438_953_472
    four = ops.flash_step_least_seconds(I2, 2048, 4, True, "TPU v5 lite")
    assert four["calls"] == 4 * 24
    assert four["ops"] == 11 * 24 * (2048 * 2048 * 128 * 16 * 4)


def test_generate_least_time():
    g = ops.generate_least_seconds(M24, 32, 512, 128, "bfloat16",
                                   "TPU v5 lite")
    # weights read by a decode step: 24 layers + head, bfloat16
    assert g["weight_bytes"] == (24 * 218_103_808 + 134_217_728) * 2 \
        == 10_737_418_240
    # prefill: 16,384 tokens x (layers 2 x 218,103,808 x 24 + causal
    # attention 2 x 512 x 4096 x 24) + head on 32 last positions
    assert g["prefill_ops"] == 16384 * (10_468_982_784 + 100_663_296) \
        + 32 * 268_435_456
    assert g["prefill_seconds"] == pytest.approx(g["prefill_ops"] / 197e12)
    # decode is bound by bytes: step 0 reads the weights and 513 positions
    # of k and v: 32 rows x 513 x (2 x 24 x 8 x 128 x 2 bytes)
    first = 10_737_418_240 + 32 * 513 * 98_304
    last = 10_737_418_240 + 32 * 640 * 98_304
    assert g["decode_bytes"] == pytest.approx(128 * (first + last) / 2)
    assert g["decode_seconds"] == pytest.approx(g["decode_bytes"] / 819e9)
    assert g["seconds"] == pytest.approx(2.8407, abs=1e-3)


def test_unknown_device_is_an_error():
    assert ops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert ops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ops.UnknownDevice):
        ops.peaks("cpu")
    with pytest.raises(ops.UnknownDevice):
        ops.flash_step_least_seconds(M2, 2048, 8, True, "TPU v9")
