import importlib

from _common import clean_steps

from benchmark import ops


def read(record, cell):
    try:
        peak = ops.peaks(record["facts"]["kind"])["bf16_flops_per_s"]
    except ops.UnknownDevice:
        return None                     # a rehearsal on the CPU
    steps = clean_steps(record["window"])
    if not steps:
        return None
    tokens_per_s = record["window"]["tokens_per_step"] * len(steps) \
        / sum(period for period, _ in steps)
    family = importlib.import_module(
        "benchmark.ops_" + cell["config_data"]["family"])
    per_token = family.train_ops_per_token(cell["config_data"],
                                           cell["traffic_data"]["seq"])
    return 100.0 * per_token * tokens_per_s / (cell["chips"] * peak)
