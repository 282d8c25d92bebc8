import importlib

from benchmark import ops


def read(record, cell):
    reduced = record.get("trace") or {}
    traffic, config = cell["traffic_data"], cell["config_data"]
    if not reduced.get("module_s"):
        return None
    family = importlib.import_module("benchmark.ops_" + config["family"])
    try:
        least = family.generate_least_seconds(
            config, traffic["max_batch_size"], traffic["prompt_tokens"],
            traffic["new_tokens"], config["param_dtype"],
            record["facts"]["kind"])
    except ops.UnknownDevice:
        return None                     # a rehearsal on the CPU
    return 100.0 * least["seconds"] * reduced["periods"] / reduced["module_s"]
