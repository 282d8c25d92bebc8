from benchmark import ops


def read(record, cell):
    reduced = record.get("trace") or {}
    traffic = cell["traffic_data"]
    if not reduced.get("module_s"):
        return None
    try:
        least = ops.generate_least_seconds(
            cell["config_data"], traffic["max_batch_size"],
            traffic["prompt_tokens"], traffic["new_tokens"],
            cell["config_data"]["param_dtype"], record["facts"]["kind"])
    except ops.UnknownDevice:
        return None
    return 100.0 * least["seconds"] * reduced["periods"] / reduced["module_s"]
