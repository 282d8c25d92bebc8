import importlib


def read(record, cell):
    """Least time of the grouped matmuls at the rows the traced steps
    themselves routed here (their own counters, a step at a time) over
    those steps' Mosaic time."""
    reduced = (record.get("trace") or {}).get("scopes") or {}
    seconds = (reduced.get("mosaic_seconds") or {}).get("rt.moe.experts")
    window = record.get("window") or {}
    counters, traced = window.get("counters"), window.get("traced_steps")
    if not seconds or not counters or not traced \
            or record["facts"]["platform"] != "tpu":
        return None          # no trace, a rehearsal, or no such kernels
    here = counters["names"].index("moe_rows_here")
    family = importlib.import_module(
        "benchmark.ops_" + cell["config_data"]["family"])
    # the reduced window: whole periods from the first traced step on
    steps = counters["steps"][traced[0]:traced[0] + reduced["periods"]]
    least = sum(family.moe_experts_step_least_seconds(
        cell["config_data"], row[here], cell["traffic_data"]["remat"],
        record["facts"]["kind"])["seconds"] for row in steps)
    return 100.0 * least / seconds
