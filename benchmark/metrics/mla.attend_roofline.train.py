import importlib

from benchmark import trace_scopes


def read(record, cell):
    seconds = trace_scopes.scope_seconds(record, "rt.mla.dense")
    if not seconds or record["facts"]["platform"] != "tpu":
        return None          # no trace, a rehearsal, or no such scope
    family = importlib.import_module(
        "benchmark.ops_" + cell["config_data"]["family"])
    traffic = cell["traffic_data"]
    least = family.mla_attend_step_least_seconds(
        cell["config_data"], traffic["seq"],
        traffic["rows_per_chip"] * cell["chips"], record["facts"]["kind"])
    periods = record["trace"]["scopes"]["periods"]
    return 100.0 * least["seconds"] * periods / seconds
