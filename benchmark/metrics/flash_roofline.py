from _common import MetricFault
from benchmark import ops


def read(record, cell):
    reduced = record.get("trace") or {}
    if not reduced or record["facts"]["platform"] != "tpu":
        return None              # no trace, or a rehearsal: no kernel ran
    traffic = cell["traffic_data"]
    least = ops.flash_step_least_seconds(
        cell["config_data"], traffic["seq"], traffic["rows_per_chip"],
        traffic["remat"], record["facts"]["kind"])
    # Only where the trace shows the calls the arithmetic counts: a program
    # that calls the kernel another number of times needs new arithmetic,
    # not a share computed from the old, and not a metric that drops out.
    want = least["calls"] * reduced["periods"]
    if reduced.get("mosaic_calls") != want or not reduced.get("mosaic_s"):
        raise MetricFault(
            f"the trace shows {reduced.get('mosaic_calls')} Mosaic calls in "
            f"{reduced['periods']} step(s), benchmark/ops.py counts {want}: "
            "flash_roofline's arithmetic no longer describes the program")
    return 100.0 * least["seconds"] * reduced["periods"] / reduced["mosaic_s"]
