from _common import median

from benchmark import spans as spans_mod

NEEDS = ("train.step",)


def read(record, cell):
    """From the program's ``train.step`` spans where the session holds
    them, else from the same counters as the worker's loop fetched them
    (its record keeps every step's)."""
    spans = spans_mod.of_kind(spans_mod.load(record, cell) or [],
                              "train.step")
    steps = [s["attrs"] for s in spans_mod.in_window(record, spans)
             if s["attrs"].get("moe_load_mean")] if spans else []
    ratios = [a["moe_load_max"] / a["moe_load_mean"] for a in steps]
    counters = (record.get("window") or {}).get("counters")
    if not ratios and counters:
        top = counters["names"].index("moe_load_max")
        mean = counters["names"].index("moe_load_mean")
        ratios = [row[top] / row[mean] for row in counters["steps"]
                  if row[mean]]
    return median(ratios)
