from _common import median

from benchmark import spans as spans_mod

NEEDS = ("train.step",)
COUNTER = "moe_count_max_over_mean"


def read(record, cell):
    """From the program's ``train.step`` spans where the session holds
    them, else from the same counter as the worker's loop fetched it (its
    record keeps every step's)."""
    spans = spans_mod.of_kind(spans_mod.load(record, cell) or [],
                              "train.step")
    ratios = [s["attrs"][COUNTER]
              for s in spans_mod.in_window(record, spans)
              if s["attrs"].get(COUNTER)] if spans else []
    counters = (record.get("window") or {}).get("counters")
    if not ratios and counters and COUNTER in counters["names"]:
        at = counters["names"].index(COUNTER)
        ratios = [row[at] for row in counters["steps"] if row[at]]
    return median(ratios)
