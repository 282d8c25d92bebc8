from benchmark import spans as spans_mod
from _common import median
from _host import clear_of

NEEDS = ("train.report",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    lo, _ = spans_mod.window_bounds(record)
    rank = {s["attrs"]["span"]: s["attrs"].get("rank")
            for s in spans_mod.of_kind(spans, "train.loop")}
    # a step of the window: a report of rank 0's loop (where the session
    # holds no train.loop yet, of any) whose period began inside it
    periods = [(s["ts"] - s["attrs"]["period_s"], s["ts"])
               for s in spans_mod.of_kind(spans_mod.in_window(record, spans),
                                          "train.report")
               if "period_s" in s["attrs"]
               and rank.get(s["attrs"].get("parent"), 0) == 0
               and s["ts"] - s["attrs"]["period_s"] >= lo]
    kept = [b - a for a, b in clear_of(record, periods, "steps",
                                       "train.period_max_over_median")]
    return max(kept) / median(kept) if kept else None
