from _common import median
from _host import clear_of, window_flushes

NEEDS = ("serve.batch.flush",)


def read(record, cell):
    flushes = window_flushes(record, cell)
    if not flushes:
        return None
    gaps = [(f["ts"] - f["attrs"]["since_last_s"], f["ts"])
            for f in flushes if "since_last_s" in f["attrs"]]
    kept = [b - a for a, b in clear_of(record, gaps, "gaps",
                                       "batch.since_last_ms")]
    return 1000.0 * median(kept) if kept else None
