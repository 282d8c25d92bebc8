from benchmark import spans as spans_mod
from _common import median

NEEDS = ("serve.batch.flush",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    gaps = spans_mod.gaps(spans_mod.flushes(
        spans_mod.in_window(record, spans)))
    return 1000.0 * median([b - a for a, b in gaps]) if gaps else None
