from benchmark import spans as spans_mod
from _common import median

NEEDS = ("serve.batch.flush",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    calls = [f["value"] for f in spans_mod.flushes(
        spans_mod.in_window(record, spans))]
    return max(calls) / median(calls) if calls else None
