from benchmark import spans as spans_mod
from benchmark import trace as trace_mod

NEEDS = ("serve.batch.flush",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    idle = spans_mod.device_idle(spans_mod.trace_dir())
    gaps = trace_mod.union(spans_mod.gaps(spans_mod.flushes(spans)))
    if not idle or not gaps or not trace_mod.total(idle):
        return None
    return 100.0 * spans_mod.overlap(idle, gaps) / trace_mod.total(idle)
