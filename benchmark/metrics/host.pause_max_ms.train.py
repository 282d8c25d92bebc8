from benchmark import spans as spans_mod
from _host import pause_max_ms, processes

NEEDS = ("train.report", "host.watch")


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    reports = spans_mod.of_kind(spans_mod.in_window(record, spans),
                                "train.report")
    if not reports:
        return None
    return pause_max_ms(record, spans, processes(reports),
                        processes(reports), "host.pause_max_ms.train")
