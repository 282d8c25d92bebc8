from _common import median

from benchmark import spans as spans_mod

NEEDS = ("generate.call",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    return median(spans_mod.values(spans_mod.in_window(record, spans),
                                   "generate.call"))
