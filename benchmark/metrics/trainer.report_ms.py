from benchmark import spans as spans_mod

NEEDS = ("train.report",)


def read(record, cell):
    return spans_mod.median_ms(record, cell, "train.report")
