from benchmark import spans as spans_mod

NEEDS = ("serve.batch.wait",)


def read(record, cell):
    return spans_mod.median_ms(record, cell, "serve.batch.wait")
