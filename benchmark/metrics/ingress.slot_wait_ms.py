from benchmark import spans as spans_mod

NEEDS = ("serve.request", "serve.handle.slot_wait")


def read(record, cell):
    return spans_mod.median_ms(record, cell, "serve.handle.slot_wait",
                               per_request=True)
