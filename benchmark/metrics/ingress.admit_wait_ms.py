from benchmark import spans as spans_mod

NEEDS = ("serve.request", "serve.proxy.admit")


def read(record, cell):
    return spans_mod.median_ms(record, cell, "serve.proxy.admit",
                               per_request=True)
