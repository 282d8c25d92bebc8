from benchmark import spans as spans_mod

NEEDS = ("serve.request", "serve.proxy.thread_wait")


def read(record, cell):
    return spans_mod.median_ms(record, cell, "serve.proxy.thread_wait",
                               per_request=True)
