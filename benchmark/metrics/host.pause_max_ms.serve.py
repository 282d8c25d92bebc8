from benchmark import spans as spans_mod
from _calls import same_host
from _host import pause_max_ms, processes

NEEDS = ("serve.batch.flush", "serve.request", "host.watch")


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    inside = spans_mod.in_window(record, spans)
    flushes = spans_mod.flushes(inside)
    requests = spans_mod.of_kind(inside, "serve.request")
    if not flushes or not requests:
        return None
    # the window is the callers', a pause is stamped by its own host's
    # clock: on one host they are one clock
    same_host(flushes[0], requests[0])
    # the app's record gives the profiler's intervals as its replica's
    return pause_max_ms(record, spans, processes(flushes + requests),
                        processes(flushes), "host.pause_max_ms.serve")
