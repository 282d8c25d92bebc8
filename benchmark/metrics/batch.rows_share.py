from benchmark import spans as spans_mod

NEEDS = ("serve.batch.flush",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    flushed = spans_mod.flushes(spans_mod.in_window(record, spans))
    room = sum(f["attrs"]["max_batch_size"] for f in flushed)
    if not room:
        return None
    return 100.0 * sum(f["attrs"]["rows"] for f in flushed) / room
