from _common import latencies, p95


def read(record, cell):
    return p95(latencies(record, "first"))
