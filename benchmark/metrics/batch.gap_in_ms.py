from _common import median
import _calls

NEEDS = ("serve.batch.flush", "serve.batch.wait", "serve.request")


def read(record, cell):
    parts = _calls.gap_parts(record, cell)
    return 1000.0 * median([p[1] for p in parts]) if parts else None
