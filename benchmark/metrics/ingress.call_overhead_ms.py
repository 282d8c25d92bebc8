from benchmark import spans as spans_mod
from _common import median

NEEDS = ("serve.request", "serve.handle.call", "serve.replica.call")


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    both = [r["serve.handle.call"]["value"] - r["serve.replica.call"]["value"]
            for r in spans_mod.window_requests(record, spans)
            if "serve.handle.call" in r and "serve.replica.call" in r]
    return 1000.0 * median(both) if both else None
