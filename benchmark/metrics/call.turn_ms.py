import _calls

NEEDS = ("serve.request", "serve.handle.call", "serve.replica.call",
         "call.turn")


def read(record, cell):
    return _calls.median_ms(record, cell, NEEDS,
                            lambda r: r["call.turn"]["value"])
