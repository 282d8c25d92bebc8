import _calls

NEEDS = ("serve.request", "serve.handle.call", "serve.replica.call",
         "call.get")


def read(record, cell):
    def woken_to_value(r):
        got = r["call.get"]
        return _calls.end(got) - got["attrs"]["woken_ts"]
    return _calls.median_ms(record, cell, NEEDS, woken_to_value)
