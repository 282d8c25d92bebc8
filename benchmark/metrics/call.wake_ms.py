import _calls

NEEDS = ("serve.request", "serve.handle.call", "serve.replica.call",
         "call.return", "call.get")


def read(record, cell):
    def wake(r):
        got, stored = r["call.get"], r["call.return"]
        _calls.same_host(got, stored)
        return got["attrs"]["woken_ts"] - _calls.end(stored)
    return _calls.median_ms(record, cell, NEEDS, wake)
