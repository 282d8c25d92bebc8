import _calls

NEEDS = ("serve.request", "serve.handle.call", "serve.replica.call")


def read(record, cell):
    def way_in(r):
        _calls.same_host(r["serve.handle.call"], r["serve.replica.call"])
        return r["serve.replica.call"]["ts"] - r["serve.handle.call"]["ts"]
    return _calls.median_ms(record, cell, NEEDS, way_in)
