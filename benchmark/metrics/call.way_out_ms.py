import _calls

NEEDS = ("serve.request", "serve.handle.call", "serve.replica.call")


def read(record, cell):
    def way_out(r):
        _calls.same_host(r["serve.handle.call"], r["serve.replica.call"])
        return _calls.end(r["serve.handle.call"]) - \
            _calls.end(r["serve.replica.call"])
    return _calls.median_ms(record, cell, NEEDS, way_out)
