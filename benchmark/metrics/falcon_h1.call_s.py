from _dots3 import reader_of

_accepted = reader_of("loop.call_s")
NEEDS = _accepted.NEEDS


def read(record, cell):
    return _accepted.read(record, cell)
