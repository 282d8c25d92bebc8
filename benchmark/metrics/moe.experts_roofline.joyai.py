from _dots3 import reader_of

_accepted = reader_of("moe.experts_roofline")


def read(record, cell):
    return _accepted.read(record, cell)
