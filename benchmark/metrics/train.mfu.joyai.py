from _dots3 import reader_of

_accepted = reader_of("train.mfu.family")


def read(record, cell):
    return _accepted.read(record, cell)
