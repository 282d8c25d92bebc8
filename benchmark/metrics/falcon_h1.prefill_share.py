from _dots3 import reader_of

_accepted = reader_of("dots3.prefill_share")


def read(record, cell):
    return _accepted.read(record, cell)
