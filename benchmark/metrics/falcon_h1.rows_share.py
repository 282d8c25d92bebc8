from _dots3 import reader_of

_accepted = reader_of("batch.rows_share")
NEEDS = _accepted.NEEDS


def read(record, cell):
    return _accepted.read(record, cell)
