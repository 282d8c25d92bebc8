from _dots3 import reader_of

_accepted = reader_of("generate_roofline.family")


def read(record, cell):
    return _accepted.read(record, cell)
