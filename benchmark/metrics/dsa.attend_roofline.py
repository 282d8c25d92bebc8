from _dots3 import part_roofline


def read(record, cell):
    return part_roofline(record, cell, "sparse", "rt.mla.sparse")
