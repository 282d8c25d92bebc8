from _common import median


def read(record, cell):
    return median([b["end"] - b["start"] for b in record["batches"]])
