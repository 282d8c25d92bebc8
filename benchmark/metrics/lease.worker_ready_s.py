def read(record, cell):
    stamps = record["stamps"]
    return stamps["entry"] - stamps["called"]
