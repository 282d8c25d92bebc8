def read(record, cell):
    return record["setup_s"]
