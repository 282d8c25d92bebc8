def read(record, cell):
    return record.get("admitted_max") or None
