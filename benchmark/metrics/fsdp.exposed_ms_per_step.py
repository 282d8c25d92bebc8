def read(record, cell):
    reduced = record.get("trace") or {}
    if not reduced.get("periods"):
        return None
    return 1000.0 * reduced["exposed_collective_s"] / reduced["periods"]
