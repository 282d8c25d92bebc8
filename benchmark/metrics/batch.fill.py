def read(record, cell):
    batches = record["batches"]
    padded = sum(b["padded_rows"] for b in batches)
    return 100.0 * sum(b["rows"] for b in batches) / padded if padded \
        else None
