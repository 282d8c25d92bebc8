def read(record, cell):
    reduced = (record.get("trace") or {}).get("module_scopes") or {}
    seconds = (reduced.get("seconds") or {}).get("rt.mtp.module")
    if not seconds:
        return None          # no trace, or a program without the module
    return 100.0 * seconds / record["trace"]["busy_s"]
