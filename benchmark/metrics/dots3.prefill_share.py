def read(record, cell):
    reduced = record.get("trace") or {}
    seconds = (reduced.get("phases") or {}).get("seconds") or {}
    if "rt.generate.prefill" not in seconds or not reduced.get("busy_s"):
        return None
    return 100.0 * seconds["rt.generate.prefill"] / reduced["busy_s"]
