from benchmark import trace_scopes


def read(record, cell):
    seconds = trace_scopes.scope_seconds(record, "rt.ssd.")
    if seconds is None or not record["trace"].get("busy_s"):
        return None
    return 100.0 * seconds / record["trace"]["busy_s"]
