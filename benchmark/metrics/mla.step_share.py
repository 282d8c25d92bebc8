from benchmark import trace_scopes


def read(record, cell):
    seconds = trace_scopes.scope_seconds(record, "rt.mla.")
    if seconds is None:
        return None
    return 100.0 * seconds / record["trace"]["busy_s"]
