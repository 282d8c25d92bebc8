from benchmark import trace_scopes


def read(record, cell):
    found = [trace_scopes.scope_seconds(record, scope)
             for scope in ("rt.dsa.index", "rt.mla.sparse")]
    if all(s is None for s in found):
        return None
    return 100.0 * sum(s or 0.0 for s in found) / record["trace"]["busy_s"]
