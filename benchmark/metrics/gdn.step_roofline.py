from _dots3 import least


def read(record, cell):
    reduced = (record.get("trace") or {}).get("decode_scopes") or {}
    seconds = sum(s for scope, s in (reduced.get("seconds") or {}).items()
                  if scope.startswith(("rt.gdn.step", "rt.gdn.conv")))
    if not seconds:
        return None
    found = least(record, cell)
    if found is None:
        return None
    return 100.0 * found["rule_seconds"] * reduced["periods"] / seconds
