from _dots3 import least


def read(record, cell):
    reduced = (record.get("trace") or {}).get("decode_scopes") or {}
    seconds = sum(s for scope, s in (reduced.get("seconds") or {}).items()
                  if scope.startswith(("rt.ssd.step", "rt.ssd.conv")))
    if not seconds:
        return None
    found = least(record, cell)
    if found is None:
        return None
    return 100.0 * found["ssd_seconds"] * reduced["periods"] / seconds
