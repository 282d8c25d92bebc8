from benchmark import spans as spans_mod

NEEDS = ("generate.call",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    calls = [s["attrs"] for s in spans_mod.of_kind(
        spans_mod.in_window(record, spans), "generate.call")
        if "cache_bytes_state" in s["attrs"]]
    if not calls:
        return None
    a = calls[-1]
    return 100.0 * (a["cache_bytes_state"] + a["cache_bytes_tail"]) \
        / a["cache_bytes"]
