from benchmark import spans as spans_mod

NEEDS = ("init.probe",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    probes = spans_mod.values(spans, "init.probe")
    return probes[-1] if probes else None
