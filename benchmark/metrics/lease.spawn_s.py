from benchmark import spans as spans_mod

NEEDS = ("worker.spawn",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    spawns = [s["value"] for s in spans_mod.of_kind(spans, "worker.spawn")
              if s["attrs"].get("chips")]
    return spawns[-1] if spawns else None
