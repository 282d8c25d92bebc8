from benchmark import spans as spans_mod

NEEDS = ("generate.call",)


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    calls = [s["attrs"]["exit_steps_mean"]
             for s in spans_mod.of_kind(spans_mod.in_window(record, spans),
                                        "generate.call")
             if s["attrs"].get("exit_steps_mean") is not None]
    return sum(calls) / len(calls) if calls else None
