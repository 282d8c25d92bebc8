from benchmark import spans as spans_mod


def read(record, cell):
    spans = spans_mod.load(record, cell)
    if not spans:
        return None
    fits = spans_mod.of_kind(spans, "train.fit")
    if not fits:
        return None
    fit = fits[-1]
    loops = [s for s in spans_mod.of_kind(spans, "train.loop")
             if s["ident"] == fit["ident"] and s["attrs"].get("rank") == 0]
    return loops[-1]["ts"] - fit["ts"] if loops else None
