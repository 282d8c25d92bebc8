from benchmark import spans as spans_mod
from benchmark.hermetic import log

NEEDS = ("train.fit", "train.loop")


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
    if loops:
        return loops[-1]["ts"] - fit["ts"]
    # The worker stores train.loop as the loop ends, milliseconds before
    # fit() kills it: a record that died with the worker never arrives.
    # The loop's own first line took a stamp 0.1 ms after the span began.
    entry = (record.get("stamps") or {}).get("entry")
    if entry is None:
        return None
    log(f"metric trainer.start_s: FALLBACK to the loop's own first-line "
        f"stamp (stamps.entry): train.fit {fit['ident']} but no train.loop "
        f"of rank 0 with it; the session holds {spans_mod.held(spans)}")
    return entry - fit["ts"]
