from benchmark.hermetic import log
from _host import window_flushes

NEEDS = ("serve.batch.flush",)
SAID = ("cause", "rows", "newest_wait_s", "oldest_wait_s", "left_pending",
        "since_last_s")


def read(record, cell):
    flushes = window_flushes(record, cell)
    if not flushes:
        return None
    unfilled = [f for f in flushes
                if f["attrs"]["rows"] < f["attrs"]["max_batch_size"]]
    for f in unfilled:
        log(f"batch.unfilled_flush_share: at {f['ts']:.3f} " + " ".join(
            f"{k}={f['attrs'][k]}" for k in SAID if k in f["attrs"])
            + f" of max_batch_size={f['attrs']['max_batch_size']}")
    return 100.0 * len(unfilled) / len(flushes)
