from _common import median, served


def read(record, cell):
    waits = [batch["start"] - enter
             for _, (enter, _), batch in served(record)]
    return 1000.0 * median(waits) if waits else None
