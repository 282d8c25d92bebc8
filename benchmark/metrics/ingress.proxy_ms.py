from _common import median, served


def read(record, cell):
    both_ways = [(enter - r["send"]) + (r["last"] - exit_)
                 for r, (enter, exit_), _ in served(record)]
    return 1000.0 * median(both_ways) if both_ways else None
