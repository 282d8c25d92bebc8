from _common import clean_steps, median


def read(record, cell):
    host = [period - device for period, device in
            clean_steps(record["window"])]
    return 1000.0 * median(host) if host else None
