from _common import idle_share_pct


def read(record, cell):
    return idle_share_pct(record)
