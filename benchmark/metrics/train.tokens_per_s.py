def read(record, cell):
    window = record["window"]
    steps = window["steps"]
    # the window begins at 0 and ends when the last step's loss is on the host
    return len(steps) * window["tokens_per_step"] / steps[-1][1]
