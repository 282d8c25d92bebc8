def read(record, cell):
    window = record["window"]
    done = [r for r in window["rows"] if r["ok"]]
    if not done:
        return None
    # every request sent in the window, until the last reply has come
    return sum(r["units"] for r in done) / \
        (max(r["last"] for r in done) - window["start"])
