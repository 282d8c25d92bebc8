"""``serve.batch`` alone, no cluster: one batch at a time, and a batch that
waited through a running one assembles from that one's END, so a closed
loop's callers find each other again after one of them came late."""

import threading
import time

import pytest

from ray_tpu import serve


class Model:
    """A handler as a replica writes one: one call at a time, ``call_s``
    a call whatever its rows."""

    def __init__(self, rows: int, window_s: float, call_s: float):
        self.calls, self.lock, self.call_s = [], threading.Lock(), call_s
        model = self

        class Handler:
            @serve.batch(max_batch_size=rows, batch_wait_timeout_s=window_s)
            def handle(self, items):
                with model.lock:
                    model.calls.append(sorted(items))
                    time.sleep(model.call_s)
                return [i * 10 for i in items]
        self.handler = Handler()

    def ask(self, item):
        assert self.handler.handle(item) == item * 10


def closed_loop(model, callers, rounds, late=None):
    """Every caller asks ``rounds`` times, each time as soon as it has its
    answer; ``late`` = (caller, seconds): that caller starts late."""
    def caller(c):
        if late and c == late[0]:
            time.sleep(late[1])
        for _ in range(rounds):
            model.ask(c)
    threads = [threading.Thread(target=caller, args=(c,))
               for c in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)


def test_a_full_batch_goes_at_once_and_a_short_one_after_the_window():
    model = Model(rows=4, window_s=0.15, call_s=0.0)
    t0 = time.monotonic()
    closed_loop(model, 4, 1)
    assert model.calls == [[0, 1, 2, 3]]
    assert time.monotonic() - t0 < 0.12           # full: no window waited
    t0 = time.monotonic()
    closed_loop(model, 2, 1)
    assert model.calls[1:] == [[0, 1]]
    assert 0.14 < time.monotonic() - t0 < 0.6


def test_a_late_caller_rejoins_the_others_after_one_call():
    """Eight callers in a closed loop over a call of 0.3 s, the window 0.1
    s; one of them starts 0.2 s late, inside the first call. It waits that
    call out, and the batch it is in assembles when the call ENDS: the
    seven just answered join it, and every later batch is whole. (Each
    arrival's own timer, the form until PR 51, flushed the late one alone
    behind the seven, and the loop ran 7 / 1 / 7 / 1 to its end.)"""
    model = Model(rows=8, window_s=0.1, call_s=0.3)
    closed_loop(model, 8, 4, late=(7, 0.2))
    sizes = [len(c) for c in model.calls]
    assert sizes[0] == 7
    assert sizes[1:] == [8, 8, 8, 1], sizes   # its 4th ask has no company
    assert sum(sizes) == 32


def test_one_batch_at_a_time_and_none_over_the_size():
    """Twenty callers at once into batches of 8 over a slow call: the
    handler never sees more than 8 rows, nor a second batch while one
    runs, and nobody is left out."""
    model = Model(rows=8, window_s=0.05, call_s=0.1)
    closed_loop(model, 20, 1)
    sizes = [len(c) for c in model.calls]
    assert max(sizes) <= 8 and sum(sizes) == 20
    assert sizes[0] == 8 and sizes[1] == 8        # what waited went whole
    assert sorted(i for c in model.calls for i in c) == list(range(20))


# (rows, call_s, [(caller, it arrives at, seconds)]) and, for the flush of
# ``caller 2``: its cause and its counters. The window is 0.1 s; caller 0
# always goes first and alone, so that every case has a flush before it.
ARRIVALS = {
    # 1 arrives 0.1 s after caller 0's answer, 2 follows 0.04 s behind and
    # does not fill the batch: 1's timer sends both
    "window": (3, 0.02, [(0, 0.0), (1, 0.25), (2, 0.29)],
               {"rows": 2, "left_pending": 0, "oldest_wait_s": 0.1,
                "newest_wait_s": 0.06, "since_last_s": 0.23}),
    # the same arrivals into batches of two: 2 fills it
    "full": (2, 0.02, [(0, 0.0), (1, 0.25), (2, 0.29)],
             {"rows": 2, "left_pending": 0, "oldest_wait_s": 0.04,
              "newest_wait_s": 0.0, "since_last_s": 0.17}),
    # a call of 0.3 s began at 0.1 (caller 0's window); 1, 2, 3 arrive
    # inside it: its end sends 1 and 2 at once and leaves 3
    "after_running": (2, 0.3, [(0, 0.0), (1, 0.2), (2, 0.25), (3, 0.3)],
                      {"rows": 2, "left_pending": 1, "oldest_wait_s": 0.2,
                       "newest_wait_s": 0.15, "since_last_s": 0.0}),
}


@pytest.mark.parametrize("cause", sorted(ARRIVALS))
def test_a_flush_says_why_it_went_when_it_did(cause):
    from ray_tpu.util import events
    rows, call_s, arrivals, expected = ARRIVALS[cause]
    events.reset_for_tests()
    try:
        model = Model(rows=rows, window_s=0.1, call_s=call_s)
        t0 = time.monotonic()

        def caller(c, at):
            time.sleep(max(0.0, t0 + at - time.monotonic()))
            model.ask(c)
        threads = [threading.Thread(target=caller, args=a) for a in arrivals]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        flushes = [ev[4] for ev in events.snapshot()
                   if ev[1] == "serve.batch.flush"]
    finally:
        events.reset_for_tests()
    assert model.calls[:2] == [[0], [1, 2]]
    first, flush = flushes[0], flushes[1]
    assert first["cause"] == "window" and first["rows"] == 1
    assert "since_last_s" not in first and first["left_pending"] == 0
    assert first["oldest_wait_s"] == pytest.approx(0.1, abs=0.05)
    assert first["newest_wait_s"] == first["oldest_wait_s"]
    assert flush["cause"] == cause
    for key, value in expected.items():
        assert flush[key] == pytest.approx(value, abs=0.06), (key, flush)
    if cause == "after_running":        # 3 goes alone, a window later
        assert flushes[2]["cause"] == "after_running"
        assert flushes[2]["since_last_s"] == pytest.approx(0.1, abs=0.06)


def test_an_error_fails_its_batch_and_not_the_next():
    calls = []

    class Handler:
        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.05)
        def handle(self, items):
            calls.append(list(items))
            if "bad" in items:
                raise ValueError("no")
            return items
    h = Handler()
    with pytest.raises(ValueError):
        h.handle("bad")
    assert h.handle("good") == "good"
    assert calls == [["bad"], ["good"]]


def test_an_old_table_routes_on_and_a_failed_fetch_is_the_next_requests():
    """A handle whose routing table is over a second old answers from it
    and fetches the next one on ONE thread; where that fetch fails, the
    next request makes the round trip itself and raises what it said."""
    from ray_tpu.serve.api import DeploymentHandle
    handle = DeploymentHandle("lm")
    fetches, gate = [], threading.Event()

    def fetch():
        fetches.append(threading.current_thread().name)
        gate.wait(5)
        raise ConnectionError("the controller is away")
    handle._fetch_routing = fetch
    handle._replicas, handle._ts = ["a replica"], time.monotonic() - 2.0
    for _ in range(3):
        handle._refresh()               # none of them waits, none raises
    assert fetches == ["serve-handle-refresh"]
    gate.set()
    deadline = time.monotonic() + 5
    while handle._refreshing and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not handle._refreshing and handle._ts == 0.0
    with pytest.raises(ConnectionError):
        handle._refresh()
    assert fetches[1:] == [threading.current_thread().name]
