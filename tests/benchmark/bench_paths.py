"""Where the benchmark lives, for the tests of this directory (the tests'
own directory is on ``sys.path`` under pytest's rootdir-less layout)."""

import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(CHECKOUT, "benchmark")
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def manifest_data() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)
