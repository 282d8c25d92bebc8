"""One test of this directory cannot pass once a training cell is added,
and the file that would have to follow is the benchmark's own, which the PR
that adds a cell may not edit (ISSUE 37 names it; PERF.md section 7, "First,
for the next benchmark issue"). It is marked here, strictly: the
`benchmark` PR that repairs the file makes it pass, and then has to delete
this mark."""

import pytest

KNOWN = {
    "test_bench_manifest.py::test_end_to_end_metric[train.tokens_per_s]":
        "benchmark/metrics/train.tokens_per_s.json repeats its manifest "
        "entry's `workloads` and is compared with `==`: PR 37 appended the "
        "cell qwen3next-train-s8192-ep16share in BENCHMARK.json and may not "
        "edit the copy",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, why in KNOWN.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))
