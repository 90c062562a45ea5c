"""One hand-over to the next `benchmark` PR, and nothing else.

`test_benchmark_rehearsal.py::test_traced_run_reports_the_layers_and_the_device_times`
holds a traced tiny run to exactly the six per-layer names that PR 24 had
off the chip. PR 25 appended eight readers of the program's own spans to
`BENCHMARK.json`, and the tiny cell is made from that file, so the run now
reports fourteen. A PR that changes the program may not edit a file the
benchmark already has, so the enlarged set cannot go where the old one is:
`test_benchmark_spans.py::test_the_traced_line_holds_exactly_the_old_and_the_new_metrics`
holds the same run to the whole set (`==`, nothing left out) with the old
test's other assertions, and the old test is expected to fail on its stale
set until a `benchmark` PR enlarges it. `strict`: the day it passes again
this hook fails the run, and is deleted with the duplicate.
"""

import pytest

_STALE_SET = ("test_benchmark_rehearsal.py::"
              "test_traced_run_reports_the_layers_and_the_device_times")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_STALE_SET):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="exact set of six names from before PR 25's eight "
                       "span metrics; see this conftest's docstring"))
