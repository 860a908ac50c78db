"""A CPU's profiler trace has no device plane, so in these tests the
recorded v5e cut of PR 26 stands in for "the trace the harness has just
written", the way ``test_benchmark.py`` lets the PR 23 cut stand in for
``trace_reduce``'s input.  ``test_span_reduce.py`` undoes it where it
tests the lookup itself."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SPAN_CUT = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_serve_spans_cut.xplane.pb")


@pytest.fixture(autouse=True)
def _recorded_cut_is_the_newest_trace(monkeypatch):
    from benchmarks import span_reduce
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: SPAN_CUT)
