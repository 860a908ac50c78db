"""``benchmarks/tests/test_benchmark.py`` in tier-1: its tests
and the two autouse fixtures of the benchmark's conftests, under this
directory's environment (CPU, 8 virtual devices, compile cache off), so
that a change to the program that breaks ``benchmarks/run.py``'s use
of it fails here and not in a chip check."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmarks.conftest import _tiny_minicpm_sala  # noqa: E402,F401
from benchmarks.tests.conftest import (  # noqa: E402,F401
    _recorded_cut_is_the_newest_trace)
from benchmarks.tests.test_benchmark import *  # noqa: E402,F401,F403


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):  # noqa: F811
    """The benchmark's own test of this name, every assertion of it,
    with one change: ``wanted`` leaves out the metrics of PR 37 that
    the recorded GPT-2 trace of PR 26 cannot give.  They join a trace
    with the map of the program that ran
    (``observability.program_blocks()``) or read a span attribute that
    program did not write yet; on that trace their readers give
    ``None``, as they must beside an older tree.  A PR that is no
    ``benchmark`` PR may not edit a file under ``benchmarks/``, so the
    original stays red under ``pytest benchmarks/tests`` until the next
    ``benchmark`` PR gives it a trace with a map (PERF.md section 7);
    ``test_traced_run_reports_every_per_layer_metric`` asserts the same
    on the cut that has one, with nothing left out."""
    cell = "gpt2-large.decode-closed32"
    real = trace_reduce.reduce  # noqa: F405
    monkeypatch.setattr(trace_reduce, "reduce",  # noqa: F405
                        lambda path: real(FIXTURE))  # noqa: F405
    result = harness.run_cell(cell, 11, 1.0, 1,  # noqa: F405
                              shrink=tiny_for(cell))  # noqa: F405
    specs = harness.metric_specs(MANIFEST, "per_layer", cell)  # noqa: F405
    no_map = {m["name"] for m, spec in specs
              if spec["reader"] == "trace_block_ms"} \
        | {"traced_context_tokens.serve"}
    assert len(no_map) == 8
    wanted = {m["name"] for m, _ in specs} - no_map
    assert set(result["metrics"]) == wanted
    assert result["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert 0 < result["metrics"]["batch_occupancy.serve"]["value"] <= 100
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    assert result["metrics"]["traced_chunk_tokens.serve"]["value"] > 0
    assert 0 <= result["metrics"]["idle_attributed_share.serve"][
        "value"] <= 100
