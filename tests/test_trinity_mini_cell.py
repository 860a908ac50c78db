"""``benchmarks/tests/test_trinity_mini_cell.py`` in tier-1: its tests
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
from benchmarks.tests.test_trinity_mini_cell import *  # noqa: E402,F401,F403


def test_new_metric_files_resolve():  # noqa: F811
    """The benchmark's own copy of this test holds PR 32's five metrics
    to ``workloads == [CELL]``.  Since PR 35 four of them list a second
    cell, as their readers were built to allow (they take the work
    functions from the cell's own family), and a PR that adds a cell may
    not edit a file under ``benchmarks/``: tier-1 runs this copy, which
    asks that the cell is listed, and the next ``benchmark`` PR repairs
    the file (PERF.md section 7)."""
    import importlib
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}  # noqa: F405
    for name in NEW_METRICS:  # noqa: F405
        entry = per_layer[name]
        spec = harness.load_json(harness.HERE, "layer_metrics",  # noqa: F405
                                 name + ".json")
        assert CELL in entry["workloads"]  # noqa: F405
        assert (entry["unit"], entry["layer"], entry["moves"]) \
            == (spec["unit"], spec["layer"], "itl_p95_ms")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        assert callable(reader.read)
        for fn in (spec["args"].get("flops"), spec["args"].get("bytes")):
            assert fn is None or callable(getattr(family, fn))  # noqa: F405
    assert per_layer["window_read_share.serve"]["workloads"] == [CELL]  # noqa: F405
