"""``benchmarks/tests/test_block_metrics.py`` in tier-1: its tests
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
from benchmarks.tests.test_block_metrics import *  # noqa: E402,F401,F403

import importlib  # noqa: E402

import pytest  # noqa: E402

#: the cells added since PR 37 that list a block metric beside ISSUE
#: 37's (their own tests hold which)
LATER_CELLS = {"LongCat-Flash-Omni.longctx16k-closed32"}
#: the per-layer metrics added since PR 37
LATER_METRICS = 3


@pytest.mark.parametrize("name", sorted(NEW_METRICS))  # noqa: F405
def test_new_metric_files_resolve(name):  # noqa: F811
    """The benchmark's own copy of this test pins each block metric's
    list of cells and the number of per-layer metrics.  Since PR 40 a
    fifth serving cell is appended to the serving lists and three
    metrics follow them, and a PR that adds a cell may not edit a file
    under ``benchmarks/``: tier-1 runs this copy, with every assertion
    of the original but the pinned list and count, and the next
    ``benchmark`` PR repairs the file (PERF.md section 7)."""
    (entry,) = [m for m in MANIFEST["per_layer"]  # noqa: F405
                if m["name"] == name]
    spec = harness.load_json(harness.HERE, "layer_metrics",  # noqa: F405
                             name + ".json")
    pinned = NEW_METRICS[name]  # noqa: F405
    assert entry["workloads"][:len(pinned)] == pinned
    assert set(entry["workloads"][len(pinned):]) <= LATER_CELLS
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        spec["unit"], spec["layer"], spec["moves"])
    assert entry["layer"] in ("model step", "serving engine", "device")
    assert callable(importlib.import_module(
        "benchmarks.readers." + spec["reader"]).read)
    # each cell reports the end-to-end metric the new one moves
    (moved,) = [m for m in MANIFEST["end_to_end"]  # noqa: F405
                if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    if name.startswith("traced_"):
        assert "not a goal" in spec["what"]
    assert len(MANIFEST["per_layer"]) == (  # noqa: F405
        38 + len(NEW_METRICS) + LATER_METRICS)  # noqa: F405
