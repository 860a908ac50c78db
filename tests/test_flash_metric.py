"""`kernel_ms.flash_attention.train` (PR 27): its file, its manifest
entry, and what `trace_kernel_ms` reads for it from a recorded cut of
the static BERT cell's traced run
(`benchmarks/tests/data/v5e_bert_flash_cut.md`).  Kept here and not in
`benchmarks/tests/` so that tier-1 runs it."""
import json
import os

import pytest

from benchmarks import harness, span_reduce
from benchmarks.readers import trace_kernel_ms

NAME = "kernel_ms.flash_attention.train"
DATA = os.path.join(harness.HERE, "tests", "data")
FLASH_CUT = os.path.join(DATA, "v5e_bert_flash_cut.xplane.pb")
SERVE_CUT = os.path.join(DATA, "v5e_serve_spans_cut.xplane.pb")
BERT_CELLS = ["bert-base-uncased.pretrain-static",
              "bert-base-uncased.pretrain-lazy"]


@pytest.fixture
def flash_cut(monkeypatch):
    """The BERT cut as the trace the harness has just written."""
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: FLASH_CUT)
    with open(os.path.join(DATA, "v5e_bert_flash_cut.expected.json")) as f:
        return json.load(f)


def _spec():
    return harness.load_json(harness.HERE, "layer_metrics", NAME + ".json")


def test_metric_file_and_manifest_entry_agree():
    spec = _spec()
    assert spec["reader"] == "trace_kernel_ms"
    assert spec["args"] == {"kernels": ["flash_attention"]}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    # appended, not inserted: right behind the last metric PR 26 left
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(NAME) == names.index(
        "idle_attributed_share.train") + 1
    for key in ("unit", "layer", "moves"):
        assert entry[key] == spec[key]
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    assert entry["workloads"] == BERT_CELLS
    # its cells report the end-to-end metric it moves
    (moved,) = [m for m in manifest["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])


def test_reads_the_three_flash_kernels_from_the_recorded_cut(flash_cut):
    """Two forward calls, one dq and one dkv lie wholly inside the cut:
    directions fold into the one kernel name, in milliseconds a step."""
    want = flash_cut["kernels"]["flash_attention"]
    assert want["count"] == 4
    run = {"samples": {"traced_steps": 1}}
    got = trace_kernel_ms.read(run, **_spec()["args"])
    assert got == pytest.approx(1e3 * want["s"], rel=1e-9)
    # 16 x 12 heads x 512 x 512 x 64 in bf16 on a v5e: a quarter to a
    # third of a millisecond a call, not the composite's two
    assert 0.2 < got / want["count"] < 0.4
    assert trace_kernel_ms.read({"samples": {"traced_steps": 2}},
                                **_spec()["args"]) == pytest.approx(got / 2)
    # the cut's other kernels still read as PR 26's metrics read them
    reduced = span_reduce.reduction()["kernels"]
    for kernel, row in flash_cut["kernels"].items():
        assert reduced[kernel]["count"] == row["count"]
        assert reduced[kernel]["s"] == pytest.approx(row["s"], rel=1e-9)
    for other in ("kernel_ms.matmul_epilogue.train",
                  "kernel_ms.layer_norm.train",
                  "kernel_ms.softmax_cross_entropy.train"):
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 other + ".json")
        assert trace_kernel_ms.read(run, **spec["args"]) > 0


def test_every_flash_call_in_the_cut_is_named(flash_cut):
    """The names the metric rests on: `%flash_attention_<direction>.N`
    opens each custom call's HLO text."""
    from jax.profiler import ProfileData
    names = [e.name.split(" = ")[0]
             for p in ProfileData.from_file(FLASH_CUT).planes
             if p.name.startswith(span_reduce.DEVICE_PLANE)
             for line in p.lines if line.name == "XLA Ops"
             for e in line.events if "flash_attention" in e.name]
    stems = sorted({n.rstrip("0123456789").rstrip(".") for n in names})
    assert stems == ["%flash_attention_bwd_dkv", "%flash_attention_bwd_dq",
                     "%flash_attention_fwd"]
    assert len(names) == flash_cut["kernels"]["flash_attention"]["count"]


@pytest.mark.parametrize("trace", [SERVE_CUT, None])
def test_a_trace_without_the_kernel_reads_nothing(monkeypatch, trace):
    """The parent commit's BERT step holds no flash kernel, PR 26's
    serving cut holds none either, and a run may have no trace: the
    reader returns None, so the result line leaves the metric out and
    nothing raises."""
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: trace)
    run = {"samples": {"traced_steps": 2}}
    assert trace_kernel_ms.read(run, **_spec()["args"]) is None
    assert trace_kernel_ms.read({"samples": {}}, **_spec()["args"]) is None
