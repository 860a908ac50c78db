"""PR 37's readers: a trace joined with the program's map of its
instructions (``trace_block_ms``), and what the traced steps carried
(``trace_span_attr``), held to a recorded v5e cut with its map.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import importlib
import json
import os

import pytest

from benchmarks import harness, span_reduce, trace_reduce
from benchmarks.readers import (trace_block_ms, trace_idle_attributed,
                                trace_span_attr)

DATA = os.path.join(os.path.dirname(__file__), "data")
CUT = os.path.join(DATA, "v5e_serve_blocks_cut.xplane.pb")
OLD_CUTS = ["v5e_serve_cut", "v5e_serve_spans_cut", "v5e_bert_flash_cut"]
MANIFEST = harness.load_manifest()
GPT, SALA, TRINITY, QWEN = (
    "gpt2-large.decode-closed32", "MiniCPM-SALA.longdoc-closed32",
    "Trinity-Mini.mixedlen-closed32",
    "Qwen3-Next-80B-A3B-Instruct.longctx24k-closed32")
STATIC = "bert-base-uncased.pretrain-static"
SERVING = [GPT, SALA, TRINITY, QWEN]
#: metric -> the cells that list it (ISSUE 37's table)
NEW_METRICS = {
    "block_ms.attention.serve": SERVING,
    "block_ms.attention_chunk.serve": [SALA, TRINITY, QWEN],
    "block_ms.kv_write.serve": SERVING,
    "block_ms.recurrent.serve": [SALA, QWEN],
    "block_ms.ffn.serve": SERVING,
    "block_ms.head.serve": SERVING,
    "block_ms.sampler.serve": SERVING,
    "block_ms.experts.serve": [TRINITY, QWEN],
    "layout_copy_ms.serve": SERVING,
    "block_attributed_share.serve": SERVING,
    "block_attributed_share.train": [STATIC],
    "block_ms.attention.train": [STATIC],
    "block_ms.ffn.train": [STATIC],
    "block_ms.head.train": [STATIC],
    "block_ms.optimizer.train": [STATIC],
    "traced_chunk_tokens.serve": SERVING,
    "traced_context_tokens.serve": SERVING,
    "idle_attributed_share.serve": SERVING,
}


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture
def maps():
    return load("v5e_serve_blocks_cut.blocks.json")


@pytest.fixture
def blocks_cut_is_the_run(monkeypatch, maps):
    """The recorded cut is the newest trace and its map the running
    program's, as after a ``--trace 1`` run of that cell."""
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: CUT)
    monkeypatch.setattr(trace_block_ms, "program_maps", lambda: maps)
    span_reduce._cache.clear()
    trace_block_ms._cache.clear()
    yield
    span_reduce._cache.clear()
    trace_block_ms._cache.clear()


def read_metric(name, run):
    spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    return reader.read(run, **spec.get("args", {}))


def test_names_and_patterns():
    assert trace_block_ms.instruction_of(
        "%fusion.348 = bf16[1,1520,73448]{2,1,0:T(8,128)(2,1)} "
        "fusion(bf16[1520,4096] %x), kind=kOutput") == "fusion.348"
    assert trace_block_ms.instruction_of(
        "%copy-done.3 = f32[32]{0} copy-done((f32[32]) %s)") == "copy-done.3"
    match = trace_block_ms._matches
    assert match(("attention/chunk", "fusion"), ["attention*"], None)
    assert not match(("kv_write", "copy"), ["attention*"], None)
    assert match(("", "copy"), ["*"], ["copy", "transpose"])
    assert not match(("", "copy"), ["?*"], None)
    assert not match(("kv_write", "fusion"), ["*"], ["copy", "transpose"])


def test_reduction_of_the_recorded_cut(maps):
    """The whole join held to the numbers it gave when the trace was
    cut (``v5e_serve_blocks_cut.md``)."""
    want = load("v5e_serve_blocks_cut.expected.json")
    got = trace_block_ms.reduce(CUT, maps)
    assert got["modules"] == want["modules"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    self_s = {f"{b}|{o}": s for (b, o), s in got["self_s"].items()}
    assert self_s == pytest.approx(want["self_s"], rel=1e-9)
    assert sorted(f"{b}|{o}" for b, o in got["known"]) == want["known"]
    # self time: blocks and rest are disjoint and inside the busy time
    # (a whole trace reads 99.5 % and more; the cut drops the one
    # operation that straddles its start)
    assert sum(self_s.values()) <= got["busy_s"] * (1 + 1e-9)
    assert sum(self_s.values()) >= 0.97 * got["busy_s"]


def test_a_conditional_keeps_only_what_its_children_leave(maps):
    """The sampler's ``conditional`` encloses the events of the branch
    it ran (scoped, ``sampler``): its own time is its duration less
    theirs, and theirs is counted once."""
    from jax.profiler import ProfileData
    (device,) = [p for p in ProfileData.from_file(CUT).planes
                 if p.name.startswith(span_reduce.DEVICE_PLANE)]
    (ops,) = [line for line in device.lines if line.name == "XLA Ops"]
    events = [(trace_block_ms.instruction_of(e.name), e.start_ns,
               e.start_ns + e.duration_ns) for e in ops.events]
    # (the small host-path programs' events are on the line too)
    table = {"": {"opcode": "", "block": ""}, **maps[0]["instructions"]}
    events = [(n if n in table else "", a, b) for n, a, b in events]
    (cond,) = [e for e in events if table[e[0]]["opcode"] == "conditional"]
    assert table[cond[0]]["block"] == "sampler"
    children = [e for e in events if e is not cond
                and cond[1] <= e[1] and e[2] <= cond[2]]
    assert children and all(table[n]["block"] == "sampler"
                            for n, _, _ in children)
    covered = sum(b - a for a, b in trace_reduce.union(
        [(a, b) for _, a, b in children]))
    assert 0 < covered < cond[2] - cond[1]
    got = trace_block_ms.reduce(CUT, maps)["self_s"]
    assert got[("sampler", "conditional")] == pytest.approx(
        (cond[2] - cond[1] - covered) / 1e9, rel=1e-9)


@pytest.mark.parametrize("name", sorted(
    n for n, cells in NEW_METRICS.items() if GPT in cells))
def test_every_metric_of_the_cell_reads_the_recorded_cut(
        name, blocks_cut_is_the_run):
    want = load("v5e_serve_blocks_cut.expected.json")["metrics"]
    value = read_metric(name, {"samples": {"traced_steps": 2}})
    assert value == pytest.approx(want[name], rel=1e-9)
    if name.endswith("_share.serve"):
        assert 0 <= value <= 100


@pytest.mark.parametrize("name", [
    "block_ms.recurrent.serve", "block_ms.experts.serve",
    "block_ms.attention_chunk.serve", "block_ms.optimizer.train"])
def test_a_model_without_the_block_reads_nothing(name,
                                                 blocks_cut_is_the_run):
    """GPT-2 has no recurrent layer, no experts, no chunk branch and no
    optimizer: the cells that list these metrics are others."""
    assert GPT not in NEW_METRICS[name]
    assert read_metric(name, {"samples": {"traced_steps": 2}}) is None


def test_a_map_without_scopes_reads_nothing(monkeypatch, maps, capsys,
                                            blocks_cut_is_the_run):
    """An executable from a compile cache that an older tree filled has
    no block in its metadata: every block metric is left out, the
    copies included, nothing raises, and stderr says why, once."""
    bare = [{**m, "instructions": {
        k: {**v, "block": ""} for k, v in m["instructions"].items()}}
        for m in maps]
    monkeypatch.setattr(trace_block_ms, "program_maps", lambda: bare)
    run = {"samples": {"traced_steps": 2}}
    for name in NEW_METRICS:
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 name + ".json")
        if spec["reader"] == "trace_block_ms":
            assert read_metric(name, run) is None
    said = capsys.readouterr().err
    assert said.count("trace_block_ms: no block metric of") == 1
    assert "carry no block scope" in said and "compile cache" in said


@pytest.mark.parametrize("cut", OLD_CUTS)
def test_readers_give_none_on_a_trace_without_a_map(cut, monkeypatch,
                                                    maps):
    """The three older cuts: their programs (``jit_pure_fn``) kept no
    map and wrote no ``context_tokens``.  With no map at all, with the
    maps of whatever this process compiled, and with another program's
    map, the readers give ``None`` and do not raise."""
    path = os.path.join(DATA, cut + ".xplane.pb")
    monkeypatch.setattr(span_reduce, "newest_trace", lambda: path)
    run = {"samples": {"traced_steps": 2}}
    for programs in (None, [], "live", maps):
        if programs != "live":
            monkeypatch.setattr(trace_block_ms, "program_maps",
                                lambda programs=programs: programs)
        trace_block_ms._cache.clear()
        for name in NEW_METRICS:
            spec = harness.load_json(harness.HERE, "layer_metrics",
                                     name + ".json")
            if spec["reader"] == "trace_block_ms":
                assert read_metric(name, run) is None, name
    trace_block_ms._cache.clear()
    assert trace_span_attr.read(run, "engine:dispatch",
                                "context_tokens") is None
    assert trace_span_attr.read({"samples": {}}, "engine:dispatch",
                                "chunk_tokens") is None


def test_span_attributes_come_from_the_host_plane():
    assert trace_span_attr.attribute_values(
        CUT, "engine:dispatch", "no_such_attribute") == []
    spans_cut = os.path.join(DATA, "v5e_serve_spans_cut.xplane.pb")
    assert trace_span_attr.attribute_values(
        spans_cut, "engine:dispatch", "chunk_tokens") == [220, 256]
    want = load("v5e_serve_blocks_cut.expected.json")["attributes"]
    for attr, values in want.items():
        assert trace_span_attr.attribute_values(
            CUT, "engine:dispatch", attr) == values
    assert set(want) == {"decode_rows", "chunk_tokens", "chunk_start",
                         "context_tokens"}


def test_program_maps_is_the_programs_own():
    from paddle_tpu import observability
    assert trace_block_ms.program_maps() == observability.program_blocks()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_files_resolve(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
    assert entry["workloads"] == NEW_METRICS[name]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        spec["unit"], spec["layer"], spec["moves"])
    assert entry["layer"] in ("model step", "serving engine", "device")
    assert callable(importlib.import_module(
        "benchmarks.readers." + spec["reader"]).read)
    # each cell reports the end-to-end metric the new one moves
    (moved,) = [m for m in MANIFEST["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    if name.startswith("traced_"):
        assert "not a goal" in spec["what"]
    assert len(MANIFEST["per_layer"]) == 38 + len(NEW_METRICS)


def test_traced_run_reports_every_per_layer_metric(monkeypatch,
                                                   blocks_cut_is_the_run):
    """``--trace 1`` end to end at a tiny size, the recorded cut and its
    map standing in for what a CPU cannot give: every per-layer metric
    that lists the cell is in the line, PR 37's among them."""
    from benchmarks.tests.test_benchmark import tiny_for
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda path: real(CUT))
    result = harness.run_cell(GPT, 2 ** 31 + 37, 1.0, 1,
                              shrink=tiny_for(GPT))
    wanted = {m["name"] for m, _ in harness.metric_specs(
        MANIFEST, "per_layer", GPT)}
    assert set(result["metrics"]) == wanted
    assert {n for n, cells in NEW_METRICS.items() if GPT in cells} <= wanted
    assert result["correct"] is True
    # what test_benchmark.py asks of a traced line, on this cut
    assert result["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert 0 < result["metrics"]["batch_occupancy.serve"]["value"] <= 100
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())


def test_idle_attributed_share_serve_reads_a_busy_chip(
        blocks_cut_is_the_run):
    """The serving form has no floor on the idle share: a chip idle for
    a thousandth of the window still says whose the gaps are (the
    training form, PR 26's, leaves out a share under its floor)."""
    t = span_reduce.reduction()
    assert 0 < 100.0 * t["idle_s"] < 0.2 * t["window_s"]
    run = {"samples": {"traced_steps": 2}}
    assert trace_idle_attributed.read(run, min_idle_percent=0.2) is None
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "idle_attributed_share.serve.json")
    assert spec["args"] == {"min_idle_percent": 0}
    assert trace_idle_attributed.read(run, **spec["args"]) \
        == pytest.approx(100.0)
    assert t["idle_gaps"] == {"engine:dispatch": pytest.approx(t["idle_s"])}
