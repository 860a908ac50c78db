"""``span_reduce`` and its readers: the arithmetic on hand-made
intervals, and the whole reduction held to a recorded v5e cut.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import shutil

import pytest

from benchmarks import harness, span_reduce
from benchmarks.readers import (trace_idle_attributed, trace_kernel_ms,
                                trace_named_share, trace_span_ms)

SPAN_CUT = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_serve_spans_cut.xplane.pb")   # conftest's
MANIFEST = harness.load_manifest()
SERVE = "gpt2-large.decode-closed32"


def test_self_time_on_hand_made_intervals():
    """step [0,100] holds wire [10,30], read [40,90] and, inside read,
    flush [50,60]; a second step [120,130] is empty."""
    spans = [("step", 0, 100), ("wire", 10, 30), ("read", 40, 90),
             ("flush", 50, 60), ("step", 120, 130)]
    pieces = span_reduce.innermost_segments(spans)
    assert pieces == [(0, 10, "step"), (10, 30, "wire"), (30, 40, "step"),
                      (40, 50, "read"), (50, 60, "flush"),
                      (60, 90, "read"), (90, 100, "step"),
                      (120, 130, "step")]
    self_time = {}
    for a, b, name in pieces:
        self_time[name] = self_time.get(name, 0) + b - a
    assert self_time == {"step": 40, "wire": 20, "read": 40, "flush": 10}
    # pieces never overlap and cover the union of the spans
    assert all(x[1] <= y[0] for x, y in zip(pieces, pieces[1:]))
    assert sum(self_time.values()) == 100 + 10
    # a child that overruns its parent by a rounding error ends with it
    assert span_reduce.innermost_segments(
        [("outer", 0, 10), ("inner", 5, 11)]) == [(0, 5, "outer"),
                                                  (5, 10, "inner")]
    assert span_reduce.overlap(pieces, [(25, 55), (95, 125)]) == {
        "wire": 5, "step": 10 + 5 + 5, "read": 10, "flush": 5}


def test_idle_gaps_go_to_the_innermost_span_program_spans_first():
    pieces = span_reduce.innermost_segments([
        ("bench:lazy.step", 0, 100), ("autograd:backward", 30, 50),
        ("lazy:wire", 60, 70), ("sync:read", 80, 100),
        ("bench:feed", 100, 110)])
    owner = lambda a, b: span_reduce.gap_owner(      # noqa: E731
        span_reduce.overlap(pieces, [(a, b)]))
    # forward capture has no span of its own: the harness's span owns it
    assert owner(0, 25) == "bench:lazy.step"
    # a program span that touches the gap comes before a bench: span,
    # however much of the gap the bench: span covers ...
    assert owner(0, 35) == "autograd:backward"
    # ... and among program spans the one that covers most wins
    assert owner(25, 75) == "autograd:backward"
    assert owner(45, 75) == "lazy:wire"
    assert owner(95, 108) == "sync:read"
    assert owner(102, 108) == "bench:feed"
    assert owner(200, 300) == "no span"


@pytest.mark.parametrize("text, kernel", [
    ('%ragged_attention_fwd.7 = bf16[20,752,64]{2,1,0} custom-call('
     's32[33,64] %copy-done.3), custom_call_target="tpu_custom_call"',
     "ragged_attention"),
    ('%layer_norm_residual_bwd.12 = (bf16[8192,768]{1,0}) custom-call('
     'bf16[8192,768] %x), custom_call_target="tpu_custom_call"',
     "layer_norm_residual"),
    ('%flash_attention_bwd_dkv = (bf16[192,512,64]) custom-call(bf16[1] '
     '%q), custom_call_target="tpu_custom_call"', "flash_attention"),
    ('%grouped_matmul_bwd_dx.2 = bf16[8,8] custom-call(bf16[8,8] %a), '
     'custom_call_target="tpu_custom_call"', "grouped_matmul"),
    # the dispatcher's jitted function is no kernel
    ('%pure_fwd.242 = bf16[20,752,64]{2,1,0} custom-call(s32[33,64] '
     '%copy-done.217), custom_call_target="tpu_custom_call"', "unnamed"),
    ('%transpose_jvp___.40 = bf16[8,8] custom-call(bf16[8,8] %a), '
     'custom_call_target="tpu_custom_call"', "unnamed"),
    # XLA's own custom calls
    ('%custom-call.235 = bf16[32,25216]{1,0} custom-call(bf16[16,25216] '
     '%slice-done), custom_call_target="ConcatBitcast"', "unnamed"),
    ("%fusion.3 = f32[1608224]{0} fusion(f32[32,50257] %custom-call.9), "
     "kind=kCustom, calls=%fused_computation.3", None),
])
def test_kernel_naming_rule(text, kernel):
    assert span_reduce.kernel_of(text) == kernel


def test_reduction_of_the_recorded_cut():
    """The cut is from PR 26's traced chip run of the serving cell
    (data/v5e_serve_spans_cut.md has the cut); the expected file holds what
    ``reduce`` gave on it when it was cut."""
    expected = harness.load_json(os.path.dirname(SPAN_CUT),
                                 "v5e_serve_spans_cut.expected.json")
    got = span_reduce.reduce(SPAN_CUT)
    assert got["chips"] == 1
    for key in ("window_s", "custom_call_s", "idle_s"):
        assert got[key] == pytest.approx(expected[key], rel=1e-9)
    assert set(got["kernels"]) == set(expected["kernels"])
    for name, row in expected["kernels"].items():
        assert got["kernels"][name]["count"] == row["count"]
        assert got["kernels"][name]["s"] == pytest.approx(row["s"], rel=1e-9)
    assert set(got["spans"]) == set(expected["spans"])
    for name, row in expected["spans"].items():
        assert got["spans"][name]["count"] == row["count"]
        for key in ("total_s", "self_s", "self_idle_s"):
            assert got["spans"][name][key] == pytest.approx(
                row[key], rel=1e-9, abs=1e-12), (name, key)
    assert got["idle_gaps"] == pytest.approx(expected["idle_gaps"],
                                             rel=1e-9)
    # the kernel is named, and the engine's spans nest under its step
    assert got["kernels"]["ragged_attention"]["count"] >= 1
    spans = got["spans"]
    assert spans["engine:step"]["total_s"] <= \
        spans["bench:engine.step"]["total_s"]
    parts = ("schedule", "pack", "dispatch", "drain", "collect")
    assert sum(spans["engine:" + p]["total_s"] for p in parts if
               "engine:" + p in spans) <= spans["engine:step"]["total_s"]
    # self times add up to the time under any span
    assert sum(r["self_s"] for r in spans.values()) <= got["window_s"]


def test_readers_on_the_recorded_cut():
    run = {"samples": {"traced_steps": 2}}
    t = span_reduce.reduction()
    ragged = trace_kernel_ms.read(run, kernels=["ragged_attention"])
    assert ragged == pytest.approx(
        1e3 * t["kernels"]["ragged_attention"]["s"] / 2)
    assert trace_kernel_ms.read(run, kernels=["flash_attention"]) is None
    assert trace_kernel_ms.read({"samples": {}},
                                kernels=["ragged_attention"]) is None
    share = trace_named_share.read(run)
    assert 0 < share <= 100
    # host_ms.serve as its file defines it: the engine's self time, the
    # dispatch only while the chip runs nothing (it waits inside)
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "host_ms.serve.json")
    host = trace_span_ms.read(run, **spec["args"])
    spans = t["spans"]
    assert host == pytest.approx(1e3 / 2 * (
        spans["engine:step"]["self_s"] + spans["engine:schedule"]["self_s"]
        + spans["engine:pack"]["self_s"] + spans["engine:collect"]["self_s"]
        + spans["engine:dispatch"]["self_idle_s"]), rel=1e-9)
    whole = trace_span_ms.read(run, spans=["engine:step"], kind="total")
    drain = trace_span_ms.read(run, spans=["engine:drain"], kind="total")
    assert 0 < host < whole - drain        # the dispatch's wait is out
    assert trace_span_ms.read(run, spans=["exe:fetch"]) is None
    waited = trace_span_ms.read(run, spans=["engine:drain"],
                                idle_only=["engine:drain"])
    assert 0 <= waited <= drain
    # the serving cell is busy: under the idle floor nothing is charged
    idle_percent = 100.0 * t["idle_s"] / t["window_s"]
    assert trace_idle_attributed.read(
        run, min_idle_percent=idle_percent * 2) is None
    assert 0 <= trace_idle_attributed.read(run, min_idle_percent=0) <= 100


def test_readers_return_none_where_there_is_nothing_to_read(monkeypatch):
    """A parent commit's trace has neither names nor program spans (the
    PR 23 cut is one), and a run may have no trace at all."""
    from test_benchmark import FIXTURE
    run = {"samples": {"traced_steps": 5}}
    for trace in (FIXTURE, None):
        monkeypatch.setattr(span_reduce, "newest_trace", lambda: trace)
        assert trace_kernel_ms.read(run, kernels=["ragged_attention"]) \
            is None
        assert trace_named_share.read(run) is None
        assert trace_span_ms.read(run, spans=["engine:step"]) is None
        # the harness's own span is there, the program's are not
        assert trace_span_ms.read(
            run, spans=["bench:engine.step", "engine:pack"]) is None
        assert trace_idle_attributed.read(run, min_idle_percent=0) is None
    assert span_reduce.reduce(FIXTURE)["kernels"]["unnamed"]["count"] == 41


def test_newest_trace_is_found_and_parsed_once(tmp_path, monkeypatch):
    monkeypatch.undo()                     # the real lookup
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    assert span_reduce.newest_trace() is None
    assert span_reduce.reduction() is None
    for age, run in ((100, "cell.1"), (50, "cell.2")):
        d = tmp_path / run / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        shutil.copy(SPAN_CUT, d / "host.xplane.pb")
        stamp = os.path.getmtime(SPAN_CUT) - age
        os.utime(d / "host.xplane.pb", (stamp, stamp))
    assert "cell.2" in span_reduce.newest_trace()
    calls = []
    real = span_reduce.reduce
    monkeypatch.setattr(span_reduce, "reduce",
                        lambda p: calls.append(p) or real(p))
    first = span_reduce.reduction()
    assert span_reduce.reduction() is first and len(calls) == 1


@pytest.mark.parametrize("metric", [
    m["name"] for m in MANIFEST["per_layer"]
    if m["name"].split(".")[0] in ("kernel_ms", "named_kernel_share",
                                   "host_ms", "capture_ms",
                                   "flush_host_ms", "idle_attributed_share")])
def test_new_metric_files_name_spans_and_kernels_that_exist(metric):
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             metric + ".json")
    args = spec["args"]
    assert set(args.get("kernels", [])) <= span_reduce.KERNELS
    for span in args.get("spans", []):
        assert span.startswith(span_reduce.BENCH_PREFIX) \
            or span_reduce.is_program_span(span)
    assert set(args.get("idle_only", [])) <= set(args.get("spans", []))
