"""Boundary spans on the profiler's clock, and kernel names in lowered
programs (ISSUE 26).

A boundary span (``observability.span(..., boundary=True)``) has two
sinks: the in-memory timeline behind the ``PADDLE_TPU_OBS`` gate, and a
``jax.profiler.TraceAnnotation`` that is entered gate on or off.  The
tests take a real ``jax.profiler`` trace on the CPU and read the host
plane of its ``.xplane.pb`` back, the way the benchmark's
``span_reduce`` does for a chip's trace.
"""
import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import paddle_tpu as paddle
from paddle_tpu import nn, observability as obs, optimizer, static
from paddle_tpu.profiler import RecordEvent

import test_tpu_compile as kernel_cases


@pytest.fixture(autouse=True)
def _gate_off_and_clean():
    prev = obs.enable(False)
    obs.get_timeline().clear()
    yield
    obs.get_timeline().clear()
    obs.enable(prev)


@contextlib.contextmanager
def host_trace(tmp_path):
    """Profile what runs inside; afterwards ``out`` holds the host
    plane's events as ``(name, start_ns, end_ns, stats)``."""
    out = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]


def named(events, name):
    return [e for e in events if e[0] == name]


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def in_order(*events):
    return all(a[2] <= b[1] for a, b in zip(events, events[1:]))


def timeline_names():
    return [e.name for e in obs.get_timeline().events()]


# -- the primitive --------------------------------------------------------
def test_boundary_span_with_the_gate_off_is_in_the_trace_only(tmp_path):
    with host_trace(tmp_path) as events:
        with obs.span("t:layer", boundary=True, step=3, rows=7) as sp:
            sp.set("late", 5)
        with obs.span("t:plain", rows=1):
            pass
    assert timeline_names() == []
    (ev,) = named(events, "t:layer")
    assert ev[3] == {"step": 3, "rows": 7, "late": 5}
    assert not named(events, "t:plain")     # timeline-only, and gated


def test_boundary_span_with_the_gate_on_is_in_both(tmp_path):
    obs.enable(True)
    with host_trace(tmp_path) as events:
        with obs.tag(shard="dp0"):
            with obs.span("t:layer", cat="dispatch", boundary=True,
                          step=4, rows=2) as sp:
                sp.set("late", 1)
        with obs.span("t:timeline-name", boundary="t:fixed", program="p"):
            pass
        with obs.span("t:plain"):
            pass
    (rec, renamed, plain) = obs.get_timeline().events()
    assert (rec.name, rec.cat, rec.step) == ("t:layer", "dispatch", 4)
    assert rec.attrs == {"shard": "dp0", "rows": 2, "late": 1}
    assert (renamed.name, plain.name) == ("t:timeline-name", "t:plain")
    (ev,) = named(events, "t:layer")
    assert ev[3] == {"shard": "dp0", "rows": 2, "step": 4, "late": 1}
    (fixed,) = named(events, "t:fixed")     # its own name in the trace
    assert fixed[3] == {"program": "p"}
    assert not named(events, "t:timeline-name")
    assert not named(events, "t:plain")


def test_boundary_span_closes_its_annotation_when_the_body_raises(tmp_path):
    with host_trace(tmp_path) as events:
        with pytest.raises(KeyError):
            with obs.span("t:outer", boundary=True):
                with obs.span("t:raises", boundary=True):
                    raise KeyError("x")
        with obs.span("t:after", boundary=True):
            pass
    (outer,), (inner,) = named(events, "t:outer"), named(events, "t:raises")
    (after,) = named(events, "t:after")
    assert inside(inner, outer) and in_order(outer, after)


@pytest.mark.parametrize("gate", [False, True])
def test_record_event_is_a_boundary_span(tmp_path, gate):
    obs.enable(gate)
    with host_trace(tmp_path) as events:
        with RecordEvent("user:stage"):
            pass
        ev = RecordEvent("user:manual")
        ev.begin()
        ev.end()
    assert timeline_names() == (["user:stage", "user:manual"] if gate
                                else [])
    assert named(events, "user:stage") and named(events, "user:manual")


# -- the three hot paths --------------------------------------------------
def test_executor_run_leaves_its_boundary_spans(tmp_path):
    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [8, 16], "float32")
            y = static.data("y", [8, 1], "float32")
            loss = paddle.nn.functional.mse_loss(nn.Linear(16, 1)(x), y)
            optimizer.SGD(learning_rate=0.1,
                          parameters=main.all_parameters()).minimize(loss)
        feed = {"x": np.ones((8, 16), np.float32),
                "y": np.ones((8, 1), np.float32)}
        exe = static.Executor()
        exe.run(main, feed=feed, fetch_list=[loss])          # compiles
        with host_trace(tmp_path) as events:
            with jax.profiler.TraceAnnotation("bench:exe.run"):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        paddle.disable_static()
    assert timeline_names() == []                      # the gate is off
    (step,) = named(events, "bench:exe.run")
    (pro,), (dis,) = named(events, "exe:prologue"), named(events,
                                                          "exe:dispatch")
    (fetch,) = named(events, "exe:fetch")
    assert all(inside(e, step) for e in (pro, dis, fetch))
    assert in_order(pro, dis, fetch)
    assert dis[3]["step"] == 1 and dis[3]["program"].startswith("static.")
    assert "h2d_bytes" not in dis[3]       # counted only when collecting
    assert not named(events, "h2d:feed")   # a plain span: timeline only


def test_executor_dispatch_keeps_its_timeline_span(tmp_path):
    """Gate on: the dispatch span is in the timeline under the
    program's label, with the payload bytes, as before."""
    obs.enable(True)
    from test_observability import TestIntegration
    with host_trace(tmp_path) as events:
        TestIntegration()._run_static(n_steps=2)
    dispatches = [e for e in obs.get_timeline().events()
                  if e.cat == "dispatch"]
    assert [d.step for d in dispatches] == [0, 1]
    assert all(d.name.startswith("static.") for d in dispatches)
    assert dispatches[0].attrs["h2d_bytes"] > 0
    assert dispatches[0].attrs["d2h_bytes"] > 0
    assert [e[3]["step"] for e in named(events, "exe:dispatch")] == [0, 1]
    assert {"exe:prologue", "h2d:feed", "exe:fetch"} <= set(
        timeline_names())


def test_lazy_step_leaves_its_boundary_spans(tmp_path):
    paddle.seed(3)
    model = nn.Linear(16, 4)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    x = np.ones((8, 16), np.float32)

    def step():
        loss = (model(paddle.to_tensor(x)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return float(loss)

    with paddle.incubate.lazy_eager():
        step()
        step()                             # the segment is cached now
        with host_trace(tmp_path) as events:
            with jax.profiler.TraceAnnotation("bench:lazy.step"):
                step()
    assert timeline_names() == []
    (whole,) = named(events, "bench:lazy.step")
    (back,), (opt_step,) = named(events, "autograd:backward"), named(
        events, "opt:step")
    reads = named(events, "sync:read")
    wires, flushes = named(events, "lazy:wire"), named(events, "lazy:flush")
    backs = named(events, "lazy:writeback")
    assert len(wires) == len(flushes) == len(backs) >= 1
    assert not named(events, "compile:lazy:segment")
    assert in_order(back, opt_step, reads[-1])
    for e in [back, opt_step] + reads + wires + flushes + backs:
        assert inside(e, whole)
    for wire, flush, wb in zip(wires, flushes, backs):
        assert in_order(wire, flush, wb)
        assert wire[3]["nodes"] == flush[3]["nodes"] > 0
        assert flush[3]["cache_hit"] in (1, True, "True")
    # the loss read forces the step's segment: that flush is its child
    assert inside(wires[-1], reads[-1]) and inside(backs[-1], reads[-1])


def test_lazy_compile_is_a_boundary_span(tmp_path):
    model = nn.Linear(5, 3)
    with paddle.incubate.lazy_eager():
        with host_trace(tmp_path) as events:
            float((model(paddle.ones([2, 5])) * 3.0).sum())
    (comp,) = named(events, "compile:lazy:segment")
    (wire,), (flush,) = named(events, "lazy:wire"), named(events,
                                                          "lazy:flush")
    assert in_order(wire, comp, flush)
    assert comp[3]["nodes"] == wire[3]["nodes"]


def test_engine_step_leaves_its_boundary_spans(tmp_path):
    from paddle_tpu.inference.serving import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64))
    model.eval()
    eng = GenerationEngine(model, num_blocks=64, max_batch=3,
                           max_model_len=64, prefill_chunk=16)
    try:
        eng.add_request(list(range(1, 12)), max_new_tokens=6)
        eng.add_request(list(range(3, 8)), max_new_tokens=6)
        for _ in range(3):                 # compile; a decode row joins
            eng.step()
        obs.enable(True)                   # the counts the benchmark reads
        with host_trace(tmp_path) as events:
            eng.step()
            eng.step()
    finally:
        eng.close()
    steps = named(events, "engine:step")
    assert len(steps) == 2 and in_order(*steps)
    assert steps[1][3]["step"] == steps[0][3]["step"] + 1
    for step in steps:
        parts = [[e for e in named(events, "engine:" + part)
                  if inside(e, step)]
                 for part in ("schedule", "pack", "dispatch", "drain",
                              "collect")]
        assert [len(p) for p in parts] == [1, 1, 1, 1, 1], parts
        assert in_order(*[p[0] for p in parts])
        dispatch, drain = parts[2][0], parts[3][0]
        assert dispatch[3]["step"] == step[3]["step"]
        assert dispatch[3]["decode_rows"] >= 1
        assert dispatch[3]["chunk_tokens"] >= 0
        assert drain[3]["lag"] >= 0
    # decode / prefill:chunk stay in the timeline, inside the dispatch
    recorded = obs.get_timeline().events()
    decodes = [e for e in recorded if e.name == "decode"]
    dispatches = [e for e in recorded if e.name == "engine:dispatch"]
    assert len(decodes) == len(dispatches) == 2
    for dec, dis in zip(decodes, dispatches):
        assert dis.ts <= dec.ts and dec.ts + dec.dur <= dis.ts + dis.dur
        assert dec.attrs["batch"] == dis.attrs["decode_rows"]
    assert not named(events, "decode")


# -- kernel names ---------------------------------------------------------
def test_kernel_span_names_and_records_nothing():
    from paddle_tpu.ops.pallas_tiles import _kernel_span
    obs.enable(True)
    with _kernel_span("layer_norm", "bwd") as kernel_name:
        assert kernel_name == "layer_norm_bwd"
    assert timeline_names() == []
    assert not any(k.startswith("kernel")
                   for k in obs.phase_breakdown())


# the _kernel_span call sites each case of tests/test_tpu_compile.py
# reaches: between them all 20 pallas_calls of paddle_tpu/ops
SITES = {
    "flash_fwd_16x512x12x64": ["flash_attention.fwd"],
    "flash_bwd_16x512x12x64": ["flash_attention.fwd",
                               "flash_attention.bwd_dq",
                               "flash_attention.bwd_dkv"],
    "flash_dropout_bwd_16x512x12x64": ["flash_attention.fwd",
                                       "flash_attention.bwd_dq",
                                       "flash_attention.bwd_dkv"],
    "layer_norm_8192x768": ["layer_norm.fwd", "layer_norm.bwd"],
    "ln_residual_8192x768": ["layer_norm_residual.fwd",
                             "layer_norm_residual.bwd"],
    "matmul_epilogue_8192x768x3072": ["matmul_epilogue.fwd",
                                      "matmul_epilogue.bwd"],
    "softmax_xent_8192x30522": ["softmax_cross_entropy.fwd",
                                "softmax_cross_entropy.bwd"],
    "ragged_attention_gpt_bf16": ["ragged_attention.fwd"],
    "ragged_attention_gpt_int8kv": ["ragged_attention_int8.fwd"],
    "rms_norm_4096x4096": ["rms_norm.fwd", "rms_norm.bwd"],
    "matmul_epilogue_int8_768x768x3072": ["matmul_epilogue_int8.fwd",
                                          "matmul_epilogue_int8.bwd"],
    "grouped_matmul_8x768x3072": ["grouped_matmul.fwd",
                                  "grouped_matmul.bwd_dx",
                                  "grouped_matmul.bwd_dw"],
    "lora_sgmv_64x768x3072_r16": ["lora_sgmv.fwd", "grouped_matmul.bwd_dx",
                                  "grouped_matmul.bwd_dw"],
}


@pytest.mark.parametrize("case", list(SITES))
def test_kernel_names_show_in_the_lowered_text(case):
    """Each pallas_call sits under ``<name>.<direction>`` (the named
    scope) and is itself called ``<name>_<direction>``."""
    fn, args = kernel_cases.CASES[case]()
    text = jax.jit(fn).lower(*[jax.ShapeDtypeStruct(s, d)
                               for s, d in args]).as_text(debug_info=True)
    for site in SITES[case]:
        assert re.search(re.escape(site) + r"\)*/"
                         + site.replace(".", "_") + "/pallas_call", text), \
            f"{site} is not named in the lowered {case}"
    assert sum(len(v) for v in SITES.values()) >= 20
