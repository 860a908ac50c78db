"""The benchmark's own tests: CPU, quick, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They live under ``benchmarks/`` because a benchmark PR may add files
only there; ``tests/`` (tier-1) does not collect them.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import generate, harness, trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_serve_cut.xplane.pb")
MANIFEST = harness.load_manifest()
CELLS = [c["name"] for c in MANIFEST["workloads"]]

# a 2-layer width-128 model and a window of a second, per family
TINY = {
    "bert": {
        "config": {"num_hidden_layers": 2, "hidden_size": 128,
                   "num_attention_heads": 2, "intermediate_size": 512,
                   "vocab_size": 1024, "max_position_embeddings": 64,
                   # logits wide enough at step 0 that a second of
                   # training visibly narrows them
                   "initializer_range": 0.1},
        "traffic": {"batch": 4, "seq": 64,
                    "optimizer": {"name": "AdamW", "learning_rate": 1e-3},
                    "check": {"sequences": 2, "logits_rel_l2_limit": 0.025,
                              "loss_step0_rtol": 0.2,
                              "loss_rise_rtol": 0.01}},
        "peaks": {"cpu": {"bf16_flops_per_s": 1e12}}},
    "gpt2": {
        "config": {"n_layer": 2, "n_embd": 128, "n_head": 2,
                   "vocab_size": 512, "n_positions": 128},
        "traffic": {
            "clients": 4,
            "engine": {"max_batch": 4, "max_model_len": 128,
                       "num_blocks": 64, "prefill_chunk": 32},
            "prompt_len": {"dist": "lognormal", "median": 30,
                           "sigma": 0.6, "min": 8, "max": 80},
            "output_len": {"dist": "lognormal", "median": 10,
                           "sigma": 0.5, "min": 4, "max": 24},
            "check": {"requests": 4, "pad_to": 128,
                      "margin_limit_std": 0.15}}},
}


def tiny_for(cell):
    family = harness.resolve(MANIFEST, cell).config["family"]
    return TINY[family]


def test_manifest_obeys_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = []
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(MANIFEST["paths"]))
        names.append(c["name"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # what it moves is reported wherever it is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads"):
        listed = [x["name"] for x in MANIFEST[kind]]
        assert len(listed) == len(set(listed))
    metrics = [m["name"] for m in MANIFEST["end_to_end"]
               + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_resolves_by_name(cell):
    import importlib
    r = harness.resolve(MANIFEST, cell)
    assert callable(r.runner.run) and callable(r.family.build)
    assert callable(r.family.reference_logits)
    (entry,) = [c for c in MANIFEST["configs"]
                if c["name"] == r.cell["config"]]
    assert r.config["reduced"] == entry["reduced"]
    assert r.config["source"].startswith(entry["source"])
    for kind in ("end_to_end", "per_layer"):
        specs = harness.metric_specs(MANIFEST, kind, cell)
        assert specs, f"{cell} reports no {kind} metric"
        for m, spec in specs:
            assert spec["unit"] == m["unit"]
            assert callable(importlib.import_module(
                f"benchmarks.readers.{spec['reader']}").read)
            if kind == "per_layer":
                assert (spec["layer"], spec["moves"]) == (m["layer"],
                                                          m["moves"])
    assert "setup_s" in [m["name"] for m, _ in harness.metric_specs(
        MANIFEST, "end_to_end", cell)]


@pytest.mark.parametrize("cell", CELLS)
def test_runner_end_to_end_at_tiny_size(cell, capsys):
    result = harness.run_cell(cell, 2 ** 31 + 5, 1.0, 0,
                              shrink=tiny_for(cell))
    line = json.loads(json.dumps(result))          # it serialises
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    wanted = {m["name"] for m, _ in harness.metric_specs(
        MANIFEST, "end_to_end", cell)}
    assert set(line["metrics"]) == wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"     # every number says where
    assert "check" in capsys.readouterr().out


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """``--trace 1`` end to end, with the recorded v5e trace standing
    in for the one a CPU cannot give."""
    cell = "gpt2-large.decode-closed32"
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda path: real(FIXTURE))
    result = harness.run_cell(cell, 11, 1.0, 1, shrink=tiny_for(cell))
    wanted = {m["name"] for m, _ in harness.metric_specs(
        MANIFEST, "per_layer", cell)}
    assert set(result["metrics"]) == wanted
    assert result["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert 0 < result["metrics"]["batch_occupancy.serve"]["value"] <= 100
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())


def test_command_refuses_a_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    assert "TPU" in done.stderr


@pytest.mark.parametrize("family", sorted(TINY))
def test_reference_agrees_with_the_program(family):
    """Float32 program (no AMP, no cache, dropout off) against the
    plain reference on the same parameters: agreement to float32
    rounding, so a wrong equation cannot hide in a bf16 tolerance."""
    import importlib

    import jax
    import paddle_tpu as paddle
    from benchmarks.families import _plain
    fam = importlib.import_module(f"benchmarks.families.{family}")
    (name,) = [c["name"] for c in MANIFEST["configs"]
               if harness.load_json(ROOT, c["file"])["family"] == family]
    cfg = {**harness.load_json(harness.HERE, "configs", name + ".json"),
           **TINY[family]["config"], "dtype": "float32"}
    paddle.seed(3)
    model = fam.build(cfg)
    model.eval()
    ids = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], (2, 48), dtype=np.int64)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids))
    got = np.asarray(got.numpy(), np.float64)
    ref = np.asarray(jax.jit(lambda p, x: fam.reference_logits(
        p, cfg, x))(_plain.arrays(model), ids), np.float64)
    assert got.shape == ref.shape == (2, 48, cfg["vocab_size"])
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 2e-5


def test_closed_loop_generator_is_a_function_of_the_seed():
    traffic = harness.load_json(harness.HERE, "traffic", "closed32.json")

    def take(seed, n):
        source = generate.requests(traffic, 50257, seed)
        return [next(source) for _ in range(n)]
    n = traffic["pool"]["size"]
    a, b, c = take(2 ** 31 + 7, n + 3), take(2 ** 31 + 7, n + 3), take(8, n)
    assert a == b and a[:n] != c
    # every seed serves the same sizes in the same order, round and round
    sizes = lambda reqs: [(len(p), o) for p, o in reqs]  # noqa: E731
    assert sizes(a[:n]) == sizes(c) == generate.request_pool(traffic)
    assert sizes(a[n:]) == sizes(a[:3])
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    assert all(lo <= len(p) <= hi for p, _ in a)
    assert all(0 <= t < 50257 for p, _ in a[:4] for t in p)
    # no request can outgrow the engine's context, ramp included
    ramp = generate.ramp(a[:32], traffic, 256)
    assert ramp == generate.ramp(c[:32], traffic, 256)
    assert max(len(p) + o for (p, _), o in zip(a, ramp)) \
        < traffic["engine"]["max_model_len"]
    # each ramp request is still decoding when the last one starts
    chunks = [-(-len(p) // 256) for p, _ in a[:32]]
    starts = np.cumsum(chunks)
    assert all(s + o > starts[-1] for s, o in zip(starts, ramp))


def test_mlm_batches_follow_the_recipe():
    traffic = harness.load_json(harness.HERE, "traffic",
                                "pretrain-static.json")
    one = next(generate.mlm_batches(traffic, 30522, 2 ** 31 + 1))
    two = next(generate.mlm_batches(traffic, 30522, 2 ** 31 + 1))
    assert all((one[k] == two[k]).all() for k in one)
    ids, labels = one["ids"], one["labels"]
    assert ids.shape == labels.shape == (16, 512) and ids.dtype == np.int64
    chosen = labels != -100
    assert abs(chosen.mean() - 0.15) < 0.02
    assert abs((ids[chosen] == 103).mean() - 0.8) < 0.04
    assert abs((ids[chosen] == labels[chosen]).mean() - 0.1) < 0.04
    assert (ids[~chosen] != 103).mean() > 0.999


def test_metric_arithmetic_on_hand_made_samples():
    from benchmarks.readers import mfu, percentile, ratio, sample
    run = {"samples": {"gaps_ms": list(range(1, 101)), "step_s": [0.2] * 9
                       + [1.0], "tokens": 900, "window_s": 45.0,
                       "carried_tokens": 30 * 10 + 128 * 5,
                       "budget_tokens": 10 * 752, "setup_s": 27.5}}
    assert percentile.read(run, of="gaps_ms", q=95) == pytest.approx(95.05)
    assert percentile.read(run, of="step_s", q=50, scale=1e3) \
        == pytest.approx(200.0)
    assert percentile.read(run, of="ttft_ms", q=95) is None   # nothing read
    assert ratio.read(run, num="tokens", den="window_s") \
        == pytest.approx(20.0)
    assert ratio.read(run, num="carried_tokens", den="budget_tokens",
                      scale=100.0) == pytest.approx(100 * 940 / 7520)
    assert ratio.read(run, num="flushes", den="steps") is None
    assert sample.read(run, of="setup_s") == 27.5
    # bert-base at seq 512: 6 * 108.9M matmul weights + attention
    from benchmarks.families import bert
    cfg = harness.load_json(harness.HERE, "configs",
                            "bert-base-uncased.json")
    flops = bert.train_flops_per_token(cfg, 512)
    assert flops == 6 * (12 * (4 * 768 ** 2 + 2 * 768 * 3072) + 768 ** 2
                         + 30522 * 768) + 12 * 12 * 512 * 768
    run.update(family=bert, config=cfg, traffic={"seq": 512},
               peaks=harness.load_json(harness.HERE, "peaks.json"),
               device={"kind": "TPU v5 lite", "count": 1})
    run["samples"].update(tokens=44000 * 45)
    assert mfu.read(run) == pytest.approx(100 * 44000 * flops / 197e12)
    run["device"]["kind"] = "TPU v9"      # no peak on record: an error
    with pytest.raises(KeyError):
        mfu.read(run)


def test_trace_reduction_on_a_recorded_v5e_trace():
    """The fixture is a cut of this PR's first traced chip run of
    ``gpt2-large.decode-closed32`` (see data/README.md for the cut)."""
    expected = harness.load_json(os.path.dirname(FIXTURE),
                                 "v5e_serve_cut.expected.json")
    got = trace_reduce.reduce(FIXTURE)
    assert got["chips"] == 1
    for key in ("window_s", "busy_s", "custom_call_s"):
        assert got[key] == pytest.approx(expected[key], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["breakdown"]["device_ops"][0][0] \
        == expected["top_op"][0]
    assert got["breakdown"]["device_ops"][0][1] \
        == pytest.approx(expected["top_op"][1], rel=1e-9)
    assert [g[0] for g in got["breakdown"]["idle_gaps"]] \
        == expected["idle_gap_owners"]
    assert trace_reduce.is_custom_call(
        '%pure_fwd.242 = bf16[20,752,64]{2,1,0} custom-call(s32[33,64] '
        '%copy-done.217), custom_call_target="tpu_custom_call"')
    assert not trace_reduce.is_custom_call(
        "%fusion.3 = f32[1608224]{0} fusion(f32[32,50257] "
        "%custom-call.9), kind=kCustom, calls=%fused_computation.3")
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace_reduce.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
