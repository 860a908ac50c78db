"""The ``Trinity-Mini.mixedlen-closed32`` cell's own tests: CPU, quick.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The cell end to end at a tiny size with ``--trace 0`` and ``1``, the
work functions on hand-made counts, the new metric files, the config
file against the catalog row.  The model against its reference and the
program's pieces are ``tests/test_afmoe.py``'s.
"""
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.families import afmoe as family  # noqa: E402
from benchmarks.readers import kernel_roofline, trace_named_ms  # noqa: E402

CELL = "Trinity-Mini.mixedlen-closed32"
MANIFEST = harness.load_manifest()
NEW_METRICS = ("kernel_ms.grouped_matmul.serve",
               "grouped_matmul_roofline.serve",
               "ragged_attention_roofline.serve", "moe_plan_fill.serve",
               "window_read_share.serve")
S, F = "sliding_attention", "full_attention"
# the published order at width 64: a dense sliding layer, then sliding,
# sliding, sliding, full with 16 experts top-4; a 16-token window over
# 8-token blocks, so that prompts of 20 to 90 tokens pass it
TINY_AFMOE = {
    "config": {
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "num_dense_layers": 1, "layer_types": [S, S, S, S, F],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 16, "num_experts": 16, "num_experts_per_tok": 4,
        "max_position_embeddings": 512, "kv_block_size": 8,
        "dtype": "float32"},
    "traffic": {
        "clients": 4,
        "engine": {"max_batch": 4, "max_model_len": 160, "num_blocks": 96,
                   "block_size": 8, "prefill_chunk": 16},
        "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                       "min": 20, "max": 90},
        "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                       "min": 4, "max": 24},
        "check": {"requests": 4, "in_flight": 2, "in_flight_min_tokens": 2,
                  "pad_multiple": 32, "margin_limit_std": 0.05,
                  "mean_margin_limit_std": 0.001}},
    "peaks": {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}

# ``test_benchmark.py`` runs every cell of the manifest from its own
# ``TINY`` table, keyed by family, and this PR may not edit it, nor
# ``benchmarks/conftest.py``: collected from this directory, hand the
# module pytest runs (by its basename here) the entry, whichever of the
# two is collected first; ``tests/conftest.py`` does it for tier-1's
# copy.  The next ``benchmark`` PR moves the entry into the table
# (PERF.md section 7).
if __name__ == "test_trinity_mini_cell":
    import test_benchmark
    test_benchmark.TINY.setdefault("afmoe", TINY_AFMOE)


def _line(out, key):
    return [json.loads(l) for l in out.splitlines() if f'"{key}"' in l][0]


def test_cell_end_to_end_at_tiny_size(capsys):
    result = harness.run_cell(CELL, 2 ** 31 + 9, 1.0, 0, shrink=TINY_AFMOE)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    out = capsys.readouterr().out
    counted = _line(out, "moe_assignments")
    carried = counted["decode_rows_carried"] \
        + counted["prompt_tokens_carried"]
    # four expert layers, top-4; reports are drained one step late
    assert 0 < counted["moe_assignments"] <= counted["moe_plan_rows"]
    assert abs(counted["moe_assignments"] - 16 * carried) <= 16 * 20
    assert 0 < counted["kv_blocks_read_window"] \
        < counted["kv_blocks_context"]
    assert counted["window_blocks_released"] > 0
    engine = _line(out, "window_groups")["engine"]
    (group,) = engine["window_groups"]
    assert group["window"] == 16 and group["layers"] == 4
    # a row holds the window, one chunk and the partial blocks
    assert engine["window_high_water"] <= 4 * (16 + 16) // 8 + 4 * 2
    assert engine["pool_bytes"] == (engine["full_pool_bytes"]
                                    + engine["window_pool_bytes"])
    check = _line(out, "check")["check"]
    assert check["requests_checked"] == 4 and check["in_flight_checked"] > 0
    assert check["worst_margin_std"] <= check["margin_limit_std"]


def test_traced_run_reports_the_new_per_layer_metrics(monkeypatch):
    """``--trace 1`` with the recorded GPT-2 trace standing in: it holds
    ``ragged_attention_fwd`` calls and no ``grouped_matmul_fwd``, so the
    grouped kernel's two metrics read nothing and are left out, as
    beside a program without the kernel."""
    from benchmarks import trace_reduce
    cut = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_serve_cut.xplane.pb")
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda path: real(cut))
    result = harness.run_cell(CELL, 12, 1.0, 1, shrink={
        **TINY_AFMOE, "peaks": {"cpu": {"bf16_flops_per_s": 1e10,
                                        "hbm_bytes_per_s": 1e8}}})
    got = result["metrics"]
    assert 0 < got["window_read_share.serve"]["value"] < 100
    assert 0 < got["moe_plan_fill.serve"]["value"] <= 100
    assert got["compiles_in_window.serve"]["value"] == 0
    assert got["kernel_ms.ragged_attention.serve"]["value"] > 0
    assert 0 < got["ragged_attention_roofline.serve"]["value"] <= 100
    assert "kernel_ms.grouped_matmul.serve" not in got
    assert "grouped_matmul_roofline.serve" not in got


def test_new_metric_files_resolve():
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_METRICS:
        entry = per_layer[name]
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 name + ".json")
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["layer"], entry["moves"]) \
            == (spec["unit"], spec["layer"], "itl_p95_ms")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        assert callable(reader.read)
        for fn in (spec["args"].get("flops"), spec["args"].get("bytes")):
            assert fn is None or callable(getattr(family, fn))
    # every serving metric the other two serving cells report, here too
    sala = "MiniCPM-SALA.longdoc-closed32"
    gpt2 = "gpt2-large.decode-closed32"
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        cells = m.get("workloads", [])
        if sala in cells and gpt2 in cells:
            assert CELL in cells, m["name"]


def test_work_functions_on_hand_made_counts():
    cfg = harness.load_json(harness.HERE, "configs", "Trinity-Mini.json")
    # a decode-only step: 32 rows x top-8 in four layers, 111 experts a
    # layer touched
    assign, touched = 32 * 8 * 4, 111 * 4
    assert family.moe_flops(assign, touched, cfg) \
        == assign * 3 * 2 * 2048 * 1024
    assert family.moe_bytes(assign, touched, cfg) \
        == (touched * 3 * 2048 * 1024 + assign * 2 * (2048 + 1024)) * 2
    # bandwidth binds by far: 1.4 GB a layer against 3.2 GFLOP
    assert family.moe_flops(assign, touched, cfg) / 197e12 \
        < family.moe_bytes(assign, touched, cfg) / 819e9 / 100
    # a row at 5,000 tokens: 33 blocks in each of 4 sliding layers, 79
    # in the full one
    assert family.attention_bytes(4 * 33, 79, cfg) \
        == (4 * 33 + 79) * 64 * 4 * 128 * 2 * 2
    assert family.attention_flops(4 * 33, 79, cfg) \
        == (4 * 33 + 79) * 64 * 32 * 128 * 4


def test_roofline_reads_the_grouped_kernel_and_refuses_over_100(monkeypatch):
    cfg = harness.load_json(harness.HERE, "configs", "Trinity-Mini.json")
    run = {"samples": {"traced_steps": 5, "traced_moe_assignments": 5120,
                       "traced_moe_experts_touched": 2220},
           "config": cfg, "family": family,
           "peaks": harness.load_json(harness.HERE, "peaks.json"),
           "device": {"kind": "TPU v5 lite"}}
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "grouped_matmul_roofline.serve.json")
    floor = family.moe_bytes(5120, 2220, cfg) / 819e9
    monkeypatch.setattr(trace_named_ms, "newest_calls", lambda: [
        ("grouped_matmul_fwd", 1.5 * floor), ("ragged_attention_fwd", 1.0),
        ("grouped_matmul_fwd", 0.5 * floor)])
    assert kernel_roofline.read(run, **spec["args"]) == pytest.approx(50.0)
    ms = harness.load_json(harness.HERE, "layer_metrics",
                           "kernel_ms.grouped_matmul.serve.json")
    assert trace_named_ms.read(run, **ms["args"]) \
        == pytest.approx(1e3 * 2 * floor / 5)
    monkeypatch.setattr(trace_named_ms, "newest_calls",
                        lambda: [("grouped_matmul_fwd", 0.9 * floor)])
    with pytest.raises(ValueError, match="roofline share of 111"):
        kernel_roofline.read(run, **spec["args"])
    # beside a program without the kernel: nothing to read
    monkeypatch.setattr(trace_named_ms, "newest_calls",
                        lambda: [("ragged_attention_fwd", 1.0)])
    assert kernel_roofline.read(run, **spec["args"]) is None


def test_config_file_keeps_every_published_key():
    """Every key of the catalog row's ``config`` under the same name and
    value, but the three listed in ``reduced``; no width among them."""
    cfg = harness.load_json(harness.HERE, "configs", "Trinity-Mini.json")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "Trinity-Mini"]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: cfg[k] for k in published} == published
    whole = cfg["published_layer_types"]
    assert len(whole) == 32 == cfg["published_layers"]
    assert cfg["published_num_dense_layers"] == 2
    assert cfg["layer_types"] == [whole[1]] + whole[4:8] == [S, S, S, S, F]
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert set(cfg["assumed"]) >= {"output_gate", "sandwich_norm", "qk_norm",
                                   "positions", "embedding_scale", "router"}
    assert set(cfg["changed"]) >= {"initializer", "expert_bias"}
    traffic = harness.load_json(harness.HERE, "traffic",
                                "mixedlen-closed32.json")
    assert traffic["engine"]["block_size"] == cfg["kv_block_size"]
    assert traffic["runner"] == "serve_closed_counted"
    assert set(traffic["counters"]) >= {
        "moe_assignments", "moe_experts_touched", "moe_plan_rows",
        "kv_blocks_read_window", "kv_blocks_read_full", "kv_blocks_context"}
