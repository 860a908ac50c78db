"""The ``Qwen3-Next-80B-A3B-Instruct.longctx24k-closed32`` cell's own
tests: CPU, quick.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The cell end to end at a tiny size with ``--trace 0`` and ``1``, the
work functions on hand-made counts, the new metric files, the config
file against the catalog row and against itself.  The model against its
reference and the program's pieces are ``tests/test_qwen3_next.py``'s.
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
from benchmarks.families import qwen3_next as family  # noqa: E402
from benchmarks.readers import kernel_roofline, trace_named_ms  # noqa: E402

CONFIG = "Qwen3-Next-80B-A3B-Instruct"
CELL = CONFIG + ".longctx24k-closed32"
MANIFEST = harness.load_manifest()
NEW_METRICS = ("kernel_ms.gated_delta_rule.serve",
               "gated_delta_rule_roofline.serve", "moe_held_share.serve")
SHARED_METRICS = ("kernel_ms.grouped_matmul.serve",
                  "grouped_matmul_roofline.serve",
                  "ragged_attention_roofline.serve", "moe_plan_fill.serve")
# the published pattern at width 64: two periods of (delta, delta,
# delta, attention), 4 of 16 experts held (chip 0 of 4), top-4; prompts
# of 20 to 90 tokens in chunks of 16
TINY_QWEN3_NEXT = {
    "config": {
        "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "linear_key_head_dim": 16, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_value_head_dim": 16,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts": 4, "published_num_experts": 16,
        "num_experts_per_tok": 4, "max_position_embeddings": 512,
        "kv_block_size": 8, "dtype": "float32"},
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
if __name__ == "test_qwen3_next_cell":
    import test_benchmark
    test_benchmark.TINY.setdefault("qwen3_next", TINY_QWEN3_NEXT)


def _line(out, key):
    return [json.loads(l) for l in out.splitlines() if f'"{key}"' in l][0]


def test_cell_end_to_end_at_tiny_size(capsys):
    result = harness.run_cell(CELL, 2 ** 31 + 9, 1.0, 0,
                              shrink=TINY_QWEN3_NEXT)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    out = capsys.readouterr().out
    counted = _line(out, "moe_assignments")
    carried = counted["decode_rows_carried"] \
        + counted["prompt_tokens_carried"]
    # eight expert layers, top-4 of 16; reports are drained a step late
    assert abs(counted["moe_assignments_routed"] - 32 * carried) <= 32 * 20
    assert 0 < counted["moe_assignments"] < counted["moe_assignments_routed"]
    assert counted["moe_assignments"] <= counted["moe_plan_rows"]
    assert 0 < counted["moe_experts_touched"]
    assert counted["kv_blocks_read_full"] > 0
    assert counted["kv_blocks_read_window"] == 0
    engine = _line(out, "state_pool_bytes")["engine"]
    # six delta layers, five slots (the pad slot): a float32 state a
    # value head and the convolution's three last inputs in the model's
    # type
    assert engine["state_pool_bytes"] == 6 * 5 * (4 * 16 * 16 * 4
                                                  + 3 * 128 * 4)
    assert engine["state_slots"] == 4 and engine["state_resets"] > 4
    assert engine["moe_max_expert_rows"] > 0
    check = _line(out, "check")["check"]
    assert check["requests_checked"] == 4 and check["in_flight_checked"] > 0
    assert check["worst_margin_std"] <= check["margin_limit_std"]


def test_every_counter_of_the_traffic_file_is_in_the_engines_stats():
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import GenerationEngine
    traffic = harness.load_json(harness.HERE, "traffic",
                                "longctx24k-closed32.json")
    cfg = {**harness.load_json(harness.HERE, "configs", CONFIG + ".json"),
           **TINY_QWEN3_NEXT["config"]}
    paddle.seed(1)
    engine = GenerationEngine(family.build(cfg),
                              **TINY_QWEN3_NEXT["traffic"]["engine"])
    try:
        engine.add_request(list(range(20)), max_new_tokens=3)
        while engine.has_unfinished():
            engine.step()
        stats = engine.stats()
    finally:
        engine.close()
    assert set(traffic["counters"]) | set(traffic["geometry"]) <= set(stats)
    assert stats["moe_assignments_routed"] == 8 * 4 * (
        stats["decode_rows_carried"] + stats["prompt_tokens_carried"])


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """``--trace 1`` with the recorded GPT-2 trace standing in: it holds
    ``ragged_attention_fwd`` calls and neither ``grouped_matmul_fwd``
    nor ``gated_delta_rule*_fwd``, so those kernels' metrics read
    nothing and are left out, as beside a program without the kernels;
    the counters' ratios are there."""
    from benchmarks import trace_reduce
    cut = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_serve_cut.xplane.pb")
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda path: real(cut))
    result = harness.run_cell(CELL, 12, 1.0, 1, shrink={
        **TINY_QWEN3_NEXT, "peaks": {"cpu": {"bf16_flops_per_s": 1e10,
                                             "hbm_bytes_per_s": 1e8}}})
    got = result["metrics"]
    assert 15 < got["moe_held_share.serve"]["value"] < 35
    assert 0 < got["moe_plan_fill.serve"]["value"] <= 100
    assert got["compiles_in_window.serve"]["value"] == 0
    assert got["kernel_ms.ragged_attention.serve"]["value"] > 0
    assert 0 < got["ragged_attention_roofline.serve"]["value"] <= 100
    for name in ("kernel_ms.grouped_matmul.serve",
                 "grouped_matmul_roofline.serve",
                 "kernel_ms.gated_delta_rule.serve",
                 "gated_delta_rule_roofline.serve"):
        assert name not in got


def test_metric_files_resolve():
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_METRICS + SHARED_METRICS:
        entry = per_layer[name]
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 name + ".json")
        assert CELL in entry["workloads"]
        assert (name in NEW_METRICS) == (entry["workloads"] == [CELL])
        assert (entry["unit"], entry["layer"], entry["moves"]) \
            == (spec["unit"], spec["layer"], "itl_p95_ms")
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        assert callable(reader.read)
        for fn in (spec["args"].get("flops"), spec["args"].get("bytes")):
            assert fn is None or callable(getattr(family, fn))
    # every serving metric the other three serving cells report, here too
    others = [c["name"] for c in MANIFEST["workloads"]
              if c["name"] != CELL and "closed32" in c["traffic"]]
    assert len(others) == 3
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        cells = m.get("workloads", [])
        if all(c in cells for c in others):
            assert CELL in cells, m["name"]
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_work_functions_on_hand_reckoned_counts():
    cfg = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
    # one decode row in six delta layers: 32 states of 128 x 128 float32
    # read and written, q and k of 16 heads, v and o of 32, g and beta
    row_bytes = 6 * (2 * 32 * 128 * 128 * 4
                     + (2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 32 * 4)
    assert family.gated_delta_bytes(1, 0, 0, cfg) == row_bytes == 25314816
    assert family.gated_delta_flops(1, 0, 0, cfg) \
        == 6 * 32 * 7 * 128 * 128 == 22020096
    # one 64-token chunk: the states once, 64 tokens of q, k, v, o, g, beta
    chunk_bytes = 6 * (2 * 32 * 128 * 128 * 4
                       + 64 * ((2 * 16 * 128 + 2 * 32 * 128) * 2
                               + 2 * 32 * 4))
    assert family.gated_delta_bytes(0, 64, 1, cfg) == chunk_bytes \
        == 34701312
    # K K^T, Q K^T; K S, Q S, K^T U; the solve and (Q K^T) U
    chunk_flops = 6 * 32 * (4 * 64 * 64 * 128 + 6 * 64 * 128 * 128
                            + 4 * 64 * 64 * 128)
    assert family.gated_delta_flops(0, 64, 1, cfg) == chunk_flops \
        == 2013265920
    # a decode row is bound by its state's bytes, by far
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert row_bytes / peaks["hbm_bytes_per_s"] \
        > 100 * 22020096 / peaks["bf16_flops_per_s"]
    # the expert kernel: one decode row's ten choices, of which three
    # fall on three held experts, in eight layers
    assert family.moe_flops(8 * 3, 8 * 3, cfg) == 24 * 3 * 2 * 2048 * 512
    assert family.moe_bytes(8 * 3, 8 * 3, cfg) \
        == (24 * 3 * 2048 * 512 + 24 * 2 * (2048 + 512)) * 2
    # a row at 12,288 tokens reads 192 blocks in each of two layers: K
    # and V, two KV heads of 256 lanes
    assert family.attention_bytes(0, 2 * 192, cfg) \
        == 384 * 64 * 2 * 256 * 2 * 2
    assert family.attention_flops(0, 2 * 192, cfg) \
        == 384 * 64 * 16 * 256 * 4


def test_roofline_reads_the_two_delta_kernels(monkeypatch):
    cfg = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
    run = {"samples": {"traced_steps": 5, "traced_decode_rows_carried": 155,
                       "traced_prompt_tokens_carried": 4096,
                       "traced_prefill_chunks": 4},
           "config": cfg, "family": family,
           "peaks": harness.load_json(harness.HERE, "peaks.json"),
           "device": {"kind": "TPU v5 lite"}}
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "gated_delta_rule_roofline.serve.json")
    floor = max(family.gated_delta_bytes(155, 4096, 4, cfg) / 819e9,
                family.gated_delta_flops(155, 4096, 4, cfg) / 197e12)
    monkeypatch.setattr(trace_named_ms, "newest_calls", lambda: [
        ("gated_delta_rule_fwd", 2.5 * floor), ("grouped_matmul_fwd", 1.0),
        ("gated_delta_rule_step_fwd", 1.5 * floor)])
    assert kernel_roofline.read(run, **spec["args"]) == pytest.approx(25.0)
    ms = harness.load_json(harness.HERE, "layer_metrics",
                           "kernel_ms.gated_delta_rule.serve.json")
    assert trace_named_ms.read(run, **ms["args"]) \
        == pytest.approx(1e3 * 4 * floor / 5)
    # beside a program without the kernels: nothing to read
    monkeypatch.setattr(trace_named_ms, "newest_calls",
                        lambda: [("ragged_attention_fwd", 1.0)])
    assert kernel_roofline.read(run, **spec["args"]) is None
    assert trace_named_ms.read(run, **ms["args"]) is None


def test_config_file_keeps_every_published_key_and_agrees_with_itself():
    """Every key of the catalog row's ``config`` under the same name and
    value, but the three listed in ``reduced``; no width among them; the
    share stated three ways agrees."""
    cfg = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    shard = cfg["expert_shard"]
    assert shard == {"chips": 4, "index": 0}
    assert cfg["num_experts"] * shard["chips"] \
        == cfg["published_num_experts"] == 512
    assert cfg["vocab_size"] * shard["chips"] \
        == cfg["published_vocab_size"] == 151936
    assert family.held_experts(cfg) == (0, 128)
    assert cfg["published_layers"] == 48 and cfg["num_hidden_layers"] == 8
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert set(cfg["assumed"]) >= {"positions", "projections", "delta_rule",
                                   "convolution", "router", "dtype"}
    assert set(cfg["changed"]) >= {"initializer", "decay", "norm_weights"}
    assert "four" in cfg["deployment"] and "128" in cfg["deployment"]
    traffic = harness.load_json(harness.HERE, "traffic",
                                "longctx24k-closed32.json")
    assert traffic["engine"] == {
        "max_batch": 32, "max_model_len": 26624, "num_blocks": 13312,
        "block_size": cfg["kv_block_size"], "prefill_chunk": 1024}
    # the pool holds the worst case: 32 rows of the longest sequence
    assert traffic["engine"]["num_blocks"] * 64 == 32 * 26624
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == 26624
    assert traffic["runner"] == "serve_closed_counted"
    assert traffic["pool"] == {"size": 64, "seed": 20261004}
    assert set(traffic["counters"]) >= {
        "moe_assignments", "moe_experts_touched", "moe_assignments_routed",
        "moe_plan_rows", "kv_blocks_read_window", "kv_blocks_read_full",
        "decode_rows_carried", "prompt_tokens_carried", "prefill_chunks"}
