"""The ``LongCat-Flash-Omni.longctx16k-closed32`` cell's own tests: CPU,
quick.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The cell end to end at a tiny size with ``--trace 0`` and ``1``, every
counter of the traffic file in ``engine.stats()``, the work functions on
hand-reckoned counts, the new metric files, the config file against the
catalog row and against itself.  The model against its reference and
the program's pieces are ``tests/test_longcat_flash.py``'s.
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
from benchmarks.families import longcat_flash as family  # noqa: E402
from benchmarks.readers import kernel_roofline, trace_named_ms  # noqa: E402

CONFIG = "LongCat-Flash-Omni"
CELL = CONFIG + ".longctx16k-closed32"
MANIFEST = harness.load_manifest()
NEW_METRICS = ("kernel_ms.latent_attention.serve",
               "latent_attention_roofline.serve", "moe_zero_share.serve")
SHARED_METRICS = ("kernel_ms.grouped_matmul.serve",
                  "grouped_matmul_roofline.serve", "moe_plan_fill.serve")
# what the cell must not list: their readers find nothing in this model
ABSENT_METRICS = ("kernel_ms.ragged_attention.serve",
                  "ragged_attention_roofline.serve", "moe_held_share.serve",
                  "window_read_share.serve", "block_ms.recurrent.serve")
# two double layers at width 64: four MLA sublayers of 4 heads (a latent
# row of 32 + 16 lanes), 4 of 16 routed experts held (chip 0 of 4) beside
# 8 zero experts, top-6; prompts of 20 to 90 tokens in chunks of 16
TINY_LONGCAT = {
    "config": {
        "vocab_size": 128, "hidden_size": 64, "ffn_hidden_size": 96,
        "expert_ffn_hidden_size": 32, "num_layers": 2,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
        "qk_rope_head_dim": 16, "qk_nope_head_dim": 16, "v_head_dim": 16,
        "n_routed_experts": 4, "published_n_routed_experts": 16,
        "zero_expert_num": 8, "moe_topk": 6,
        "expert_shard": {"chips": 4, "index": 0},
        "max_position_embeddings": 512, "expert_bias_std": 0.01,
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
# ``TINY`` table, keyed by family, and a PR that adds a cell may not
# edit it: collected from this directory, hand the module pytest runs
# (by its basename here) the entry; ``tests/conftest.py`` does it for
# tier-1's copy.  The next ``benchmark`` PR moves the entry into the
# table (PERF.md section 7).
if __name__ == "test_longcat_flash_cell":
    import test_benchmark
    test_benchmark.TINY.setdefault("longcat_flash", TINY_LONGCAT)


def _line(out, key):
    return [json.loads(l) for l in out.splitlines() if f'"{key}"' in l][0]


def test_cell_end_to_end_at_tiny_size(capsys):
    result = harness.run_cell(CELL, 2 ** 31 + 13, 1.0, 0,
                              shrink=TINY_LONGCAT)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    out = capsys.readouterr().out
    counted = _line(out, "moe_zero_assignments")
    carried = counted["decode_rows_carried"] \
        + counted["prompt_tokens_carried"]
    # two expert layers, six picks a token; reports drain a step late
    assert abs(counted["moe_assignments_routed"] - 12 * carried) <= 12 * 20
    assert 0 < counted["moe_zero_assignments"] \
        < counted["moe_assignments_routed"]
    assert 0 < counted["moe_assignments"] <= counted["moe_plan_rows"]
    assert counted["latent_blocks_read"] > 0
    # four layers of four heads see at least one key a carried row
    assert counted["latent_pairs"] >= 16 * carried
    engine = _line(out, "moe_max_expert_rows")["engine"]
    # four latent layers, one row of 48 float32 lanes a token in a
    # 128-lane tile, 97 blocks
    assert engine["pool_bytes"] == 4 * 97 * 8 * 128 * 4
    check = _line(out, "check")["check"]
    assert check["requests_checked"] == 4 and check["in_flight_checked"] > 0
    assert check["worst_margin_std"] <= check["margin_limit_std"]


def test_every_counter_of_the_traffic_file_is_in_the_engines_stats():
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import GenerationEngine
    traffic = harness.load_json(harness.HERE, "traffic",
                                "longctx16k-closed32.json")
    cfg = {**harness.load_json(harness.HERE, "configs", CONFIG + ".json"),
           **TINY_LONGCAT["config"]}
    paddle.seed(1)
    engine = GenerationEngine(family.build(cfg),
                              **TINY_LONGCAT["traffic"]["engine"])
    try:
        engine.add_request(list(range(20)), max_new_tokens=3)
        while engine.has_unfinished():
            engine.step()
        stats = engine.stats()
    finally:
        engine.close()
    assert set(traffic["counters"]) | set(traffic["geometry"]) <= set(stats)
    carried = stats["decode_rows_carried"] + stats["prompt_tokens_carried"]
    assert stats["moe_assignments_routed"] == 2 * 6 * carried
    # 20 prompt tokens in chunks of 16 (one q-block of 16 tokens each),
    # then two decode rows at positions 20 and 21, in four layers
    assert stats["latent_blocks_read"] == 4 * (2 + 3 + 3 + 3)
    assert stats["latent_pairs"] == 4 * 4 * (
        sum(range(1, 21)) + 21 + 22)


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """``--trace 1`` with the recorded GPT-2 trace standing in: it holds
    ``ragged_attention_fwd`` calls and neither ``latent_attention_fwd``
    nor ``grouped_matmul_fwd``, so those kernels' metrics read nothing
    and are left out, as beside a program without the kernels; the
    counters' ratios are there."""
    from benchmarks import trace_reduce
    cut = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_serve_cut.xplane.pb")
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda path: real(cut))
    result = harness.run_cell(CELL, 2 ** 33 + 12, 1.0, 1, shrink={
        **TINY_LONGCAT, "peaks": {"cpu": {"bf16_flops_per_s": 1e10,
                                          "hbm_bytes_per_s": 1e8}}})
    got = result["metrics"]
    assert 10 < got["moe_zero_share.serve"]["value"] < 90
    assert 0 < got["moe_plan_fill.serve"]["value"] <= 100
    assert got["compiles_in_window.serve"]["value"] == 0
    for name in ("kernel_ms.latent_attention.serve",
                 "latent_attention_roofline.serve",
                 "kernel_ms.grouped_matmul.serve",
                 "grouped_matmul_roofline.serve") + ABSENT_METRICS:
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
    for name in ABSENT_METRICS:
        assert CELL not in per_layer[name]["workloads"]
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_work_functions_on_hand_reckoned_counts():
    cfg = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
    # one decode row at 12,288 tokens in eight latent layers: 192 blocks
    # of 64 rows of 576 lanes each, 64 heads' 576-lane queries in and
    # 512-lane sums out
    assert family.latent_attention_bytes(8 * 192, 0, 1, 0, cfg) == (
        8 * 192 * 64 * 576 + 8 * 64 * (576 + 512)) * 2 == 114360320
    # 64 heads over 12,288 keys, eight layers: the score over 576 lanes
    # and the sum over 512
    pairs = 8 * 64 * 12288
    assert family.latent_attention_flops(0, pairs, 1, 0, cfg) \
        == pairs * 2 * (576 + 512) == 13690208256
    # a decode row is bound by its bytes, a 1,024-token chunk by its
    # operations
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert 114360320 / peaks["hbm_bytes_per_s"] \
        > 13690208256 / peaks["bf16_flops_per_s"]
    chunk_pairs = 8 * 64 * (1024 * 11264 + 1024 * 1025 // 2)
    chunk_blocks = 8 * sum(min(192, (a + 15) // 64 + 1)
                           for a in range(11264, 12288, 16))
    assert family.latent_attention_flops(chunk_blocks, chunk_pairs, 0,
                                         1024, cfg) / peaks[
        "bf16_flops_per_s"] > family.latent_attention_bytes(
        chunk_blocks, chunk_pairs, 0, 1024, cfg) / peaks["hbm_bytes_per_s"]
    # the expert kernel: one decode row's twelve choices, of which two
    # fall on held experts, in four layers
    assert family.moe_flops(4 * 2, 4 * 2, cfg) == 8 * 3 * 2 * 6144 * 2048
    assert family.moe_bytes(4 * 2, 4 * 2, cfg) \
        == (8 * 3 * 6144 * 2048 + 8 * 2 * (6144 + 2048)) * 2


def test_roofline_reads_the_latent_kernel(monkeypatch):
    cfg = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
    work = (8 * 5000, 8 * 64 * 3_000_000, 150, 4096)
    run = {"samples": {"traced_steps": 5,
                       "traced_latent_blocks_read": work[0],
                       "traced_latent_pairs": work[1],
                       "traced_decode_rows_carried": work[2],
                       "traced_prompt_tokens_carried": work[3]},
           "config": cfg, "family": family,
           "peaks": harness.load_json(harness.HERE, "peaks.json"),
           "device": {"kind": "TPU v5 lite"}}
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "latent_attention_roofline.serve.json")
    floor = max(family.latent_attention_bytes(*work, cfg) / 819e9,
                family.latent_attention_flops(*work, cfg) / 197e12)
    monkeypatch.setattr(trace_named_ms, "newest_calls", lambda: [
        ("latent_attention_fwd", 2.5 * floor), ("grouped_matmul_fwd", 1.0),
        ("latent_attention_fwd", 1.5 * floor)])
    assert kernel_roofline.read(run, **spec["args"]) == pytest.approx(25.0)
    ms = harness.load_json(harness.HERE, "layer_metrics",
                           "kernel_ms.latent_attention.serve.json")
    assert trace_named_ms.read(run, **ms["args"]) \
        == pytest.approx(1e3 * 4 * floor / 5)
    # beside a program without the kernel: nothing to read
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
        "num_layers", "n_routed_experts", "vocab_size"]
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/"
        "main/config.json")
    published = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    assert {k: cfg[k] for k in published} == published
    shard = cfg["expert_shard"]
    assert shard == {"chips": 64, "index": 0}
    assert cfg["n_routed_experts"] * shard["chips"] \
        == cfg["published_n_routed_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"] == 131072
    assert family.held_experts(cfg) == (0, 8)
    assert cfg["published_layers"] == 28 and cfg["num_layers"] == 4
    assert set(cfg["assumed"]) >= {"double_layer", "mla", "positions",
                                   "router", "experts", "left_out"}
    assert set(cfg["changed"]) >= {"initializer", "expert_bias",
                                   "norm_weights"}
    assert "64" in cfg["deployment"] and "chip 0" in cfg["deployment"]
    traffic = harness.load_json(harness.HERE, "traffic",
                                "longctx16k-closed32.json")
    assert traffic["engine"] == {
        "max_batch": 32, "max_model_len": 17408, "num_blocks": 8704,
        "block_size": cfg["kv_block_size"], "prefill_chunk": 1024}
    # the pool holds the worst case: 32 rows of the longest sequence
    assert traffic["engine"]["num_blocks"] * 64 == 32 * 17408
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == 17408
    assert traffic["runner"] == "serve_closed_counted"
    assert set(traffic["counters"]) >= {
        "moe_assignments", "moe_experts_touched", "moe_assignments_routed",
        "moe_plan_rows", "moe_zero_assignments", "latent_blocks_read",
        "latent_pairs", "decode_rows_carried", "prompt_tokens_carried"}
