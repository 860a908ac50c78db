"""The cell ``MiniCPM-SALA.longdoc-closed32`` and its readers on the
CPU: the cell through ``run_cell(..., shrink=...)`` at tiny widths, the
two new readers against hand-made calls and samples, and the config
file against the catalog's rules."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, span_reduce  # noqa: E402
from benchmarks.families import minicpm_sala as family  # noqa: E402
from benchmarks.readers import kernel_roofline, trace_named_ms  # noqa: E402

CELL = "MiniCPM-SALA.longdoc-closed32"
MANIFEST = harness.load_manifest()
# four layers of width 64 (a sparse layer, two lightning layers, a
# sparse layer); the selector's sizes shrink with the contexts so that
# a prompt of 100 tokens prunes
TINY_SALA = {
    "config": {
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 4,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4"],
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
        "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
        "max_position_embeddings": 512, "dim_model_base": 16,
        "dtype": "float32",
        "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                          "block_size": 16, "window_size": 32,
                          "init_blocks": 1, "dense_len": 64, "topk": 2}},
    "traffic": {
        "clients": 4,
        "engine": {"max_batch": 4, "max_model_len": 256, "num_blocks": 64,
                   "block_size": 16, "prefill_chunk": 32},
        "prompt_len": {"dist": "lognormal", "median": 90, "sigma": 0.4,
                       "min": 40, "max": 160},
        "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                       "min": 4, "max": 24},
        "check": {"requests": 4, "in_flight": 2, "in_flight_min_tokens": 2,
                  "pad_multiple": 64, "margin_limit_std": 0.1,
                  "mean_margin_limit_std": 0.0018}},
    "peaks": {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}


def test_cell_end_to_end_at_tiny_size(capsys):
    result = harness.run_cell(CELL, 2 ** 31 + 9, 1.0, 0, shrink=TINY_SALA)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    out = capsys.readouterr().out
    counted = [json.loads(l) for l in out.splitlines()
               if '"sparse_blocks_selected"' in l][0]
    # past 64 tokens the selection prunes
    assert 0 < counted["sparse_blocks_selected"] \
        < counted["sparse_blocks_visible"]
    assert counted["decode_rows_carried"] > 0
    check = [json.loads(l) for l in out.splitlines()
             if '"check"' in l][0]["check"]
    # ended requests, and successors in flight in reused slots
    assert check["requests_checked"] == 4 and check["in_flight_checked"] > 0
    assert check["positions_checked"] > check["in_flight_positions"] > 0
    assert check["worst_margin_std"] <= check["margin_limit_std"]
    assert check["mean_margin_std"] <= check["mean_margin_limit_std"]


def test_the_reference_at_a_lower_precision_is_another_function():
    """The check's control: the reference held in float8 moves the
    hidden states by about a rounding (2^-4), float32 leaves them."""
    import jax.numpy as jnp
    import numpy as np
    a = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)),
                    jnp.float32)
    assert family.held_in(a, None) is a
    low = family.held_in(a, jnp.float8_e4m3fn)
    err = float(jnp.abs(low - a).max() / jnp.abs(a).max())
    assert 0 < err <= 2 ** -4


def test_traced_run_reports_the_new_per_layer_metrics(monkeypatch):
    """``--trace 1`` with the recorded GPT-2 trace standing in: it holds
    ``ragged_attention_fwd`` calls and neither new kernel, so the two
    ``kernel_ms`` metrics and the lightning roofline read nothing and
    are left out, as beside a program without the kernels."""
    from benchmarks import trace_reduce
    cut = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_serve_cut.xplane.pb")
    real = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda path: real(cut))
    result = harness.run_cell(CELL, 12, 1.0, 1, shrink={
        **TINY_SALA, "peaks": {"cpu": {"bf16_flops_per_s": 1e10,
                                       "hbm_bytes_per_s": 1e8}}})
    got = result["metrics"]
    assert 0 < got["sparse_selected_share.serve"]["value"] < 100
    assert got["compiles_in_window.serve"]["value"] == 0
    assert got["kernel_ms.ragged_attention.serve"]["value"] > 0
    assert "kernel_ms.lightning_attention.serve" not in got
    assert "lightning_attention_roofline.serve" not in got
    assert 0 < got["sparse_attention_roofline.serve"]["value"] <= 100


CALLS = [("lightning_attention_fwd", 0.004),
         ("lightning_attention_step_fwd", 0.001),
         ("lightning_attention_fwd", 0.004), ("sparse_select_fwd", 0.002),
         ("ragged_attention_fwd", 0.010), ("pure_fwd", 0.5)]


def test_a_kernel_outside_span_reduce_is_read(monkeypatch):
    assert "lightning_attention" not in span_reduce.KERNELS
    assert span_reduce.kernel_of(
        '%lightning_attention_fwd.7 = bf16[1024,4096]{1,0} custom-call('
        'bf16[1024,4096] %p), custom_call_target="tpu_custom_call"') \
        == span_reduce.UNNAMED
    monkeypatch.setattr(trace_named_ms, "newest_calls", lambda: CALLS)
    run = {"samples": {"traced_steps": 5}}
    assert trace_named_ms.read(run, names=[
        "lightning_attention_fwd", "lightning_attention_step_fwd"]) \
        == pytest.approx(1e3 * 0.009 / 5)
    assert trace_named_ms.read(run, names=["sparse_select_fwd"]) \
        == pytest.approx(0.4)
    assert trace_named_ms.read(run, names=["no_such_kernel_fwd"]) is None
    assert trace_named_ms.read({"samples": {}}, names=["pure_fwd"]) is None


def test_instruction_names_are_matched_in_a_recorded_trace():
    """The v5e cut of PR 26 holds the GPT-2 engine's step:
    ``ragged_attention_fwd`` is found by this reader's own rule."""
    calls = trace_named_ms.custom_calls(span_reduce.newest_trace())
    names = {n for n, _ in calls}
    assert "ragged_attention_fwd" in names
    total = trace_named_ms.seconds(calls, ["ragged_attention_fwd"])
    assert total == pytest.approx(
        span_reduce.reduction()["kernels"]["ragged_attention"]["s"])


def roofline_run():
    cfg = harness.load_json(harness.HERE, "configs", "MiniCPM-SALA.json")
    return {"samples": {"traced_steps": 5,
                        "traced_decode_rows_carried": 150,
                        "traced_prompt_tokens_carried": 2048,
                        "traced_prefill_chunks": 2},
            "config": cfg, "family": family,
            "peaks": harness.load_json(harness.HERE, "peaks.json"),
            "device": {"kind": "TPU v5 lite"}}, cfg


def test_roofline_share_and_its_refusal(monkeypatch):
    run, cfg = roofline_run()
    args = dict(names=["lightning_attention_fwd",
                       "lightning_attention_step_fwd"],
                flops="lightning_flops", bytes="lightning_bytes",
                work=["traced_decode_rows_carried",
                      "traced_prompt_tokens_carried",
                      "traced_prefill_chunks"])
    nbytes = family.lightning_bytes(150, 2048, 2, cfg)
    # nine layers: 152 states of 2 MB in and out, q, k, v, o a token
    assert nbytes == 9 * (2 * 32 * 128 * 128 * 4 * 152
                          + 4 * 32 * 128 * 2 * (150 + 2048))
    floor = nbytes / 819e9            # bandwidth binds: 7 FLOP a byte
    assert family.lightning_flops(150, 2048, 2, cfg) / 197e12 < floor
    monkeypatch.setattr(trace_named_ms, "newest_calls",
                        lambda: [("lightning_attention_fwd", 4 * floor)])
    assert kernel_roofline.read(run, **args) == pytest.approx(25.0)
    # less time than the chip's peaks allow: an error, not a reading
    monkeypatch.setattr(trace_named_ms, "newest_calls",
                        lambda: [("lightning_attention_fwd", 0.9 * floor)])
    with pytest.raises(ValueError, match="roofline share of 111"):
        kernel_roofline.read(run, **args)
    # beside a program without the kernel or its counters: nothing
    monkeypatch.setattr(trace_named_ms, "newest_calls", lambda: CALLS[-1:])
    assert kernel_roofline.read(run, **args) is None
    del run["samples"]["traced_prefill_chunks"]
    monkeypatch.setattr(trace_named_ms, "newest_calls", lambda: CALLS)
    assert kernel_roofline.read(run, **args) is None


def test_sparse_work_counts_selected_blocks_and_pooled_keys():
    cfg = harness.load_json(harness.HERE, "configs", "MiniCPM-SALA.json")
    # one row at 12,000 tokens: 98 of 188 blocks, 3 layers, 2 KV heads
    sel, vis = 98 * 6, 188 * 6
    assert family.sparse_bytes(sel, vis, cfg) \
        == (sel * 2 * 64 + vis * 4) * 128 * 2
    assert family.sparse_flops(sel, vis, cfg) \
        == 16 * 128 * (sel * 4 * 64 + vis * 2 * 4)


def test_config_file_keeps_every_published_key():
    """Every key of the catalog row's ``config`` under the same name
    and value, but the two listed in ``reduced``; no width among them."""
    cfg = harness.load_json(harness.HERE, "configs", "MiniCPM-SALA.json")
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "MiniCPM-SALA"]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                  "mixer_types"]
    assert cfg["source"] == entry["source"]
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06,
        "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
        "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    assert {k: cfg[k] for k in published} == published
    whole = cfg["published_mixer_types"]
    assert len(whole) == 32 and cfg["published_layers"] == 32
    assert cfg["mixer_types"] == whole[9:21] and cfg["num_hidden_layers"] == 12
    assert cfg["mixer_types"].count("minicpm4") == 3
    assert set(cfg["assumed"]) >= {"lightning_decay", "lightning_state_dtype",
                                   "sparse_config", "qk_norm_extent",
                                   "output_norm_extent"}
    traffic = harness.load_json(harness.HERE, "traffic",
                                "longdoc-closed32.json")
    assert traffic["engine"]["block_size"] \
        == cfg["sparse_config"]["block_size"]
