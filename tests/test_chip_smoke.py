"""`chip_smoke.py`'s phases at a tiny size on the CPU (the first
rehearsal of the `on-chip-measurement` guide), and its refusal to
start without a chip.  Off the chip every kernel call site takes the
XLA composite, so the kernel lists are empty and kernels-on equals
kernels-off; what is tested here is the control flow and the checks."""
import math
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402
from paddle_tpu.models import BertConfig, GPTConfig  # noqa: E402

BERT = BertConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                  num_attention_heads=2, intermediate_size=256,
                  max_position_embeddings=64)
GPT = GPTConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                num_attention_heads=2, max_position_embeddings=128)


def test_train_static_tiny():
    out = chip_smoke.train_static(BERT, 4, 32, steps=4, fused_steps=2)
    c = out["checked"]
    assert abs(c["loss_step0"] - math.log(512)) < 0.05 * math.log(512)
    assert c["loss_run_steps"] < c["loss_last"] < c["loss_step0"]
    assert c["loss_step0_composites"] == c["loss_step0"]
    assert out["kernels"] == {}


def test_attention_dropout_tiny():
    """Interpret mode: the hash stands in for the chip's generator."""
    out = chip_smoke.attention_dropout(BERT, 2, 48, depth=1)
    c = out["checked"]
    assert abs(c["keep_rate"] - 0.9) < 4 * c["keep_rate_sigma"]
    assert set(c["rel_l2_vs_masked_composite"]) == {"fwd", "dq", "dk", "dv"}
    assert max(c["rel_l2_vs_masked_composite"].values()) < 3e-2
    assert out["kernels"] == {}


@pytest.mark.parametrize("counted_before", [0, 3])
def test_hidden_dropout_tiny(counted_before):
    """Interpret mode: the hash stands in for the chip's generator; 96
    rows of 128, one block.  The paths reported are the phase's own,
    whatever the process counted before it."""
    from paddle_tpu import observability as obs
    prev = obs.enable(True)
    for path in ("plain", "composite.gate"):
        obs.get_registry().counter(
            f"layer_norm_residual.path.{path}").inc(counted_before)
    obs.enable(prev)
    out = chip_smoke.hidden_dropout(BERT, 2, 48, depth=1)
    c = out["checked"]
    assert abs(c["keep_rate"] - 0.9) < 3 * c["keep_rate_sigma"]
    assert set(c["rel_l2_vs_masked_composite"]) == {
        "fwd", "d_x", "d_residual", "d_gamma", "d_beta"}
    assert max(c["rel_l2_vs_masked_composite"].values()) < 3e-2
    assert c["dropped_with_gradient"] == 0
    # off the chip the gate is closed: the op's composite, once a norm
    assert c["paths"] == {"composite.gate": 2} and out["kernels"] == {}


def test_loss_head_tiny():
    """Interpret mode: 96 rows of 2,298 logits, so the second vocabulary
    block holds 250 real columns and the rest is masked in the kernel."""
    import dataclasses
    out = chip_smoke.loss_head(
        dataclasses.replace(BERT, vocab_size=2048 + 250), 2, 48)
    c = out["checked"]
    assert c["blocks"] == [96, 2048] and c["ignored_rows"] == 14
    assert c["ignored_rows_with_gradient"] == 0
    assert set(c["rel_l2_vs_composite"]) == {"loss", "d_logits"}
    assert max(c["rel_l2_vs_composite"].values()) <= c["rel_l2_limit"]
    assert out["kernels"] == {}


@pytest.mark.parametrize("lazy_tier", [False, True])
def test_train_eager_tiny(lazy_tier):
    out = chip_smoke.train_eager(BERT, 4, 32, steps=3,
                                 lazy_tier=lazy_tier)
    c = out["checked"]
    if lazy_tier:      # the whole step flushes as a segment or two
        assert c["tier"] == "lazy" and c["launches_per_steady_step"] <= 2
    else:
        assert c["tier"] == "per-op"
        assert c["launches_per_steady_step"] > 10
    assert c["losses"][-1] < c["losses"][0]
    assert out["kernels"] == {}


def test_serve_sala_tiny():
    """Float32 at width 64: the served rows equal the reference to
    rounding, the contexts prune, and rounding the scores to bfloat16
    flips some choices in the reference."""
    config = {**chip_smoke.sala_config(), "vocab_size": 97,
              "hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 8, "num_key_value_heads": 2,
              "head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
              "lightning_head_dim": 16, "dim_model_base": 16,
              "dtype": "float32",
              "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                                "block_size": 16, "window_size": 32,
                                "init_blocks": 1, "dense_len": 64,
                                "topk": 2}}
    out = chip_smoke.serve_sala(config, [150, 40], new_tokens=4, engine={
        "max_batch": 2, "block_size": 16, "num_blocks": 32,
        "max_model_len": 256, "prefill_chunk": 32})
    c = out["checked"]
    assert c["positions"] == 8 and c["state_resets"] == 2
    assert c["worst_row_rel_l2_vs_reference"] < 1e-4
    assert c["sparse_blocks_selected"] < c["sparse_blocks_visible"]
    assert c["block_choices_flipped_by_bf16_scores"] >= 0
    assert c["step_program_compiles"] == 1 and out["kernels"] == {}


def test_serve_afmoe_tiny():
    """Float32 at width 64: the served rows equal the reference to
    rounding while the windowed group releases."""
    config = {**chip_smoke.afmoe_config(), "vocab_size": 97,
              "hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "sliding_window": 16, "num_experts": 16,
              "num_experts_per_tok": 4, "kv_block_size": 8,
              "dtype": "float32"}
    out = chip_smoke.serve_afmoe(config, [70, 12], new_tokens=4, engine={
        "max_batch": 2, "block_size": 8, "num_blocks": 32,
        "max_model_len": 128, "prefill_chunk": 16})
    c = out["checked"]
    assert c["positions"] == 8
    assert c["worst_row_rel_l2_vs_reference"] < 1e-4
    assert chip_smoke.afmoe_config((1, 4), "float32")["num_dense_layers"] \
        == 0
    assert c["greedy_tokens_the_reference_agrees_with"] == 8
    assert c["window_blocks_released"] > 0
    assert c["kv_blocks_read_window"] < c["kv_blocks_context"]
    assert c["step_program_compiles"] == 1 and out["kernels"] == {}


def test_serve_qwen3_next_tiny():
    """Float32 at width 64, one period, a quarter of 16 experts held:
    the served rows equal the reference of the same share to rounding,
    through both pools of the delta layers."""
    config = {**chip_smoke.qwen3_next_config(chips=4), "vocab_size": 97,
              "hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "linear_key_head_dim": 16, "linear_num_key_heads": 2,
              "linear_num_value_heads": 4, "linear_value_head_dim": 16,
              "moe_intermediate_size": 32,
              "shared_expert_intermediate_size": 32, "num_experts": 4,
              "published_num_experts": 16, "num_experts_per_tok": 4,
              "kv_block_size": 8}
    assert config["num_hidden_layers"] == 4 and config["dtype"] == "float32"
    assert chip_smoke.qwen3_next_config()["num_experts"] == 16
    out = chip_smoke.serve_qwen3_next(config, [70, 12], new_tokens=4,
                                      engine={
        "max_batch": 2, "block_size": 8, "num_blocks": 32,
        "max_model_len": 128, "prefill_chunk": 16})
    c = out["checked"]
    assert c["positions"] == 8 and c["state_resets"] == 2
    assert c["worst_row_rel_l2_vs_reference"] < 1e-5
    assert c["greedy_tokens_the_reference_agrees_with"] == 8
    assert 0 < c["moe_assignments"] < c["moe_assignments_routed"]
    assert c["step_program_compiles"] == 1 and out["kernels"] == {}


def test_sampler_gate_tiny():
    """Five rows of 301 logits: tokens of the gated sampler against the
    ungated one, greedy and with one sampling row; no device, no time."""
    c = chip_smoke.sampler_gate(5, 301)["checked"]
    assert c["tokens_off_the_argmax"]["greedy"] == 0
    assert set(c["device_ms"]) == {"greedy", "one_sampling_row",
                                   "ungated_greedy"}
    assert set(c["device_ms"].values()) == {None}


def test_ragged_walk_tiny():
    """The three forms of a step's attention call at width 64 (two KV
    heads a 128-lane row of the pool), three rows and tables of four
    blocks: the kernel (interpreted) against the fallback, the blocks
    counted, the scatter and the kernel on donated pools run; no
    device, no time."""
    calls = (("t.step", "mixed", 1, 2, 64, 16, 4, None, 32),
             ("t.decode", "decode", 2, 2, 64, 16, 4, 24, 0),
             ("t.chunk", "chunk", 2, 2, 64, 16, 4, None, 32))
    c = chip_smoke.ragged_walk(calls, rows=3, chunk_bq=16)["checked"]
    assert len(c) == 6 and all(
        "us" not in e and "scatter_kernel_us" not in e for e in c.values())
    assert all(e["rel_l2_vs_fallback"] < chip_smoke.BF16_REL_L2
               for k, e in c.items() if k.endswith("@0.25"))
    # full tables: 56 keys of 64.  Two decode rows read four blocks of
    # two KV heads, the chunk's two q-blocks three (the first ends at
    # key 39) and four; the windowed rows keys 32-55: two blocks
    assert c["t.step@1.0"]["kv_blocks"] == 2 * (2 * 4 + 3 + 4)
    assert c["t.decode@1.0"]["kv_blocks"] == 3 * 2 * 2
    assert c["t.chunk@1.0"]["kv_blocks"] == 2 * (3 + 4)


def test_serve_tiny():
    out = chip_smoke.serve(GPT, [5, 40, 70, 90], new_tokens=6)
    c = out["checked"]
    assert c["requests"] == 4 and c["mixed_steps"] > 0
    assert c["decode_logits_rel_l2_vs_composites"] == 0.0
    assert c["first_diverging_greedy_position"] == [None] * 4
    assert out["kernels"] == {}


def test_train_static_mesh_tiny():
    out = chip_smoke.train_static_mesh(BERT, 4, 32, steps=2)
    c = out["checked"]
    assert c["collectives"]["all-reduce"] > 0
    assert sorted(c["param_bytes_per_device"]) == [
        d.id for d in jax.devices()[:4]]


def test_mosaic_kernel_names_from_hlo():
    line = ('  %%matmul_epilogue_fwd%s = bf16[8,8]{1,0} custom-call(bf16[8,8] '
            '%%x), custom_call_target="tpu_custom_call", backend_config={}')
    assert chip_smoke.mosaic_kernels(
        line % ".3" + "\n" + line % "" + "\n  %add.1 = f32[] add()\n"
        '  ROOT %layer_norm_bwd.12 = f32[8] custom-call(), '
        'custom_call_target="tpu_custom_call"\n'
        '  custom-call(), custom_call_target="tpu_custom_call"') == {
        "matmul_epilogue_fwd": 2, "layer_norm_bwd": 1, "unnamed": 1}


def test_refuses_to_start_without_a_chip(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""        # no phase, no result
