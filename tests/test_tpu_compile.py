"""Compile the Pallas kernels for a described (not attached) TPU v5e.

Interpret mode skips Mosaic's block-mapping, tiling and VMEM checks, so
a kernel can pass every CPU test and still be refused by the chip's
compiler.  Each case below runs the full XLA + Mosaic pipeline through
a compile-only TPU client (`jax.experimental.topologies`) — the third
rehearsal of the `on-chip-measurement` guide, kept as tests so it
guards every later change at no chip time.

`SMOKE_CASES` are the kernels `chip_smoke.py` drives, at its real
widths: BERT-base at batch 16 x sequence 512 (8192 rows, hidden 768,
FFN 3072, vocab 30522, 12 heads of 64) and the GPT engine's unified
step (bf16: 8 rows, 256-token prefill chunk, 1,024-token contexts).
`OTHER_CASES` keep the remaining gated kernels compiling at
transformer widths.  Nothing runs and nothing is timed here.

The topology, the shardings and every shape are built inside
module-scoped fixtures: only the pytest worker that is handed this file
loads libtpu (see the guide on why nothing here may run at import).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from benchmarks import span_reduce
import paddle_tpu.ops.pallas_fused as pf
import paddle_tpu.ops.pallas_gated_delta as pgd
import paddle_tpu.ops.pallas_grouped as pgm
import paddle_tpu.ops.pallas_kernels as pk
import paddle_tpu.ops.pallas_latent as plat
import paddle_tpu.ops.pallas_lightning as pll
import paddle_tpu.ops.pallas_ragged as pr
import paddle_tpu.ops.pallas_sparse as pls
import paddle_tpu.ops.pallas_tiles as pt

bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip, with the kernels lowering
    through Mosaic (the CPU backend would pick interpret mode) and the
    persistent cache off (a compile-only entry cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    # each kernel module binds _interpret by name at import
    for mod in (pk, pf, pr, pgm, pt, pll, pls, pgd, plat):
        mp.setattr(mod, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    mp.undo()


def _sum_grad(fn, argnums):
    return jax.grad(lambda *a: fn(*a).astype(f32).sum(), argnums=argnums)


# -- chip_smoke.py widths ------------------------------------------------
B, S, H, D, HID, FFN, VOCAB = 16, 512, 12, 64, 768, 3072, 30522
ROWS = B * S


def _flash(direction):
    qkv = [((B, S, H, D), bf16)] * 3
    fwd = functools.partial(pk.flash_attention, causal=False)
    return (fwd if direction == "fwd" else _sum_grad(fwd, (0, 1, 2))), qkv


def _flash_dropout(direction, batch=B, seq=S):
    """BERT's attention as the cells run it: dropout 0.1 drawn inside
    the kernels by the core's generator.  384 and 200 are lengths the
    block table serves with one 384-row and one 256-row block."""
    def fwd(q, k, v, seed):
        return pk.flash_attention(q, k, v, dropout_p=0.1, seed=seed)
    return ((fwd if direction == "fwd" else _sum_grad(fwd, (0, 1, 2))),
            [((batch, seq, H, D), bf16)] * 3 + [((1,), i32)])


def _layer_norm():
    return (_sum_grad(pk.fused_layer_norm, (0, 1, 2)),
            [((ROWS, HID), bf16), ((HID,), bf16), ((HID,), bf16)])


def _ln_residual():
    return (_sum_grad(pf.fused_layer_norm_residual, (0, 1, 2, 3)),
            [((ROWS, HID), bf16)] * 2 + [((HID,), bf16)] * 2)


def _ln_residual_dropout(dtype):
    """BERT's hidden dropout as the cells run it: rate 0.1 drawn inside
    both kernels by the core's generator (float32 under amp O1, which
    black-lists the op; bfloat16 for a model held in it)."""
    def fwd(x, r, g, b, seed):
        return pf.fused_layer_norm_residual(x, r, g, b, dropout_p=0.1,
                                            seed=seed)
    return (_sum_grad(fwd, (0, 1, 2, 3)),
            [((ROWS, HID), dtype)] * 2 + [((HID,), dtype)] * 2
            + [((1,), i32)])


def _matmul_epilogue(k, n, act="gelu_tanh", rows=ROWS):
    return (_sum_grad(lambda x, w, b: pf.fused_linear_act(
                x, w, b, act), (0, 1, 2)),
            [((rows, k), bf16), ((k, n), bf16), ((n,), bf16)])


def _xent():
    return (jax.grad(lambda x, lbl: pk.fused_softmax_cross_entropy(
                x, lbl).sum()),
            [((ROWS, VOCAB), f32), ((ROWS,), i32)])


def _mlm_head():
    """BERT's MLM head as amp O1 runs it: the logits' matmul in bf16,
    the black-listed loss on their float32 cast, gradients to the hidden
    states and to the tied embedding."""
    def loss(h, w, lbl):
        logits = jnp.dot(h, w.T).astype(f32)
        return pk.fused_softmax_cross_entropy(logits, lbl).mean()
    return (jax.grad(loss, argnums=(0, 1)),
            [((ROWS, HID), bf16), ((VOCAB, HID), bf16), ((ROWS,), i32)])


def _ragged(kv_dtype):
    """The GPT engine's unified step in bf16 (engine.py: token_budget =
    chunk_pad + (max_batch-1)*block_q, table_width = context/block)."""
    from paddle_tpu.inference.serving.kv_cache import kv_block_size
    from paddle_tpu.inference.serving.scheduler import (
        max_batch_size, prefill_chunk_size)
    bq = pr.ragged_q_block(bf16)
    seqs, bs = max_batch_size(), kv_block_size()
    chunk_pad = -(-prefill_chunk_size() // bq) * bq
    tokens = chunk_pad + (seqs - 1) * bq
    nqb, width = tokens // bq, 1024 // bs
    # 0.3 of a 16 GB chip over 12 layers of 12x64 bf16 K+V blocks
    nb = int(0.3 * 16e9) // (2 * 12 * H * bs * D * 2) + 1
    pool = ((nb, bs, H * D), kv_dtype)
    args = [((tokens, H, D), bf16), pool, pool, ((seqs, width), i32),
            ((seqs,), i32), ((nqb,), i32), ((nqb,), i32), ((nqb,), i32)]
    if kv_dtype == i8:
        sc = ((nb, bs, pr.KV_SCALE_LANES), f32)
        return (lambda q, kp, vp, bt, cl, sid, qs, qv, ks, vs:
                pr.ragged_paged_attention(q, kp, vp, bt, cl, sid, qs, qv,
                                          k_scales=ks, v_scales=vs),
                args + [sc, sc])
    return pr.ragged_paged_attention, args


def _ragged_gpt2_large():
    """`gpt2-large.decode-closed32`: 20 heads of 64, blocks of 16, a
    table of 64 slots, 47 q-blocks of 16 rows a step."""
    heads, d, bs, width, seqs, nqb = 20, 64, 16, 64, 32, 47
    pool = ((2049, bs, heads * d), bf16)
    return (pr.ragged_paged_attention,
            [((nqb * 16, heads, d), bf16), pool, pool,
             ((seqs, width), i32), ((seqs,), i32), ((nqb,), i32),
             ((nqb,), i32), ((nqb,), i32)])


def _ragged_grouped(form, d, kv_heads, width, num_blocks, window=None):
    """The grouped cells' two calls a layer as the engine makes them
    (`attention._grouped_attend_impl`): 32 decode rows whose 8 query
    heads a KV head go as the rows of a 16-row q-block, or a 1,024-token
    chunk in q-blocks of 128 tokens x 8 heads, over blocks of 64."""
    from paddle_tpu.inference.serving import attention as att
    seqs, group, bs, chunk = 32, 8, 64, 1024
    pool = ((num_blocks, bs, kv_heads * d), bf16)
    if form == "decode":
        def fn(q, kp, vp, tables, ctx):
            return att.grouped_decode_attention(
                q, kp, vp, tables, ctx, True, window=window,
                block_q=att.decode_block_q(group, bf16))
        return fn, [((seqs, group * kv_heads, d), bf16), pool, pool,
                    ((seqs, kv_heads, width), i32), ((seqs, kv_heads), i32)]

    def fn(q, kp, vp, table, ctx, start, valid):
        return att.grouped_chunk_attention(
            q, kp, vp, table, ctx[0], start[0], valid[0], window=window,
            chunk_bq=128, use_pallas=True)
    return fn, [((chunk, group * kv_heads, d), bf16), pool, pool,
                ((width,), i32), ((1,), i32), ((1,), i32), ((1,), i32)]


SMOKE_CASES = {
    "flash_fwd_16x512x12x64": lambda: _flash("fwd"),
    "flash_bwd_16x512x12x64": lambda: _flash("bwd"),
    "flash_dropout_fwd_16x512x12x64": lambda: _flash_dropout("fwd"),
    "flash_dropout_bwd_16x512x12x64": lambda: _flash_dropout("bwd"),
    "flash_dropout_bwd_4x384x12x64": lambda: _flash_dropout("bwd", 4, 384),
    "flash_dropout_bwd_4x200x12x64": lambda: _flash_dropout("bwd", 4, 200),
    "layer_norm_8192x768": _layer_norm,
    "ln_residual_8192x768": _ln_residual,
    "ln_residual_dropout_8192x768_f32": lambda: _ln_residual_dropout(f32),
    "ln_residual_dropout_8192x768_bf16":
        lambda: _ln_residual_dropout(bf16),
    "matmul_epilogue_8192x768x3072": lambda: _matmul_epilogue(HID, FFN),
    "matmul_epilogue_8192x3072x768": lambda: _matmul_epilogue(FFN, HID),
    "softmax_xent_8192x30522": _xent,
    "ragged_attention_gpt_bf16": lambda: _ragged(bf16),
    "ragged_attention_gpt_int8kv": lambda: _ragged(i8),
    # the serving cells' forms of the in-program walk
    "ragged_attention_gpt2_large": _ragged_gpt2_large,
    **{f"ragged_attention_trinity_{form}_{name}":
       functools.partial(_ragged_grouped, form, 128, 4, width, blocks,
                         window)
       for form in ("decode", "chunk")
       for name, width, blocks, window in (
           ("window", 34 if form == "decode" else 50, 1601, 2048),
           ("full", 224, 7169, None))},
    **{f"ragged_attention_qwen3_next_{form}":
       functools.partial(_ragged_grouped, form, 256, 2, 416, 13313)
       for form in ("decode", "chunk")},
}


# -- the gated kernels chip_smoke.py does not reach ----------------------
def _rms_norm():
    return (_sum_grad(pk.fused_rms_norm, (0, 1)),
            [((4096, 4096), bf16), ((4096,), bf16)])


def _matmul_epilogue_int8():
    return (_sum_grad(lambda x, w, s, b: pf.fused_linear_act_int8(
                x, w, s, b, "gelu_tanh"), (0, 2, 3)),
            [((768, HID), bf16), ((HID, FFN), i8), ((FFN,), f32),
             ((FFN,), bf16)])


def _grouped_matmul():
    experts, tokens = 8, 1024
    _, nb, rows = pgm.grouped_layout(tokens, experts, bf16)
    gid = jnp.zeros((nb,), i32)
    return (_sum_grad(lambda x, w, b: pgm.grouped_linear_act(
                x, w, b, block_group=gid, act="gelu_tanh"), (0, 1, 2)),
            [((rows, HID), bf16), ((experts, HID, FFN), bf16),
             ((experts, FFN), bf16)])


def _lora_sgmv():
    adapters, tokens = 64, 1024
    _, nb, rows = pgm.grouped_layout(tokens, adapters, bf16)
    r = pgm.lora_rank_pad(16, bf16)
    aid = jnp.zeros((nb,), i32)
    return (_sum_grad(lambda z, x, a, b: pgm.lora_segment_epilogue(
                z, x, a, b, block_adapter=aid, act="gelu_tanh"),
                (0, 1, 2, 3)),
            [((rows, FFN), bf16), ((rows, HID), bf16),
             ((adapters, HID, r), bf16), ((adapters, r, FFN), bf16)])


OTHER_CASES = {
    # every epilogue the kernels offer ("gelu" needs an in-kernel erf:
    # Mosaic lowers no erf primitive)
    **{f"matmul_epilogue_act_{act}":
       functools.partial(_matmul_epilogue, HID, FFN, act, 256)
       for act in pf.ACTIVATIONS if act != "gelu_tanh"},
    "rms_norm_4096x4096": _rms_norm,
    "matmul_epilogue_int8_768x768x3072": _matmul_epilogue_int8,
    "grouped_matmul_8x768x3072": _grouped_matmul,
    "lora_sgmv_64x768x3072_r16": _lora_sgmv,
}

CASES = {**SMOKE_CASES, **OTHER_CASES}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case]()
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # every Mosaic call is named after its kernel in the compiled HLO,
    # which is the name a device trace shows, and the benchmark's
    # reader (span_reduce.kernel_of) finds that name by its own rule
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(span_reduce.kernel_of(c) in span_reduce.KERNELS
                         for c in calls), calls
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def test_mlm_head_moves_the_logits_only_through_compute(one_chip):
    """The loss kernels take the head's logits at their own width, so
    XLA hands the matmul's output to them as it is: no instruction of
    the compiled head re-lays (`copy`), pads or slices a matrix of the
    logits' size.  Each of those was one bandwidth-bound pass over 1 GB
    in BERT's step (8.4 of 88.9 ms: ledger, PR 30)."""
    fn, args = _mlm_head()
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    text = jax.jit(fn).lower(*avals).compile().as_text()
    moved = [m.group(0) for m in re.finditer(
        r"\[8192,(?:30522|30720)\](?:\{[^}]*\})? (?:copy|pad|slice)\(",
        text)]
    assert not moved, moved
    assert text.count('custom_call_target="tpu_custom_call"') == 2


# -- MiniCPM-SALA's engine step (benchmarks/traffic/longdoc-closed32) ----
# 32 rows, a 1,024-token chunk, 32 heads of 128, 33 state slots, 10,240
# blocks of 64 tokens with 2 KV heads, 1,280 pooled keys a slot
SALA_H, SALA_D, SALA_ROWS, SALA_SLOTS = 32, 128, 32, 33


def _lightning_chunk():
    slopes = tuple(float(x) for x in pll.decay_slopes(SALA_H))
    rows = ((1024, SALA_H, SALA_D), bf16)
    return (lambda q, k, v, pool, slot, n, first:
            pll.lightning_attention_fwd(q, k, v, pool, slot, n, first,
                                        slopes),
            [rows] * 3 + [((SALA_SLOTS, SALA_H, SALA_D, SALA_D), f32)]
            + [((), i32)] * 3)


def _lightning_step():
    slopes = tuple(float(x) for x in pll.decay_slopes(SALA_H))
    rows = ((SALA_ROWS, SALA_H, SALA_D), bf16)
    return (lambda q, k, v, pool, slots: pll.lightning_attention_step(
                q, k, v, pool, slots, slopes),
            [rows] * 3 + [((SALA_SLOTS, SALA_H, SALA_D, SALA_D), f32),
                          ((SALA_ROWS,), i32)])


def _sparse_select():
    sizes = pls.SparseSizes()
    return (lambda q, ck, slots, t: pls.sparse_select_scores(
                q, ck, slots, t, sizes, use_pallas=True),
            [((SALA_ROWS, SALA_H, SALA_D), bf16),
             ((SALA_SLOTS, 2, sizes.num_keys(20480), SALA_D), bf16),
             ((SALA_ROWS,), i32), ((SALA_ROWS,), i32)])


def _sparse_decode():
    """The sparse layers' decode rows through the ragged kernel: a
    (row, KV head) pair a sequence, its 16 query heads a q-block, a
    table of the 128 blocks a token can read."""
    from paddle_tpu.inference.serving.attention import (
        grouped_decode_attention)
    width = pls.SparseSizes().table_width(20480)
    pool = ((10241, 64, 2 * SALA_D), bf16)
    return (functools.partial(grouped_decode_attention, use_pallas=True),
            [((SALA_ROWS, SALA_H, SALA_D), bf16), pool, pool,
             ((SALA_ROWS, 2, width), i32), ((SALA_ROWS, 2), i32)])


SALA_CASES = {
    "lightning_attention_fwd": _lightning_chunk,
    "lightning_attention_step_fwd": _lightning_step,
    "sparse_select_fwd": _sparse_select,
    "ragged_attention_fwd": _sparse_decode,
}


@pytest.mark.parametrize("name", list(SALA_CASES))
def test_sala_kernel_compiles_for_v5e(one_chip, name):
    """The kernels MiniCPM-SALA's step adds, at the cell's sizes, each
    under the instruction name the benchmark's reader matches
    (``benchmarks/readers/trace_named_ms.py``: ``span_reduce.KERNELS``
    is closed and lacks the new ones)."""
    fn, args = SALA_CASES[name]()
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*avals).compile().as_text()
    calls = [span_reduce._INSTRUCTION.match(
        line.strip().removeprefix("ROOT ")).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    assert calls == [name], calls


# -- Trinity-Mini's engine step (benchmarks/traffic/mixedlen-closed32) ---
# 32 rows, a 1,024-token chunk, 32 query / 4 KV heads of 128; a full
# group of 7,168 blocks of 64 tokens (224 table slots) and a windowed
# group of 1,600 (50 slots, 34 read by a decode row); 128 experts of
# 2048 x 1024, top-8 over the 1,520-row budget
TRI_H, TRI_KV, TRI_D, TRI_ROWS, TRI_BUDGET = 32, 4, 128, 32, 1520


def _trinity_experts():
    from paddle_tpu.distributed.auto_parallel import moe_dispatch as md
    return (lambda x, idx, w, gate_up, down, carried: md.gated_experts(
                x, idx, w, gate_up, down, carried, use_pallas=True),
            [((TRI_BUDGET, 2048), bf16), ((TRI_BUDGET, 8), i32),
             ((TRI_BUDGET, 8), f32), ((128, 2048, 2048), bf16),
             ((128, 1024, 2048), bf16), ((TRI_BUDGET,), jnp.bool_)])


def _trinity_decode(window, blocks, width):
    from paddle_tpu.inference.serving.attention import (
        grouped_decode_attention)
    pool = ((blocks + 1, 64, TRI_KV * TRI_D), bf16)
    return (functools.partial(grouped_decode_attention, use_pallas=True,
                              window=window, block_q=16),
            [((TRI_ROWS, TRI_H, TRI_D), bf16), pool, pool,
             ((TRI_ROWS, TRI_KV, width), i32), ((TRI_ROWS, TRI_KV), i32)])


def _trinity_chunk(window, blocks, width):
    from paddle_tpu.inference.serving.attention import (
        grouped_chunk_attention)
    pool = ((blocks + 1, 64, TRI_KV * TRI_D), bf16)
    return (functools.partial(grouped_chunk_attention, window=window,
                              chunk_bq=128, use_pallas=True),
            [((1024, TRI_H, TRI_D), bf16), pool, pool, ((width,), i32)]
            + [((), i32)] * 3)


TRINITY_CASES = {
    "experts_128x2048x1024": (_trinity_experts, ["grouped_matmul_fwd"] * 2),
    "decode_rows_window": (lambda: _trinity_decode(2048, 1600, 34),
                           ["ragged_attention_fwd"]),
    "decode_rows_full": (lambda: _trinity_decode(None, 7168, 224),
                         ["ragged_attention_fwd"]),
    "chunk_window": (lambda: _trinity_chunk(2048, 1600, 50),
                     ["ragged_attention_fwd"]),
    "chunk_full": (lambda: _trinity_chunk(None, 7168, 224),
                   ["ragged_attention_fwd"]),
}


@pytest.mark.parametrize("name", list(TRINITY_CASES))
def test_trinity_kernel_compiles_for_v5e(one_chip, name):
    """The grouped expert kernel's gated form and the ragged kernel's
    window and head-group forms at the cell's sizes, under the names
    the benchmark reads; and the expert stacks go to the kernel as they
    lie: no copy, pad, concatenate or transpose of a stack."""
    build, want = TRINITY_CASES[name]
    fn, args = build()
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*avals).compile().as_text()
    calls = [span_reduce._INSTRUCTION.match(
        line.strip().removeprefix("ROOT ")).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    assert calls == want, calls
    assert all(span_reduce.kernel_of(line.strip().removeprefix("ROOT "))
               in span_reduce.KERNELS for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)
    moved = [m.group(0) for m in re.finditer(
        r"bf16\[12[89],(?:2048|1024),(?:2048|1024)\](?:\{[^}]*\})? "
        r"(?:copy|pad|concatenate|transpose)\(", text)]
    assert not moved, moved


# -- Qwen3-Next's engine step (benchmarks/traffic/longctx24k-closed32) ---
# 32 rows, a 1,024-token chunk; delta layers of 16 key / 32 value heads
# of 128 with 33 state slots; attention of 16 query / 2 KV heads of 256
# over 13,312 blocks of 64 tokens (416 table slots); 128 held experts of
# 2048 x 512, top-10 of 512 over the 1,520-row budget
QN_HK, QN_HV, QN_D, QN_SLOTS = 16, 32, 128, 33


def _delta_chunk():
    return (pgd.gated_delta_rule_fwd,
            [((1024, QN_HK, QN_D), bf16)] * 2
            + [((1024, QN_HV, QN_D), bf16)] + [((1024, QN_HV), f32)] * 2
            + [((QN_SLOTS, QN_HV, QN_D, QN_D), f32)] + [((), i32)] * 3)


def _delta_step():
    return (pgd.gated_delta_rule_step_fwd,
            [((TRI_ROWS, QN_HK, QN_D), bf16)] * 2
            + [((TRI_ROWS, QN_HV, QN_D), bf16)]
            + [((TRI_ROWS, QN_HV), f32)] * 2
            + [((QN_SLOTS, QN_HV, QN_D, QN_D), f32), ((TRI_ROWS,), i32)])


def _held_experts():
    from paddle_tpu.distributed.auto_parallel import moe_dispatch as md
    return (lambda x, idx, w, gate_up, down, carried: md.gated_experts(
                x, idx, w, gate_up, down, carried, use_pallas=True,
                held=(0, 128)),
            [((TRI_BUDGET, 2048), bf16), ((TRI_BUDGET, 10), i32),
             ((TRI_BUDGET, 10), f32), ((128, 2048, 1024), bf16),
             ((128, 512, 2048), bf16), ((TRI_BUDGET,), jnp.bool_)])


def _wide_decode():
    from paddle_tpu.inference.serving.attention import (
        grouped_decode_attention)
    pool = ((13313, 64, 2 * 256), bf16)
    return (functools.partial(grouped_decode_attention, use_pallas=True,
                              block_q=16),
            [((TRI_ROWS, 16, 256), bf16), pool, pool,
             ((TRI_ROWS, 2, 416), i32), ((TRI_ROWS, 2), i32)])


def _wide_chunk():
    from paddle_tpu.inference.serving.attention import (
        grouped_chunk_attention)
    pool = ((13313, 64, 2 * 256), bf16)
    return (functools.partial(grouped_chunk_attention, window=None,
                              chunk_bq=128, use_pallas=True),
            [((1024, 16, 256), bf16), pool, pool, ((416,), i32)]
            + [((), i32)] * 3)


QWEN3_NEXT_CASES = {
    "delta_chunk": (_delta_chunk, ["gated_delta_rule_fwd"]),
    "delta_step": (_delta_step, ["gated_delta_rule_step_fwd"]),
    "held_experts_128x2048x512": (_held_experts,
                                  ["grouped_matmul_fwd"] * 2),
    "decode_rows_width_256": (_wide_decode, ["ragged_attention_fwd"]),
    "chunk_width_256": (_wide_chunk, ["ragged_attention_fwd"]),
}


@pytest.mark.parametrize("name", list(QWEN3_NEXT_CASES))
def test_qwen3_next_kernel_compiles_for_v5e(one_chip, name):
    """The two gated delta rule kernels, the grouped expert kernel over
    the held experts and the ragged kernel at head width 256 with head
    groups of 8, at the cell's sizes, each under the instruction name
    the benchmark's readers match; the held stacks go to the kernel as
    they lie."""
    build, want = QWEN3_NEXT_CASES[name]
    fn, args = build()
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*avals).compile().as_text()
    calls = [span_reduce._INSTRUCTION.match(
        line.strip().removeprefix("ROOT ")).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    assert calls == want, calls
    moved = [m.group(0) for m in re.finditer(
        r"bf16\[128,(?:2048|512),(?:2048|1024)\](?:\{[^}]*\})? "
        r"(?:copy|pad|concatenate|transpose)\(", text)]
    assert not moved, moved


# -- LongCat-Flash's expert layer (benchmarks/traffic/longctx16k-closed32)
# 8 held experts of 6144 x 2048, top-12 of 768 (512 routed, 256
# zero-compute) over the 1,520-row budget: the widest rows any cell's
# dispatch moves
LC_T, LC_D, LC_K = 1520, 6144, 12


def _longcat_experts():
    from paddle_tpu.distributed.auto_parallel import moe_dispatch as md
    return (lambda x, idx, w, gate_up, down, carried: md.gated_experts(
                x, idx, w, gate_up, down, carried, use_pallas=True,
                held=(0, 8), identity=512),
            [((LC_T, LC_D), bf16), ((LC_T, LC_K), i32), ((LC_T, LC_K), f32),
             ((8, LC_D, 4096), bf16), ((8, 2048, LC_D), bf16),
             ((LC_T,), jnp.bool_)])


def _buffers(text):
    """Instruction lines of the computations that are no fusion's body:
    each writes a buffer of its own."""
    bodies = set(re.findall(r"\bcalls=(%[\w.\-]+)", text))
    lines, keep = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head:
            keep = head.group(1) not in bodies
        elif keep and line.startswith(" "):
            lines.append(line.strip())
    return lines


def _row_scatters(text, lanes):
    """Scatters, fused or not, whose rows are ``lanes`` wide."""
    return re.findall(rf"\w+\[\d+,{lanes}\]\{{[^}}]*\}} scatter\(", text)


def test_longcat_experts_move_rows_by_gathers(one_chip):
    """`gated_experts` at the LongCat-Flash cell's shapes: the grouped
    kernel pair under its name; the 6,144-lane rows enter and leave the
    grouped buffer by gathers (no scatter of them), no float32 ``[N, k,
    D]`` buffer is written, and nothing contracts over the choices (the
    lowered program has no ``dot_general``)."""
    import chip_smoke
    fn, args = _longcat_experts()
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    with jax.enable_x64(False):
        lowered = jax.jit(fn).lower(*avals)
        text = lowered.compile().as_text()
    assert "dot_general" not in lowered.as_text()
    assert chip_smoke.mosaic_kernels(text) == {"grouped_matmul_fwd": 2}
    assert not _row_scatters(text, LC_D)
    wide = [line for line in _buffers(text) if re.search(
        rf"= f32\[(?:{LC_T},{LC_K},{LC_D}|{LC_T * LC_K},{LC_D})\]", line)]
    assert not wide, wide


def test_row_scatter_guard_sees_the_scatter_form(one_chip):
    """The guard's own control: the dispatch the gathers replaced
    (`chip_smoke._scatter_dispatch`, tokens repeated and scattered into
    a zeroed buffer) compiles to a scatter of 6,144-lane rows, and the
    einsum combine lowers to a ``dot_general``."""
    import chip_smoke
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((LC_T, LC_D), bf16), ((LC_T * LC_K,), i32), ((LC_T, LC_K), f32))]
    with jax.enable_x64(False):
        text = jax.jit(lambda x, rows: chip_smoke._scatter_dispatch(
            x, rows, LC_K, 19328)).lower(*avals[:2]).compile().as_text()
        combine = jax.jit(lambda y, rows, w: chip_smoke._einsum_combine(
            y, rows, w)).lower(
            jax.ShapeDtypeStruct((19328, LC_D), bf16, sharding=one_chip),
            *avals[1:]).as_text()
    assert _row_scatters(text, LC_D)
    assert "dot_general" in combine


# -- a layer's scatter and attention on donated pools, a serving cell ---
# The pools are [num_blocks, block_size, H_kv * D]: the scatter writes
# rows of them in place and the ragged kernel copies lane windows out of
# the same arrays, so no step relays a pool (PERF.md section 6, PR 38).
def _pool_layer(form, d, kv_heads, width, blocks, window=None, group=8):
    """(function, arguments, elements of a pool) of `_kv_scatter_impl`
    then one attention call of a step, as the engine makes them:
    ``mixed`` (GPT-2's one call: 47 q-blocks of 16 rows), ``decode`` (32
    rows whose ``group`` heads a KV head are a q-block's rows, a table a
    (row, KV head) pair) or ``chunk`` (1,024 tokens in head groups)."""
    from paddle_tpu.inference.serving import attention as att
    seqs, bs = 32, 16 if form == "mixed" else 64
    tokens = 752 if form == "mixed" else 1520
    pool = ((blocks, bs, kv_heads * d), bf16)
    new = ((1, tokens, kv_heads, d), bf16)
    if form == "mixed":
        def attend(q, kp, vp, bt, cl, sid, qs, qv):
            return pr.ragged_paged_attention(q, kp, vp, bt, cl, sid, qs, qv)
        rest = [((seqs, width), i32), ((seqs,), i32)] + [((47,), i32)] * 3
        q = ((tokens, kv_heads, d), bf16)
    elif form == "decode":
        def attend(q, kp, vp, tables, ctx):
            return att.grouped_decode_attention(
                q, kp, vp, tables, ctx, True, window=window,
                block_q=att.decode_block_q(group, bf16))
        rest = [((seqs, kv_heads, width), i32), ((seqs, kv_heads), i32)]
        q = ((seqs, group * kv_heads, d), bf16)
    else:
        def attend(q, kp, vp, table, ctx, start, valid):
            return att.grouped_chunk_attention(
                q, kp, vp, table, ctx, start, valid, window=window,
                chunk_bq=128, use_pallas=True)
        rest = [((width,), i32)] + [((), i32)] * 3
        q = ((1024, group * kv_heads, d), bf16)

    def layer(q, kp, vp, kn, vn, slots, *rest):
        kp, vp = att._kv_scatter_impl(kp, vp, kn, vn, slots)
        return attend(q, kp, vp, *rest), kp, vp
    return (layer, [q, pool, pool, new, new, ((tokens,), i32)] + rest,
            blocks * bs * kv_heads * d)


POOL_LAYER_CASES = {
    "gpt2_large_mixed": ("mixed", 64, 20, 64, 2049),
    "trinity_full_decode": ("decode", 128, 4, 224, 7169),
    "trinity_full_chunk": ("chunk", 128, 4, 224, 7169),
    "trinity_window_decode": ("decode", 128, 4, 34, 1601, 2048),
    "trinity_window_chunk": ("chunk", 128, 4, 50, 1601, 2048),
    # two KV heads of 128, a selected table of 128 blocks, 16 heads a pair
    "minicpm_sala_selected_decode": ("decode", 128, 2, 128, 10241, None,
                                     16),
    "qwen3_next_decode": ("decode", 256, 2, 416, 13313),
    "qwen3_next_chunk": ("chunk", 256, 2, 416, 13313),
}


@pytest.mark.parametrize("case", list(POOL_LAYER_CASES))
def test_scatter_and_ragged_kernel_leave_the_pools_where_they_lie(
        one_chip, case):
    """`_kv_scatter_impl` then the ragged call on donated pools, at each
    serving cell's geometry: the call keeps its name, the module aliases
    both pools to its results, and the optimised text holds no copy and
    no transpose of a whole pool."""
    import chip_smoke
    fn, args, elements = _pool_layer(*POOL_LAYER_CASES[case])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    with jax.enable_x64(False):
        text = jax.jit(fn, donate_argnums=(1, 2)).lower(
            *avals).compile().as_text()
    assert chip_smoke.mosaic_kernels(text) == {"ragged_attention_fwd": 1}
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias and "{1}: (1," in alias.group(1) \
        and "{2}: (2," in alias.group(1), text[:400]
    moves = chip_smoke.pool_sized_moves(text, elements)
    assert not moves, moves


def test_latent_scatter_and_kernel_leave_the_pool_where_it_lies(one_chip):
    """One latent layer of the LongCat-Flash cell's step on a donated
    pool at its geometry (8,705 blocks of 64 rows of 512 + 64 lanes in
    five 128-lane tiles, 32
    rows, tables of 272 blocks, a 1,024-token chunk, 64 heads): the
    scatter, the decode rows' and the chunk's ``latent_attention_fwd``
    calls; the pool is aliased to its result and no instruction copies
    or transposes it whole."""
    import chip_smoke
    from paddle_tpu.inference.serving import attention as att
    T, S, H, width, blocks = 1520, 32, 64, 576, 8705

    def layer(q, rows, pool, slots, tables, dec_index, row_pos, meta):
        return att._latent_attend_impl(
            q, rows, pool, slots, tables, dec_index, row_pos, meta,
            chunk_rows=1024, chunk_tokens=att.latent_chunk_tokens(H, 1024),
            value_lanes=512, scale=192 ** -0.5, use_pallas=True)
    args = [((1, T, H, width), bf16), ((1, T, width), bf16),
            ((blocks, 64, 640), bf16), ((T,), i32), ((S, 272), i32),
            ((S,), i32), ((S,), i32), ((6,), i32)]
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in args]
    with jax.enable_x64(False):
        text = jax.jit(layer, donate_argnums=(2,)).lower(
            *avals).compile().as_text()
    assert chip_smoke.mosaic_kernels(text) == {"latent_attention_fwd": 2}
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias and "{1}: (2," in alias.group(1), text[:400]
    moves = chip_smoke.pool_sized_moves(text, blocks * 64 * 640)
    assert not moves, moves


def test_pool_sized_moves_sees_a_relaid_pool(one_chip):
    """The guard's own control: the scatter into a head-major pool
    ``[nb, H, bs, D]`` (the layout before PR 38) makes XLA copy the
    whole donated pool into the scatter's layout and back."""
    import chip_smoke

    def scatter(pool, new, slots):
        return pool.at[slots // 16, :, slots % 16, :].set(new)
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((2049, 20, 16, 64), bf16), ((752, 20, 64), bf16), ((752,), i32))]
    with jax.enable_x64(False):
        text = jax.jit(scatter, donate_argnums=(0,)).lower(
            *avals).compile().as_text()
    assert chip_smoke.pool_sized_moves(text, 2049 * 20 * 16 * 64)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ring_flash_compiles_for_v5e_2x2(topo, one_chip, direction):
    """Mosaic kernels inside shard_map over the four described chips
    (the sep-axis long-context path)."""
    from paddle_tpu.ops.ring_flash_attention import (
        ring_flash_attention_local)
    n_dev = len(topo.devices)
    mesh = Mesh(np.array(topo.devices).reshape(n_dev), ("sep",))
    spec = P(None, "sep", None, None)
    fn = jax.shard_map(
        functools.partial(ring_flash_attention_local, axis="sep",
                          axis_size=n_dev, causal=True, scale=0.125),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    if direction == "bwd":
        fn = _sum_grad(fn, (0, 1, 2))
    qa = jax.ShapeDtypeStruct((2, 512, 4, 64), bf16,
                              sharding=NamedSharding(mesh, spec))
    text = jax.jit(fn).lower(qa, qa, qa).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
