"""Pallas/Mosaic tiling legality: the TPU1xx analyzer.

Mosaic lays the last two dims of every array crossing a ``pallas_call``
boundary onto (sublane, lane) vector registers.  The minimum legal tile
depends on itemsize — (8,128) for 4-byte dtypes, (16,128) for 2-byte,
(32,128) for 1-byte — and a block dim must either equal the array dim
or be a multiple of the minimum tile, with the grid covering the array
exactly.  Violating either is a Mosaic *compile* error on hardware
(the (1,128) flash-attention block of round 2's chip run), which the
interpret-mode CPU path never sees; this module checks the same rules
statically so the CLI and the gate catch them before dispatch.

Checks are pure shape arithmetic — no jax import, no tracing — so the
gate can diagnose a failed probe without paying a second compile.
"""
from __future__ import annotations

import math

import numpy as np

from .diagnostics import Diagnostic, DiagnosticReport

__all__ = ["LANE", "VMEM_BYTES", "min_tile", "check_block_spec",
           "check_pallas_call", "estimate_vmem_bytes",
           "audit_flash_attention", "audit_ragged_attention",
           "audit_layer_norm_residual",
           "audit_matmul_epilogue", "audit_grouped_matmul",
           "audit_lora_sgmv"]

LANE = 128
# per-core VMEM; Mosaic needs headroom for double buffering, so the
# estimate errors at the full budget and stays silent below it.
VMEM_BYTES = 16 * 1024 * 1024

# itemsize (bytes) -> minimum sublane rows. 8-byte dtypes only appear
# when x64 leaks into a kernel; treat them like 4-byte for the sublane
# rule (the dtype itself is flagged by the TPU4xx audit).
_MIN_SUBLANE = {8: 8, 4: 8, 2: 16, 1: 32}


def min_tile(dtype):
    """Minimum legal (sublane, lane) tile for ``dtype``."""
    itemsize = np.dtype(dtype).itemsize
    return _MIN_SUBLANE.get(itemsize, 8), LANE


def _fmt(shape):
    return "(" + ",".join(str(s) for s in shape) + ")"


def check_block_spec(block_shape, array_shape, dtype, *, site="",
                     operand=""):
    """Diagnostics for one operand's BlockSpec against Mosaic rules.

    ``block_shape`` of None means "whole array" (always legal).  Rules
    checked on the last two dims: minimum sublane/lane tile (TPU101),
    grid coverage / divisibility (TPU102), rank (TPU104).
    """
    where = f"{site}[{operand}]" if operand else site
    diags = []
    if block_shape is None:
        return diags
    block_shape = tuple(int(b) for b in block_shape)
    array_shape = tuple(int(a) for a in array_shape)
    if len(block_shape) != len(array_shape):
        diags.append(Diagnostic(
            "TPU102",
            f"block rank {len(block_shape)} != array rank "
            f"{len(array_shape)} ({_fmt(block_shape)} vs "
            f"{_fmt(array_shape)})",
            site=where))
        return diags
    if len(array_shape) < 2:
        diags.append(Diagnostic(
            "TPU104",
            f"rank-{len(array_shape)} array {_fmt(array_shape)} crosses "
            "the kernel boundary; Mosaic tiles the last two dims",
            site=where,
            hint="reshape to at least 2D (e.g. (1, n)) before the "
                 "pallas_call"))
        return diags

    sub_min, lane_min = min_tile(dtype)
    dname = np.dtype(dtype).name
    # leading (grid-mapped) dims only need to divide the array dims
    for i, (b, a) in enumerate(zip(block_shape[:-2], array_shape[:-2])):
        if b <= 0 or a % b:
            diags.append(Diagnostic(
                "TPU102",
                f"leading block dim {i} = {b} does not divide array "
                f"dim {a}",
                site=where,
                hint="pad the array or pick a divisor block"))
    for name, lim, b, a in (
            ("sublane", sub_min, block_shape[-2], array_shape[-2]),
            ("lane", lane_min, block_shape[-1], array_shape[-1])):
        if b <= 0:
            diags.append(Diagnostic(
                "TPU102", f"non-positive {name} block dim {b}",
                site=where))
            continue
        full = b == a
        if not full and b % lim:
            diags.append(Diagnostic(
                "TPU101",
                f"{name} block dim {b} of {_fmt(block_shape)} is not a "
                f"multiple of the {dname} minimum {lim} "
                f"(min tile ({sub_min},{lane_min}))",
                site=where,
                hint=f"round the {name} dim up to a multiple of {lim} "
                     "or pass the full array dim"))
        elif not full and a % b:
            diags.append(Diagnostic(
                "TPU102",
                f"{name} block dim {b} does not divide array dim {a}; "
                "the grid leaves a ragged tail",
                site=where,
                hint="pad the array to a block multiple before the "
                     "kernel (the repo's kernels pad with _round_up)"))
    return diags


def estimate_vmem_bytes(operands, scratch=()):
    """Rough per-grid-step VMEM working set: one block per operand
    (double-buffered) plus scratch buffers."""
    total = 0
    for block_shape, array_shape, dtype in operands:
        shape = array_shape if block_shape is None else block_shape
        total += 2 * int(math.prod(int(s) for s in shape)) * \
            np.dtype(dtype).itemsize
    for shape, dtype in scratch:
        total += int(math.prod(int(s) for s in shape)) * \
            np.dtype(dtype).itemsize
    return total


def check_pallas_call(operands, *, scratch=(), site="pallas_call",
                      vmem_budget=VMEM_BYTES):
    """Validate a whole kernel's block plan.

    ``operands``: iterable of (name, block_shape_or_None, array_shape,
    dtype).  ``scratch``: iterable of (shape, dtype) resident per grid
    step.  Returns a ``DiagnosticReport`` of TPU101/102/103/104.
    """
    report = DiagnosticReport(label=site)
    sized = []
    for name, block_shape, array_shape, dtype in operands:
        report.extend(check_block_spec(block_shape, array_shape, dtype,
                                       site=site, operand=name))
        sized.append((block_shape, array_shape, dtype))
    vmem = estimate_vmem_bytes(sized, scratch)
    if vmem > vmem_budget:
        report.add(Diagnostic(
            "TPU103",
            f"estimated VMEM working set {vmem / 2**20:.1f} MiB exceeds "
            f"the {vmem_budget / 2**20:.0f} MiB budget",
            site=site,
            hint="shrink block dims or stage fewer operands per grid "
                 "step",
            data={"vmem_bytes": vmem}))
    return report


def audit_flash_attention(batch, seq_q, seq_k, heads, head_dim,
                          dtype="float32", causal=False,
                          direction="fwd"):
    """Statically validate the exact block plan the flash kernels would
    use for these shapes (see ``ops.pallas_kernels.flash_block_plan``).
    ``direction``: ``"fwd"``, ``"bwd_dq"`` or ``"bwd_dkv"``."""
    from ..ops.pallas_kernels import flash_block_plan
    plan = flash_block_plan(batch, seq_q, seq_k, heads, head_dim,
                            dtype=dtype, direction=direction)
    report = check_pallas_call(
        plan["operands"], scratch=plan.get("scratch", ()),
        site=f"flash_attention.{direction}[{np.dtype(dtype).name} "
             f"q={seq_q} k={seq_k} d={head_dim}]")
    report.plan = plan
    return report


def audit_layer_norm_residual(rows, hidden, dtype="float32",
                              direction="fwd", dropout=False):
    """Statically validate the fused layernorm+residual block plan
    (see ``ops.pallas_fused.ln_residual_block_plan``); ``dropout``
    audits the form that draws its mask in the kernels.  Its seed is
    scalar-prefetched into SMEM, untiled, and so not among the checked
    operands, like the ragged kernels' block tables."""
    from ..ops.pallas_fused import ln_residual_block_plan
    plan = ln_residual_block_plan(rows, hidden, dtype=dtype,
                                  direction=direction, dropout=dropout)
    report = check_pallas_call(
        plan["operands"], scratch=plan.get("scratch", ()),
        site=f"layer_norm_residual{'_dropout' if dropout else ''}."
             f"{direction}[{np.dtype(dtype).name} rows={rows} n={hidden}]")
    report.plan = plan
    return report


def audit_matmul_epilogue(m, k, n, dtype="float32", direction="fwd",
                          weight_dtype=None):
    """Statically validate the matmul-epilogue fusion block plan
    (see ``ops.pallas_fused.matmul_epilogue_block_plan``).

    ``weight_dtype="int8"`` audits the dequant-fused int8-weight
    variant; tile violations on the int8 operand additionally raise
    TPU405 (int8 needs (32,128)-legal tiles)."""
    from ..ops.pallas_fused import matmul_epilogue_block_plan
    plan = matmul_epilogue_block_plan(m, k, n, dtype=dtype,
                                      direction=direction,
                                      weight_dtype=weight_dtype)
    wtag = ""
    if weight_dtype is not None:
        wtag = f" w={np.dtype(weight_dtype).name}"
    site = (f"matmul_epilogue.{direction}"
            f"[{np.dtype(dtype).name}{wtag} m={m} k={k} n={n}]")
    report = check_pallas_call(
        plan["operands"], scratch=plan.get("scratch", ()), site=site)
    _flag_int8_relayout(report, plan, site=site)
    report.plan = plan
    return report


def audit_grouped_matmul(tokens, k, n, num_experts, dtype="float32",
                         direction="fwd"):
    """Statically validate the grouped-expert matmul block plan
    (see ``ops.pallas_grouped.grouped_matmul_block_plan``).

    The scalar-prefetched ``block_group`` descriptor is untiled and
    omitted from the plan, like the ragged kernels' block tables."""
    from ..ops.pallas_grouped import grouped_matmul_block_plan
    plan = grouped_matmul_block_plan(tokens, k, n, num_experts,
                                     dtype=dtype, direction=direction)
    site = (f"grouped_matmul.{direction}"
            f"[{np.dtype(dtype).name} tokens={tokens} k={k} n={n} "
            f"e={num_experts}]")
    report = check_pallas_call(
        plan["operands"], scratch=plan.get("scratch", ()), site=site)
    report.plan = plan
    return report


def audit_lora_sgmv(tokens, k, n, rank, num_adapters, dtype="float32",
                    direction="fwd", block_rows=None):
    """Statically validate the segmented LoRA SGMV epilogue block plan
    (see ``ops.pallas_grouped.lora_epilogue_block_plan``).

    The scalar-prefetched ``block_adapter`` descriptor is untiled and
    omitted from the plan, like the grouped kernel's ``block_group``."""
    from ..ops.pallas_grouped import lora_epilogue_block_plan
    plan = lora_epilogue_block_plan(tokens, k, n, rank, num_adapters,
                                    dtype=dtype, direction=direction,
                                    block_rows=block_rows)
    site = (f"lora_sgmv.{direction}"
            f"[{np.dtype(dtype).name} tokens={tokens} k={k} n={n} "
            f"r={rank} adapters={num_adapters}]")
    report = check_pallas_call(
        plan["operands"], scratch=plan.get("scratch", ()), site=site)
    report.plan = plan
    return report


def _flag_int8_relayout(report, plan, *, site):
    """Append TPU405 when an int8 operand in ``plan`` has a tile
    violation (TPU101/TPU102): int8 demands (32,128)-legal tiles, and
    an illegal block forces Mosaic to relayout the narrow operand."""
    int8_ops = {name for name, block, shape, dtype in plan["operands"]
                if np.dtype(dtype).itemsize == 1}
    if not int8_ops:
        return
    hit = any(d.code in ("TPU101", "TPU102") and
              any(f"[{op}]" in (d.site or "") for op in int8_ops)
              for d in report)
    if hit:
        report.add(Diagnostic(
            "TPU405",
            "int8 operand tiled below the (32,128) minimum: Mosaic "
            "relayouts the quantized tensor before the MXU",
            site=site,
            hint="round the sublane block dim up to 32 (int8 itemsize "
                 "1 => 32-row minimum tile)"))


def audit_ragged_attention(num_heads, head_dim, block_size,
                           num_q_blocks=4, block_q=None, num_blocks=64,
                           table_width=8, dtype="float32",
                           kv_dtype=None):
    """Statically validate the ragged mixed prefill+decode attention
    block plan (see ``ops.pallas_ragged.ragged_block_plan``).

    ``kv_dtype="int8"`` audits the quantized-KV variant, whose plan
    carries int8 k/v pools plus f32 per-slot scale tables; int8 tile
    violations additionally raise TPU405."""
    from ..ops.pallas_ragged import ragged_block_plan
    plan = ragged_block_plan(num_heads, head_dim, block_size,
                             num_q_blocks=num_q_blocks, block_q=block_q,
                             num_blocks=num_blocks,
                             table_width=table_width, dtype=dtype,
                             kv_dtype=kv_dtype)
    kvtag = ""
    if kv_dtype is not None:
        kvtag = f" kv={np.dtype(kv_dtype).name}"
    site = (f"ragged_attention[{np.dtype(dtype).name}{kvtag} "
            f"H={num_heads} D={head_dim} bs={block_size} "
            f"bq={plan['block_q']}]")
    report = check_pallas_call(
        plan["operands"], scratch=plan.get("scratch", ()), site=site)
    _flag_int8_relayout(report, plan, site=site)
    report.plan = plan
    return report
