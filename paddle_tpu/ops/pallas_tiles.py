"""Shared tile primitives for the Pallas kernel suite.

ThunderKittens (arxiv 2410.20399) argues a small set of reusable
tile/layout primitives covers the fast-kernel design space; this module
is that layer for the kernel suite (flash, ragged, fused
LN/RMS/xent, matmul-epilogue, grouped-expert).  Everything here is
shape/layout/tracing policy — no kernel bodies:

  * tracing + dispatch policy: `_x32` (trace pallas_call builders under
    x32 because the framework globally enables x64), `_interpret`
    (interpret mode off-TPU), `_kernel_span` (the kernel's name in the
    device trace);
  * dtype-aware block picking: `_min_rows` (Mosaic sublane minima),
    `_sane_block` (clamp requested blocks to legality),
    `_ln_block_rows` / `_xent_blocks` (VMEM-budgeted row/vocab blocks),
    `matmul_accum_blocks` (full-K resident rows, N split under a VMEM
    weight-block budget — the k-blocked f32 accumulator plan shared by
    matmul-epilogue, its int8 variant, and the grouped-expert matmul);
  * running-softmax scratch: `softmax_scratch` / `stat_scratch` (the
    acc/m/l VMEM triplet every online-softmax kernel carries across a
    sequential grid dim);
  * segment descriptors: `group_segments` (block-aligned per-group
    descriptors driving scalar-prefetched BlockSpec index maps) and
    `num_group_blocks` (their static grid bound);
  * layout utilities: `_round_up`, `_pad_dim`, `_lanes` (stat-lane
    broadcast), `_demote_f64`, `_NEG_INF`, `_STAT_LANES`.

Every kernel module binds these by `from .pallas_tiles import ...`, so
a helper is ONE object process-wide — the bit-identity guarantee of the
refactor is that the kernels call the same code they inlined before.
Tooling that monkeypatches `_interpret` (tests/test_tpu_compile.py,
scripts/aot_check_smoke.py) must patch each kernel module's own global.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

# pltpu is importable on CPU builds of jax as well; the VMEM scratch
# helpers below require it even in interpret mode
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "group_segments",
    "matmul_accum_blocks",
    "num_group_blocks",
    "softmax_scratch",
    "stat_scratch",
]

_NEG_INF = -1e30
_STAT_LANES = 8  # trailing lane dim for per-row stat arrays


def _x32(fn):
    """Trace the wrapped pallas_call builder under x32 semantics.

    The framework enables jax_enable_x64 globally (paddle_tpu/__init__.py)
    for Paddle's int64/float64 tensor semantics.  Under x64, Pallas
    index-map literals and in-kernel weak ints trace as i64, which Mosaic
    cannot legalize ("failed to legalize func.return (i32, i64)") and
    whose int64 converts send Mosaic's _convert_helper into infinite
    recursion — this was the root cause of ALL four round-2 kernel
    failures on hardware.  Every dtype inside the kernels is explicit
    (f32/bf16/i32), so tracing them x32 changes nothing numerically.
    """
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)
    return wrapper


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def _kernel_span(name: str, direction: str):
    """Names one pallas_call for the device trace.

    Every pallas_call is built inside ``with _kernel_span(name,
    direction) as kernel_name`` and passes ``name=kernel_name``:
    ``<name>_<direction>`` names the Mosaic kernel itself, which is the
    name of its HLO instruction and so of its event in a profiler trace
    (``benchmarks/span_reduce.py`` holds the rule that reads it), and
    the ``jax.named_scope("<name>.<direction>")`` puts the kernel into
    the op's ``op_name`` path, which no trace carries: it is in the
    compiled step's text (``compiled.as_text()``), inside the model's
    block the call sits in (``observability/blocks.py``, whose map
    keeps the block and not yet this scope: nothing reads it).
    Nothing is timed here: in a compiled step this runs at trace time.
    """
    with jax.named_scope(f"{name}.{direction}"):
        yield f"{name}_{direction}"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_dim(x, dim, target, value=0.0):
    pad = target - x.shape[dim]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[dim] = (0, pad)
    # dtype-matched fill: a python float is a strong f64 under the
    # framework's global x64 mode and would promote the padded array
    return jnp.pad(x, widths, constant_values=jnp.asarray(value, x.dtype))


def _lanes(x2d):
    """Broadcast a (rows,) or (rows, 1) stat to the stat-lane layout."""
    if x2d.ndim == 1:
        x2d = x2d[:, None]
    return jnp.broadcast_to(x2d, x2d.shape[:-1] + (_STAT_LANES,))


def _demote_f64(*xs):
    """TPU has no float64: demote f64 inputs to f32 (grad flows back
    through the cast).  The global x64 mode (paddle_tpu/__init__.py)
    makes f64 a reachable input dtype on the CPU test path."""
    return tuple(
        x.astype(jnp.float32) if x is not None
        and jnp.issubdtype(x.dtype, jnp.floating)
        and jnp.dtype(x.dtype).itemsize == 8 else x
        for x in xs)


# =====================================================================
# Dtype-aware block picking
# =====================================================================

def _min_rows(dtype) -> int:
    """Mosaic minimum sublane rows for `dtype`: 8 for 4-byte, 16 for
    2-byte (bf16/f16), 32 for 1-byte tiles."""
    return {1: 32, 2: 16}.get(jnp.dtype(dtype).itemsize, 8)


def _sane_block(b, seq, min_rows=16):
    """Clamp any requested block to a legal tiling for `seq`/`dtype`."""
    try:
        b = int(b)
    except (TypeError, ValueError):
        return None
    if b < min_rows or b % min_rows:
        return None
    return min(b, _round_up(max(seq, min_rows), min_rows))


def _ln_block_rows(rows, n, itemsize=4):
    # keep a block under ~2MB of f32 VMEM working set; 16-row multiples
    # keep bf16 blocks on whole (16, 128) tiles
    budget = max(1, (2 << 20) // max(n * itemsize, 1))
    return min(_round_up(rows, 16), max(16, min(512, _round_up(budget, 16))))


def _xent_blocks(rows, v):
    """(block_rows, block_v) with bounded VMEM; the grid is the ceiling
    of each quotient over the logits at their own shape."""
    bv = min(_round_up(v, 128), 2048)
    br = min(_round_up(rows, 16), 256)
    return br, bv


def matmul_accum_blocks(m, k, n, dtype, weight_itemsize=None):
    """(bm, bn, m_pad, n_pad) for a full-K f32-accumulator matmul:
    resident (bm, K) rows, N split so the double-buffered (K, bn)
    weight block stays under ~6MB of VMEM.

    ``weight_itemsize`` sizes the weight-block budget independently of
    the activation dtype (int8 weights travel at 1 byte/element so bn
    can run wider); default is the activation dtype's own itemsize.
    This is the shared accumulator plan of `matmul_epilogue`, its int8
    variant, and the grouped-expert matmul.
    """
    itemsize = weight_itemsize or jnp.dtype(dtype).itemsize
    bm = min(_round_up(max(m, 1), _min_rows(dtype)), 128)
    bn = 512
    while bn > 128 and 2 * k * bn * itemsize > (6 << 20):
        bn //= 2
    bn = min(bn, _round_up(max(n, 1), 128))
    return bm, bn, _round_up(m, bm), _round_up(n, bn)


# =====================================================================
# Running-softmax / accumulator scratch
# =====================================================================

def softmax_scratch(rows, width):
    """The acc/m/l VMEM triplet of an online-softmax accumulation:
    (rows, width) f32 weighted-value accumulator plus (rows,
    _STAT_LANES) running max and running sum-exp, persisting across a
    sequential innermost grid dim (the ragged attention pattern)."""
    return [
        pltpu.VMEM((rows, width), jnp.float32),
        pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
        pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
    ]


def stat_scratch(rows, count):
    """``count`` per-row f32 stat accumulators in the stat-lane layout
    (the xent kernel's running max / sum-exp / picked-logit pattern)."""
    return [pltpu.VMEM((rows, _STAT_LANES), jnp.float32)
            for _ in range(count)]


# =====================================================================
# Segment descriptors (block-aligned grouping)
# =====================================================================

def num_group_blocks(total_rows, num_groups, block_rows):
    """Static upper bound on the number of `block_rows`-row blocks
    needed to cover `total_rows` rows split into `num_groups`
    block-aligned groups: each group wastes less than one block of
    padding, so cdiv(total) + num_groups always suffices."""
    return -(-total_rows // block_rows) + num_groups


def group_segments(group_sizes, block_rows, num_blocks):
    """Block-aligned segment descriptors for grouped (per-expert) rows.

    ``group_sizes``: [G] int32 row counts (traced is fine).  Each
    group's rows are padded up to a `block_rows` multiple so every
    block is wholly owned by one group — the grouped-matmul analogue of
    `pallas_ragged.ragged_segments`'s per-q-block descriptors.

    Returns ``(block_group, group_row_offsets)``:
      * ``block_group``: [num_blocks] int32, the group owning each
        block; blocks past the padded total get the null id ``G``
        (callers append a zero row to the indexed operand, exactly like
        the ragged kernels' null segment);
      * ``group_row_offsets``: [G] int32, the first padded row of each
        group — dispatch scatters token ``j`` of group ``g`` to row
        ``group_row_offsets[g] + j``.
    """
    gs = jnp.asarray(group_sizes, jnp.int32)
    nblk = (gs + block_rows - 1) // block_rows            # [G]
    ends = jnp.cumsum(nblk)                               # [G]
    starts = ends - nblk
    i = jnp.arange(num_blocks, dtype=jnp.int32)
    # block i belongs to the group whose [starts, ends) contains it ==
    # the count of ends <= i; empty groups collapse to zero-width
    # intervals that can never claim a block, and blocks past ends[-1]
    # land on the null id G
    gid = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
    return gid, (starts * block_rows).astype(jnp.int32)
