"""The shared tile-primitive layer (ops/pallas_tiles.py): the refactor's
bit-identity contract — every kernel module binds the SAME helper
objects it used to inline — plus the segment-descriptor math the
grouped-expert kernel and the dropless router must agree on."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import (pallas_fused, pallas_grouped, pallas_kernels,
                            pallas_ragged, pallas_tiles as tiles)

pytestmark = pytest.mark.analysis


# ---------------------------------------------------------------------
# bit-identity: re-exports are the same objects, not copies
# ---------------------------------------------------------------------

# (module, names it re-binds from pallas_tiles)
_REBOUND = [
    (pallas_kernels, ["_NEG_INF", "_STAT_LANES", "_demote_f64",
                      "_interpret", "_kernel_span", "_lanes",
                      "_ln_block_rows", "_min_rows", "_pad_dim",
                      "_round_up", "_sane_block", "_x32",
                      "_xent_blocks", "stat_scratch"]),
    (pallas_fused, ["_STAT_LANES", "_demote_f64", "_interpret",
                    "_kernel_span", "_ln_block_rows", "_pad_dim",
                    "_round_up", "_x32", "matmul_accum_blocks"]),
    (pallas_ragged, ["_NEG_INF", "_STAT_LANES", "_demote_f64",
                     "_interpret", "_kernel_span", "_lanes",
                     "_min_rows", "_x32", "softmax_scratch"]),
    (pallas_grouped, ["_demote_f64", "_interpret", "_kernel_span",
                      "_min_rows", "_pad_dim", "_round_up", "_x32",
                      "group_segments", "matmul_accum_blocks",
                      "num_group_blocks"]),
]


@pytest.mark.parametrize("mod,names", _REBOUND,
                         ids=[m.__name__.rsplit(".", 1)[-1]
                              for m, _ in _REBOUND])
def test_kernel_modules_bind_the_same_objects(mod, names):
    for name in names:
        assert getattr(mod, name) is getattr(tiles, name), \
            f"{mod.__name__}.{name} is a copy, not the shared object"


@pytest.mark.parametrize("shape,dtype", [
    ((8, 64, 128), jnp.float32),
    ((128, 768, 3072), jnp.float32),
    ((200, 512, 512), jnp.bfloat16),
    ((16, 4096, 1024), jnp.bfloat16),
])
def test_me_blocks_is_matmul_accum_blocks(shape, dtype):
    """matmul-epilogue's block plan IS the shared accumulator plan —
    the factored helper must pick identical tilings."""
    m, k, n = shape
    assert pallas_fused._me_blocks(m, k, n, dtype) \
        == tiles.matmul_accum_blocks(m, k, n, dtype)


def test_matmul_accum_blocks_invariants():
    for m, k, n, dt in [(8, 64, 128, jnp.float32),
                        (100, 768, 3072, jnp.bfloat16),
                        (1, 128, 50304, jnp.float32)]:
        bm, bn, m_pad, n_pad = tiles.matmul_accum_blocks(m, k, n, dt)
        assert bm % tiles._min_rows(dt) == 0 and bm <= 128
        assert bn % 128 == 0
        assert m_pad % bm == 0 and m_pad >= m
        assert n_pad % bn == 0 and n_pad >= n
        # double-buffered weight block fits the VMEM budget (or bn
        # already hit the 128-lane floor)
        itemsize = jnp.dtype(dt).itemsize
        assert 2 * k * bn * itemsize <= (6 << 20) or bn == 128


# ---------------------------------------------------------------------
# segment descriptors
# ---------------------------------------------------------------------

def test_group_segments_uneven_counts():
    counts = jnp.asarray([5, 0, 17, 8], jnp.int32)     # empty group 1
    br = 8
    nb = tiles.num_group_blocks(int(counts.sum()), 4, br)
    gid, offsets = tiles.group_segments(counts, br, nb)
    gid, offsets = np.asarray(gid), np.asarray(offsets)
    # per-group block need: ceil(5/8)=1, 0, ceil(17/8)=3, 1
    assert gid.tolist()[:5] == [0, 2, 2, 2, 3]
    # everything past the padded total is the null id G=4
    assert (gid[5:] == 4).all()
    # offsets point at the first padded row of each group; the empty
    # group collapses onto the next group's start
    assert offsets.tolist() == [0, 8, 8, 32]
    assert len(gid) == nb


def test_num_group_blocks_always_covers():
    rng = np.random.RandomState(0)
    for _ in range(50):
        G = int(rng.randint(1, 9))
        br = int(rng.choice([8, 16, 32, 128]))
        counts = rng.randint(0, 200, size=G)
        need = int(np.ceil(counts / br).sum())
        nb = tiles.num_group_blocks(int(counts.sum()), G, br)
        assert nb >= need, (counts.tolist(), br, nb, need)


def test_group_segments_matches_dropless_plan_rows():
    """The router and the kernel agree: dropless_plan scatters token j
    of expert e to offsets[e] + j, rows are unique, counts exact."""
    from paddle_tpu.distributed.auto_parallel import moe_dispatch as md
    rng = np.random.RandomState(3)
    topk = jnp.asarray(rng.randint(0, 4, size=(24, 2)), jnp.int32)
    bm, nb, R = pallas_grouped.grouped_layout(24 * 2, 4, jnp.float32)
    rows, gid, counts = md.dropless_plan(topk, 4, bm, nb)
    rows = np.asarray(rows)
    assert len(set(rows.tolist())) == rows.size          # unique
    assert rows.max() < R
    exp = np.bincount(np.asarray(topk).ravel(), minlength=4)
    assert np.asarray(counts).tolist() == exp.tolist()
    # each row lands inside its expert's block run
    _, offsets = tiles.group_segments(counts, bm, nb)
    offsets = np.asarray(offsets)
    e_flat = np.asarray(topk).ravel()
    for r, e in zip(rows, e_flat):
        assert offsets[e] <= r < offsets[e] + int(
            np.ceil(exp[e] / bm)) * bm
