"""AFMoE (Trinity) through the model, the router, the gated grouped
kernel, the ragged kernel's window and head groups, and the cache
manager's groups of paged layers; the plain reference is
``benchmarks/families/afmoe.py``.  Float32, seeded, tiny widths."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from benchmarks.families import _plain, afmoe  # noqa: E402
from paddle_tpu.distributed.auto_parallel import moe_dispatch as md  # noqa: E402
from paddle_tpu.inference.serving import GenerationEngine  # noqa: E402
from paddle_tpu.inference.serving import attention as att  # noqa: E402
from paddle_tpu.inference.serving.kv_cache import (  # noqa: E402
    PagedKVCache, WindowGroup)
from paddle_tpu.ops import pallas_grouped as pg  # noqa: E402
from paddle_tpu.ops import pallas_ragged as pr  # noqa: E402
from paddle_tpu.ops.pallas_tiles import group_segments  # noqa: E402

S, F = "sliding_attention", "full_attention"
TINY = dict(
    dtype="float32", vocab_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
    layer_types=[S, S, S, S, F], num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, sliding_window=16, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, route_norm=True,
    route_scale=2.826, score_func="sigmoid", mup_enabled=True,
    max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=10000,
    initializer_range=0.02, expert_bias_std=0.05, kv_block_size=8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    return afmoe.build(TINY)


# (a) the dense forward against the plain reference ---------------------
@pytest.mark.parametrize("length", [5, 50])
def test_model_matches_the_reference(model, length):
    ids = np.random.default_rng(length).integers(0, 96, (2, length))
    got = model(paddle.to_tensor(ids)).value()
    ref = afmoe.reference_logits(_plain.arrays(model), TINY,
                                 jnp.asarray(ids))
    assert float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)) < 2e-5


def test_expert_stacks_are_stored_as_the_kernel_reads_them(model):
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()
              if ".experts." in n and ".1." in n}
    assert shapes == {"model.layers.1.mlp.experts.gate_up": (16, 64, 64),
                      "model.layers.1.mlp.experts.down": (16, 32, 64)}


# (b) the engine: chunks, both groups, the window passed, a preemption --
def _serve(model, num_blocks, prompts, new_tokens=40, **engine):
    eng = GenerationEngine(model, max_batch=4, max_model_len=128,
                           block_size=8, prefill_chunk=8,
                           num_blocks=num_blocks, **engine)
    rids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    try:
        while eng.has_unfinished():
            eng.step()
        return ([eng.result(r) for r in rids], eng.stats(),
                sum(eng._results[r].preemptions for r in rids))
    finally:
        eng.close()


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).tolist() for n in lengths]


def _worst_margin(model, prompts, sequences):
    """How far each emitted token's reference logit lies under its
    position's maximum, in standard deviations of the position."""
    params, worst = _plain.arrays(model), 0.0
    for prompt, seq in zip(prompts, sequences):
        logits = afmoe.reference_logits(params, TINY, jnp.asarray([seq]))[0]
        rows = logits[len(prompt) - 1:len(seq) - 1]
        chosen = jnp.take_along_axis(
            rows, jnp.asarray(seq[len(prompt):])[:, None], 1)[:, 0]
        worst = max(worst, float(((rows.max(-1) - chosen)
                                  / rows.std(-1)).max()))
    return worst


@pytest.mark.parametrize("num_blocks, preempted", [(64, False), (20, True)])
def test_engine_matches_the_reference(model, num_blocks, preempted):
    prompts = _prompts((3, 70, 25, 9, 40))
    sequences, stats, preemptions = _serve(model, num_blocks, prompts)
    assert all(len(s) == len(p) + 40 for s, p in zip(sequences, prompts))
    assert (preemptions > 0) == preempted
    assert _worst_margin(model, prompts, sequences) <= 1e-4
    (group,) = stats["window_groups"]
    # contexts pass the 16-token window several times: blocks came back
    assert group["blocks_released"] > 20 and group["blocks_in_use"] == 0
    assert stats["prefix_bypassed_window"] >= 5
    assert 0 < stats["kv_blocks_read_window"] < stats["kv_blocks_context"]
    # every block read is a slot of a table the kernel was handed
    assert (stats["kv_blocks_read_window"] + stats["kv_blocks_read_full"]
            <= stats["kv_table_slots"])
    assert stats["moe_assignments"] == 4 * 4 * (
        stats["decode_rows_carried"] + stats["prompt_tokens_carried"])
    assert stats["moe_assignments"] <= stats["moe_plan_rows"]
    assert stats["pool_bytes"] == (stats["full_pool_bytes"]
                                   + stats["window_pool_bytes"])


def test_decode_only_step_dispatches_rows_times_top_k(model):
    eng = GenerationEngine(model, max_batch=4, max_model_len=128,
                           block_size=8, prefill_chunk=8, num_blocks=64)
    try:
        for p in _prompts((5, 6, 7)):
            eng.add_request(p, max_new_tokens=12)
        for _ in range(6):              # the prompts, one chunk a step
            eng.step()
        before = eng.stats()
        eng.step()
        eng.step()
        after = eng.stats()
    finally:
        eng.close()
    rows = after["decode_rows_carried"] - before["decode_rows_carried"]
    assert after["prefill_chunks"] == before["prefill_chunks"] and rows == 6
    # three rows a step, top-4, four expert layers; drained a step late
    assert after["moe_assignments"] - before["moe_assignments"] \
        == 3 * 4 * 4 * 2


# (c) the router alone --------------------------------------------------
def test_router_bias_moves_the_choice_and_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 5.0, 0.0])
    idx, w = md.sigmoid_topk_router(logits, bias, 2, route_scale=2.0)
    assert idx.tolist() == [[4, 0]]
    s = jax.nn.sigmoid(logits[0])
    want = 2.0 * jnp.stack([s[4], s[0]]) / (s[4] + s[0])
    np.testing.assert_allclose(np.asarray(w[0]), np.asarray(want),
                               rtol=1e-6)
    plain_idx, plain_w = md.sigmoid_topk_router(logits, None, 2,
                                                route_norm=False)
    assert plain_idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(np.asarray(plain_w[0]),
                               np.asarray(s[:2]), rtol=1e-6)


def test_the_layer_holds_every_expert_through_the_held_path(model):
    """``AfmoeMoE`` calls the expert layer with all 16 experts held: the
    same plan as with no share stated, and every routed assignment is
    dispatched here."""
    layer = model.model.layers[1].mlp
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)),
                    jnp.float32)
    args = (x, layer.router.weight.value(), layer.expert_bias.value(),
            layer.experts.gate_up.value(), layer.experts.down.value(),
            jnp.ones(12, bool))
    from paddle_tpu.models.afmoe import _routed_impl
    y, counted = _routed_impl(*args, top_k=4, route_scale=2.826,
                              route_norm=True, use_pallas=False)
    assert counted.tolist()[0] == counted.tolist()[4] == 12 * 4
    idx, weight = md.sigmoid_topk_router(
        jnp.dot(x, args[1], precision="highest"), args[2], 4, 2.826, True)
    plain, _ = md.gated_experts(x, idx, weight, args[3], args[4], args[5])
    assert (np.asarray(y) == np.asarray(plain)).all()
    half, c = md.gated_experts(x, idx, weight, args[3][:8], args[4][:8],
                               args[5], held=(0, 8))
    other, _ = md.gated_experts(x, idx, weight, args[3][8:], args[4][8:],
                                args[5], held=(8, 16))
    assert 0 < int(c[0]) < int(c[4]) == 48
    np.testing.assert_allclose(np.asarray(half + other), np.asarray(y),
                               rtol=1e-5, atol=1e-7)


def test_router_ties_go_to_the_lower_index():
    idx, _ = md.sigmoid_topk_router(jnp.zeros((1, 6)), None, 3)
    assert idx.tolist() == [[0, 1, 2]]
    ref = afmoe.route(jnp.zeros((1, 4)), {
        "mlp.router.weight": jnp.zeros((4, 6)),
        "mlp.expert_bias": jnp.zeros(6)},
        {**TINY, "num_experts_per_tok": 3})
    assert (np.asarray(ref[0]) > 0).tolist() == [True] * 3 + [False] * 3


def test_rows_that_carry_nothing_are_dispatched_nowhere():
    idx = jnp.asarray(np.random.default_rng(0).integers(0, 4, (10, 2)))
    carried = jnp.arange(10) % 5 == 0                # rows 0 and 5
    rows, gid, counts = md.dropless_plan(idx, 4, 8, carried=carried)
    rows = np.asarray(rows).reshape(10, 2)
    total = gid.shape[0] * 8
    assert int(counts.sum()) == 4
    assert (rows[np.asarray(carried)] < total).all()
    assert (rows[~np.asarray(carried)] == total).all()
    # the plan's live blocks cover the carried assignments alone
    assert int((np.asarray(gid) < 4).sum()) == int((counts > 0).sum())
    x = jnp.ones((10, 3))
    xd = md.dropless_dispatch(x, jnp.asarray(rows.reshape(-1)), 2, total)
    assert float(xd.sum()) == 4 * 3
    y = md.dropless_combine(xd, jnp.asarray(rows.reshape(-1)),
                            jnp.ones((10, 2)))
    assert np.asarray(y)[:, 0].tolist() == [2, 0, 0, 0, 0, 2, 0, 0, 0, 0]
    assert md.plan_counters(counts, 8, 4).tolist()[:2] == [
        4, int((counts > 0).sum())]


# (d) the gated grouped kernel, interpret mode, against the composite ---
@pytest.mark.parametrize("sizes", [(24, 0, 9, 40), (8, 8, 8, 8),
                                   (0, 0, 0, 3)])
def test_gated_grouped_kernel_matches_its_composite(sizes):
    rng = np.random.default_rng(7)
    E, K, N, bm = len(sizes), 128, 256, 8
    nb = sum(-(-s // bm) for s in sizes) + 2         # two null blocks
    gid, _ = group_segments(jnp.asarray(sizes, jnp.int32), bm, nb)
    assert int((gid == E).sum()) == 2
    x = jnp.asarray(rng.normal(size=(nb * bm, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, K, 2 * N)) * 0.1, jnp.float32)
    got = pg.grouped_gated_act(x, w, block_group=gid)
    want = pg.grouped_gated_act_ref(x, w, block_group=gid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # by hand, one live block; and the null blocks are zero
    e = int(gid[0])
    z = x[:bm] @ w[e]
    np.testing.assert_allclose(
        np.asarray(got[:bm]),
        np.asarray(jax.nn.silu(z[:, :N]) * z[:, N:]), rtol=2e-4, atol=2e-4)
    assert not np.asarray(got[-2 * bm:]).any()


def test_grouped_kernel_reads_the_stack_where_it_lies():
    """No zero expert is appended and nothing is padded: the jaxpr of a
    call holds no concatenate and no pad of the stack."""
    gid = jnp.asarray([0, 1, 2, 2], jnp.int32)
    x = jnp.zeros((32, 128), jnp.float32)
    for fn, w in ((pg.grouped_gated_act, jnp.zeros((2, 128, 256))),
                  (pg.grouped_linear_act, jnp.zeros((2, 128, 128)))):
        text = str(jax.make_jaxpr(
            lambda x, w: fn(x, w, block_group=gid))(x, w))
        assert "concatenate" not in text and " pad" not in text, text


# (e) the ragged kernel's window and head groups -------------------------
def _pool(rng, blocks, heads, bs, d):
    """K and V pools as the engine keeps them: a token's heads side by
    side in a row, whole 128-lane windows a row."""
    return (jnp.asarray(rng.normal(size=(blocks, bs, heads * d)),
                        jnp.float32) for _ in range(2))


def _dense(q, k, v, window):
    """q [t, H, d] at the last t positions of k/v [s, Hkv, d]."""
    t, H, d = q.shape
    s, hkv, _ = k.shape
    qg = q.reshape(t, hkv, H // hkv, d)
    a = jnp.einsum("qngd,snd->nqgs", qg, k) / np.sqrt(d)
    pos = jnp.arange(s - t, s)[:, None]
    seen = jnp.arange(s)[None, :] <= pos
    if window is not None:
        seen &= jnp.arange(s)[None, :] > pos - window
    a = jax.nn.softmax(jnp.where(seen[None, :, None, :], a, -jnp.inf), -1)
    return jnp.einsum("nqgs,snd->qngd", a, v).reshape(t, H, d)


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_chunk_and_decode_rows_with_head_groups(window, use_pallas):
    rng = np.random.default_rng(11)
    bs, d, hkv, H = 8, 64, 2, 4
    k_pool, v_pool = _pool(rng, 12, hkv, bs, d)
    table = jnp.asarray([3, 7, 1, 9, 5], jnp.int32)      # 40 positions
    flat = lambda p: p[table].reshape(-1, hkv, d)  # noqa: E731
    k, v = flat(k_pool), flat(v_pool)
    # a chunk of 16 rows, 13 of them real, ending at position 36
    q = jnp.asarray(rng.normal(size=(16, H, d)), jnp.float32)
    got = att.grouped_chunk_attention(
        q, k_pool, v_pool, table, jnp.int32(37), jnp.int32(24),
        jnp.int32(13), window=window, chunk_bq=8, use_pallas=use_pallas)
    want = _dense(q[:13], k[:37], v[:37], window)
    np.testing.assert_allclose(np.asarray(got[:13]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[13:]).any()
    # decode rows: contexts 37 and 9, and an idle row
    qd = jnp.asarray(rng.normal(size=(3, H, d)), jnp.float32)
    tables = jnp.broadcast_to(table[None, None, :], (3, hkv, 5))
    ctx = jnp.broadcast_to(jnp.asarray([37, 9, 0])[:, None], (3, hkv))
    got = att.grouped_decode_attention(
        qd, k_pool, v_pool, tables, ctx, use_pallas, window=window,
        block_q=att.decode_block_q(H // hkv, jnp.float32))
    for row, n in ((0, 37), (1, 9)):
        want = _dense(qd[row:row + 1], k[:n], v[:n], window)
        np.testing.assert_allclose(np.asarray(got[row]),
                                   np.asarray(want[0]), rtol=2e-5,
                                   atol=2e-5)
    assert not np.asarray(got[2]).any()


def test_window_none_is_todays_kernel_bit_for_bit():
    rng = np.random.default_rng(5)
    bs, d, H, bq = 8, 64, 2, 8
    k_pool, v_pool = _pool(rng, 9, H, bs, d)
    q = jnp.asarray(rng.normal(size=(3 * bq, H, d)), jnp.float32)
    args = (q, k_pool, v_pool,
            jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32),
            jnp.asarray([20, 11], jnp.int32),
            jnp.asarray([0, 0, 1], jnp.int32),
            jnp.asarray([4, 12, 10], jnp.int32),
            jnp.asarray([8, 8, 1], jnp.int32))
    plain = lambda *a: pr.ragged_paged_attention(*a, block_q=bq)  # noqa: E731
    named = lambda *a: pr.ragged_paged_attention(  # noqa: E731
        *a, block_q=bq, window=None, block_tokens=None)
    with jax.enable_x64(False):
        assert str(jax.make_jaxpr(plain)(*args)) \
            == str(jax.make_jaxpr(named)(*args))
        out = plain(*args)
        ref = att._ragged_ref(*args, bq, 1.0 / np.sqrt(d))
        wide = pr.ragged_paged_attention(*args, block_q=bq, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # a window wider than every context masks nothing
    np.testing.assert_array_equal(np.asarray(out), np.asarray(wide))


# (f) the windowed group --------------------------------------------------
def test_window_group_gives_blocks_back_as_the_position_passes():
    group = WindowGroup(window=16, layers=(0, 1), block_size=8, rows=2,
                        span=8)
    assert group.table_width == 5 and group.num_blocks == 11
    group.open("a")
    high = 0
    for start in range(0, 200, 8):                   # 25 chunks of 8
        group.release_passed("a", start)
        group.extend("a", start + 8)
        high = max(high, group.blocks_in_use)
        base = group.context_base("a")
        # everything the chunk's first token can still see is held
        assert base <= max(0, start - 16 + 1)
        assert group.slot_mapping("a", start, 8).min() >= 8
    for pos in range(200, 260):                      # then decode
        group.extend("a", pos + 1)
        group.release_passed("a", pos)
        high = max(high, group.blocks_in_use)
    # bounded by window + chunk whatever the context
    assert high <= 4 and group.high_water <= 5
    assert group.released == -(-260 // 8) - len(group._tables["a"])
    group.truncate("a", 257)
    group.free("a")
    assert group.blocks_in_use == 0 and group.admits()


def test_a_released_block_is_never_read(model):
    """The engine's tokens equal those of a run that never releases:
    the model's own dense forward, which holds every K/V and knows the
    window as a mask alone."""
    prompts = _prompts((60, 33), seed=4)
    sequences, stats, _ = _serve(model, 64, prompts, new_tokens=30)
    assert stats["window_blocks_released"] > 0
    # 2 rows x (16 + 8 tokens and the partial blocks at the ends)
    assert stats["window_high_water"] <= 2 * 5
    for prompt, seq in zip(prompts, sequences):
        logits = model(paddle.to_tensor([seq])).value()[0]
        greedy = np.asarray(logits.argmax(-1))[len(prompt) - 1:-1]
        assert greedy.tolist() == seq[len(prompt):]


# (g) a model without a window: one group, today's tables ----------------
def test_no_window_builds_one_group():
    cache = PagedKVCache(num_layers=2, num_heads=2, head_dim=16,
                         num_blocks=16, block_size=8, register=False)
    assert cache.window_groups == [] and cache.num_layers == 2
    assert cache.pool_bytes == cache.full_pool_bytes
    assert cache.allocate("a", 20) and cache.append("a", 1)
    assert cache.write_window("a", 20, 1) == 0
    assert len(cache.block_table("a")) == cache.table_width
    stats = cache.stats()
    assert stats["window_groups"] == [] and stats["window_pool_bytes"] == 0
    view = att.RaggedCacheView(cache, 8, chunk_rows=8)
    assert not view.grouped


def test_geometry_error_names_the_groups():
    specs = [{"kind": "paged_kv", "num_kv_heads": 2, "head_dim": 16},
             {"kind": "paged_kv", "num_kv_heads": 4, "head_dim": 16,
              "window": 32}]
    with pytest.raises(ValueError, match=r"\(32, 4, 16\)"):
        PagedKVCache(layer_specs=specs, num_blocks=16, block_size=8,
                     register=False, state_slots=2)
    with pytest.raises(ValueError, match="without a window"):
        PagedKVCache(layer_specs=specs[1:], num_blocks=16, block_size=8,
                     register=False, state_slots=2)
