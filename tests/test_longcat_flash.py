"""LongCat-Flash through the model, latent attention in its two forms,
the latent kernel, the router with its choice-only bias and scale, the
zero-compute experts, the shares of a deployment, and the engine's
latent pool; the plain reference is ``benchmarks/families/
longcat_flash.py``.  Float32, seeded, tiny widths."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from benchmarks.families import _plain  # noqa: E402
from benchmarks.families import longcat_flash as family  # noqa: E402
from paddle_tpu.core.autograd import no_grad  # noqa: E402
from paddle_tpu.distributed.auto_parallel import moe_dispatch as md  # noqa: E402
from paddle_tpu.inference.serving import GenerationEngine  # noqa: E402
from paddle_tpu.inference.serving import attention as att  # noqa: E402
from paddle_tpu.inference.serving.engine import ragged_sample_next  # noqa: E402
from paddle_tpu.inference.serving.kv_cache import PagedKVCache  # noqa: E402
from paddle_tpu.models import longcat_flash as program  # noqa: E402
from paddle_tpu.ops import pallas_latent  # noqa: E402

# two double layers at width 64: four MLA sublayers of 4 heads (nope 16,
# rope 16, values 16, latent 32 + 16 lanes), 16 routed experts of which
# all are held beside 8 zero experts, top-6
TINY = dict(
    dtype="float32", vocab_size=256, hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, n_routed_experts=16,
    published_n_routed_experts=16, zero_expert_num=8, moe_topk=6,
    routed_scaling_factor=6, expert_shard={"chips": 1, "index": 0},
    max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=10000000,
    initializer_range=0.02, norm_weight_std=0.1, expert_bias_std=0.01,
    kv_block_size=8)
ENGINE = dict(max_batch=4, max_model_len=128, block_size=8, prefill_chunk=8,
              num_blocks=64)
# Float32 everywhere, so program and reference differ by the order of
# their sums and by the form of MLA: the absorbed queries against the
# latent rows, against each head's keys and values up-projected.  That
# reads about 1e-6 of a row's norm here; the reference held in float8
# reads far more (`test_a_lower_precision_fails_the_tolerance`).
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    return family.build(TINY)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def _worst_row(got, ref):
    """The largest relative L2 distance of a row of logits."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float((np.linalg.norm(got - ref, axis=-1)
                  / np.linalg.norm(ref, axis=-1)).max())


# (a) the dense forward against the plain reference ---------------------
def _dense_distance(model, cfg=TINY, length=50, compute_dtype=None):
    ids = jnp.asarray(np.random.default_rng(length).integers(
        0, 256, (2, length)))
    with no_grad():
        got = model(paddle.to_tensor(np.asarray(ids))).value()
    params = _plain.arrays(model)
    ref = jnp.stack([family.reference_head(
        params, cfg, family.reference_hidden(params, cfg, row,
                                             compute_dtype=compute_dtype),
        compute_dtype=compute_dtype) for row in ids])
    return _worst_row(got, ref)


@pytest.mark.parametrize("length", [5, 50, 100])
def test_model_matches_the_reference(model, length):
    """The program's absorbed form against the reference's keys and
    values up-projected a head: two derivations of MLA."""
    assert _dense_distance(model, length=length) < TOL


def test_a_lower_precision_fails_the_tolerance(model):
    """The reference with its weights, inputs and sublayer outputs held
    in float8 reads far outside the tolerance."""
    assert _dense_distance(model, compute_dtype=jnp.float8_e4m3fn) \
        > 100 * TOL


def test_layers_and_spec_are_what_the_config_says(model):
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    layer = "model.layers.1."
    assert shapes[layer + "mlp.experts.gate_up"] == (16, 64, 64)
    assert shapes[layer + "mlp.experts.down"] == (16, 32, 64)
    assert shapes[layer + "mlp.router.weight"] == (64, 24)
    assert shapes[layer + "mlp.expert_bias"] == (24,)
    assert shapes[layer + "self_attn.0.q_b_proj.weight"] == (32, 4 * 32)
    assert shapes[layer + "self_attn.1.kv_a_proj_with_mqa.weight"] \
        == (64, 32 + 16)
    assert shapes[layer + "self_attn.1.kv_b_proj.weight"] == (32, 4 * 32)
    assert shapes[layer + "mlps.1.gate_proj.weight"] == (64, 96)
    spec = model.cache_spec()
    assert len(spec) == 4 and {s["kind"] for s in spec} == {"latent"}
    assert spec[0]["width"] == 48 and spec[0]["value_lanes"] == 32
    with pytest.raises(NotImplementedError, match="serving engine"):
        model(paddle.to_tensor(np.zeros((1, 4), np.int64)), use_cache=True)


def test_the_double_layer_takes_its_shortcut_from_the_first_mlp(model):
    """A double layer against its equations written with its own
    sublayers: the expert layer sees ``N_post0(h1)`` and its output is
    added after the second MLP; fed ``N_post1(h3)`` instead it reads far
    off."""
    layer = model.model.layers[0]
    x = paddle.to_tensor(np.random.default_rng(1).normal(
        size=(1, 12, 64)).astype(np.float32))
    pos = paddle.arange(0, 12, dtype="int64")
    with no_grad():
        got = np.asarray(layer(x, pos).value())
        h1 = x + layer.self_attn[0](layer.input_layernorm[0](x), pos)
        u = layer.post_attention_layernorm[0](h1)
        h3 = h1 + layer.mlps[0](u)
        h3 = h3 + layer.self_attn[1](layer.input_layernorm[1](h3), pos)
        u1 = layer.post_attention_layernorm[1](h3)
        want = np.asarray((h3 + layer.mlps[1](u1) + layer.mlp(u)).value())
        late = np.asarray((h3 + layer.mlps[1](u1) + layer.mlp(u1)).value())
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(late - want).max() > 100 * np.abs(got - want).max()


# (b) the router, the zero experts and the shares ------------------------
def test_router_chooses_by_probability_plus_bias_and_scales_by_six():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0]])
    p = np.asarray(jax.nn.softmax(logits, -1))[0]
    idx, w = md.softmax_bias_topk_router(logits, jnp.zeros(5), 2, 6.0)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(np.asarray(w)[0], 6 * p[:2], rtol=1e-6)
    # a bias moves the choice and not the weight: no renormalisation
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0, 0.0])
    idx, w = md.softmax_bias_topk_router(logits, bias, 2, 6.0)
    assert idx.tolist() == [[3, 0]]
    np.testing.assert_allclose(np.asarray(w)[0], 6 * p[[3, 0]], rtol=1e-6)


def test_zero_experts_add_their_weighted_input_and_are_counted():
    """Picks at or past ``identity`` go to no plan row, add ``w x`` to
    the carried rows and are counted in the sixth counter."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(4, 16, 16)), jnp.float32)
    dn = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    idx = jnp.asarray([[0, 5], [6, 1], [2, 3], [7, 4], [0, 1], [5, 6]],
                      jnp.int32)
    w = jnp.asarray(rng.uniform(size=(6, 2)), jnp.float32)
    carried = jnp.asarray([True] * 5 + [False])
    y, c = md.gated_experts(x, idx, w, gu, dn, carried, held=(0, 4),
                            identity=4)
    plain, c0 = md.gated_experts(x, jnp.where(idx < 4, idx, 0),
                                 jnp.where(idx < 4, w, 0.0), gu, dn, carried,
                                 held=(0, 4))
    zero = np.where((np.asarray(idx) >= 4) & np.asarray(carried)[:, None],
                    np.asarray(w), 0.0).sum(-1)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(plain) + zero[:, None] * np.asarray(x),
        atol=1e-5)
    assert c.tolist()[5] == 4 and c.tolist()[4] == 10
    assert c.tolist()[0] == 6 and len(c0) == 5


@pytest.mark.parametrize("use_pallas", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(model, use_pallas,
                                                   monkeypatch):
    """The routed halves of an expert layer held as experts 0-3, 4-7,
    8-11, 12-15 of 16, and the zero experts' term counted once, sum to
    the uncut layer's output and to the reference's."""
    from paddle_tpu.ops import pallas_grouped as pg
    if use_pallas:                      # the kernel, interpret mode
        monkeypatch.setattr(pg, "_interpret", lambda: True)
    params = _plain.arrays(model)
    head = "model.layers.1."
    w = {k[len(head):]: a for k, a in params.items() if k.startswith(head)}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    carried = jnp.arange(24) % 6 != 5           # some rows carry nothing

    def routed(held):
        lo, hi = held
        y, counters = program._routed_impl(
            x, w["mlp.router.weight"], w["mlp.expert_bias"],
            w["mlp.experts.gate_up"][lo:hi], w["mlp.experts.down"][lo:hi],
            carried, top_k=6, scale=6.0, held=held, identity=16,
            use_pallas=use_pallas)
        return np.asarray(y), np.asarray(counters)

    whole, counted = routed((0, 16))
    parts = [routed((lo, lo + 4)) for lo in range(0, 16, 4)]
    low = lambda a, scale=None: a.astype(jnp.float32)      # noqa: E731
    with jax.default_matmul_precision("highest"):
        weight = family.route(x, w, TINY)
        zero = np.asarray(weight[:, 16:].sum(-1)[:, None] * x) \
            * np.asarray(carried)[:, None]
        ref = family.expert_layer(
            x, w, tuple((w[k], None) for k in family._STACKS), TINY, low)
    # the zero experts' term is in every share: count it once
    assert np.abs(sum(p for p, _ in parts) - 3 * zero - whole).max() < 1e-5
    assert counted[4] == 6 * 20 and all(c[4] == counted[4] for _, c in parts)
    assert sum(c[0] for _, c in parts) == counted[0]
    assert counted[0] + counted[5] == counted[4]
    assert all(c[5] == counted[5] for _, c in parts)
    assert np.abs(whole - np.asarray(ref))[np.asarray(carried)].max() < 1e-5
    assert (whole[~np.asarray(carried)] == 0).all()


def test_a_model_that_holds_a_quarter_matches_the_reference_of_it():
    """The whole model as chip 2 of 4 (experts 8-11 of 16), against the
    reference given the same share."""
    cfg = {**TINY, "n_routed_experts": 4,
           "expert_shard": {"chips": 4, "index": 2}}
    paddle.seed(9)
    part = family.build(cfg)
    assert part.config.held_experts == (8, 12)
    assert _dense_distance(part, cfg) < TOL
    prompts = _prompts((21, 10), seed=8)
    served = step_logits(part, prompts, new_tokens=6)
    assert served_distance(part, prompts, served, cfg) < TOL
    assert 0 < served[3]["moe_assignments"] \
        < served[3]["moe_assignments_routed"]


# (c) the engine: chunks of 8, then decode through the latent pool ------
class LogitTap:
    """The engine's step, run eagerly, keeping the logits row of every
    token it samples: ``rows[(request id, position)]``."""

    def __init__(self, engine):
        self.engine, self.rows, self._cache = engine, {}, {}

    def __call__(self, ids, seeds, *controls):
        eng, view = self.engine, self.engine._view
        with no_grad():
            logits = eng.model(ids, cache=view, use_cache=False)
        z = np.asarray(logits._value[0])
        index = np.asarray(view.last_index._value)
        where = np.asarray(view.sample_pos._value)
        for r, req in enumerate(eng._rows):
            if req is not None and where[r] > 0:
                self.rows[(req.id, int(where[r]))] = z[index[r]]
        tok = ragged_sample_next(logits, view.last_index, seeds,
                                 view.sample_pos, *controls)
        return tok, view.take_reports()


def step_logits(model, prompts, new_tokens=12, **engine):
    eng = GenerationEngine(model, **{**ENGINE, **engine})
    eng._step_fn = LogitTap(eng)
    rids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    try:
        while eng.has_unfinished():
            eng.step()
        return rids, [eng.result(r) for r in rids], eng._step_fn.rows, \
            eng.stats()
    finally:
        eng.close()


def served_distance(model, prompts, served, cfg=TINY):
    """The largest relative L2 between a served logits row and the
    reference's full forward over the request's final sequence."""
    rids, sequences, rows, _ = served
    params, worst = _plain.arrays(model), 0.0
    for rid, seq, prompt in zip(rids, sequences, prompts):
        ref = np.asarray(family.reference_logits(
            params, cfg, jnp.asarray([seq]))[0])
        got = np.stack([rows[(rid, pos)]
                        for pos in range(len(prompt), len(seq))])
        worst = max(worst, _worst_row(got, ref[len(prompt) - 1:-1]))
    return worst


@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_matches_the_reference(model, use_pallas, monkeypatch):
    """Prefill in chunks of 8, then 12 decode steps, three requests of
    unequal length in flight, through the gather form or the latent
    kernel (interpret mode)."""
    import paddle_tpu.ops.pallas_gate as gate
    if use_pallas:
        real = gate.pallas_enabled
        monkeypatch.setattr(gate, "pallas_enabled", lambda name, **kw: (
            name == "latent_attention" or real(name, **kw)))
    prompts = _prompts((5, 29, 18))
    served = step_logits(model, prompts)
    _, sequences, _, stats = served
    assert all(len(s) == len(p) + 12 for s, p in zip(sequences, prompts))
    assert served_distance(model, prompts, served) < TOL
    assert stats["prefill_chunks"] == 1 + 4 + 3
    # four latent layers: a row of 48 lanes in a tile of 128, 65 blocks
    assert stats["pool_bytes"] == 4 * 65 * 8 * 128 * 4
    carried = stats["decode_rows_carried"] + stats["prompt_tokens_carried"]
    assert stats["moe_assignments"] + stats["moe_zero_assignments"] \
        == stats["moe_assignments_routed"] == 2 * 6 * carried
    assert stats["latent_blocks_read"] > 0
    assert stats["latent_pairs"] >= 4 * 4 * carried


def test_prefix_cache_serves_the_latent_pool(model):
    """A second request with the first's prompt shares its full blocks,
    computes the rest and reads the same logits; a write into a shared
    block splits it."""
    prompt = _prompts((27,), seed=3)[0]
    eng = GenerationEngine(model, **ENGINE, prefix_cache=True)
    eng._step_fn = LogitTap(eng)
    try:
        first = eng.add_request(prompt, max_new_tokens=4)
        while eng.has_unfinished():
            eng.step()
        second = eng.add_request(prompt, max_new_tokens=4)
        while eng.has_unfinished():
            eng.step()
        stats, rows = eng.stats(), eng._step_fn.rows
        assert eng.result(first) == eng.result(second)
    finally:
        eng.close()
    assert stats["prefix_hit_rate"] > 0.4
    for pos in range(27, 31):
        assert _worst_row(rows[(second, pos)][None],
                          rows[(first, pos)][None]) < 1e-6


def test_blocks_cannot_be_exported_from_a_latent_pool():
    cache = PagedKVCache(layer_specs=[{"kind": "latent", "width": 48,
                                       "value_lanes": 32,
                                       "query_heads": 4}] * 2,
                         block_size=8, num_blocks=16, register=False)
    assert cache.latent and cache.sides == 1
    assert [tuple(t.shape) for t in cache.pool_tensors()] \
        == [(17, 8, 128)] * 2
    assert cache.allocate("a", 20)
    with pytest.raises(NotImplementedError, match="latent rows"):
        cache.export_sequence("a")
    with pytest.raises(ValueError, match="not both"):
        PagedKVCache(layer_specs=[
            {"kind": "latent", "width": 48, "query_heads": 4},
            {"kind": "paged_kv", "num_kv_heads": 1, "head_dim": 128}],
            block_size=8, num_blocks=16, register=False)


# (d) the latent kernel against its jnp form ----------------------------
def _kernel_case(rows, block_tokens, heads, ctx_lens, starts, valids,
                 seq_ids, tables, lanes=128, value=64, seed=0):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(12, 8, lanes)), jnp.float32)
    pool = pool.at[0].set(jnp.nan)         # the null block is never read
    q = jnp.asarray(rng.normal(size=(rows, lanes)), jnp.float32)
    args = (q, pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(seq_ids, jnp.int32),
            jnp.asarray(starts, jnp.int32), jnp.asarray(valids, jnp.int32))
    kw = dict(block_q=heads * block_tokens, block_tokens=block_tokens,
              value_lanes=value, scale=0.125)
    with jax.enable_x64(False):
        got = pallas_latent.latent_attention(*args, **kw)
    pool_ok = pool.at[0].set(0.0)
    ref = att._latent_ref(args[0], pool_ok, *args[2:], **kw)
    return np.asarray(got), np.asarray(ref)


def test_kernel_decode_rows_against_the_gather_form():
    """Three decode rows of 16 heads over partly filled tables (one
    ends mid-block, one is idle: the null q-block reads zeros)."""
    tables = [[1, 2, 3, 10], [4, 5, 0, 0], [0, 0, 0, 0]]
    got, ref = _kernel_case(48, 1, 16, [27, 9, 0], [26, 8, 0], [1, 1, 1],
                            [0, 1, 3], tables)
    assert np.isfinite(got).all()
    assert np.abs(got - ref).max() < 1e-5
    assert (got[32:] == 0).all()


def test_kernel_chunk_against_the_gather_form():
    """A 24-token chunk at positions 6-29 of one sequence in q-blocks of
    4 heads by 8 tokens, the last q-block half full."""
    tables = [[6, 7, 8, 9]]
    starts = [6, 14, 22]
    valids = [8, 8, 4]          # 20 real tokens: positions 6 to 25
    got, ref = _kernel_case(3 * 32, 8, 4, [26], starts, valids, [0, 0, 0],
                            tables)
    assert np.abs(got - ref).max() < 1e-5
    rows = np.arange(96)
    dead = (rows // 32 == 2) & (rows % 8 >= 4)
    assert (got[dead] == 0).all() and np.abs(got[~dead]).max() > 0
