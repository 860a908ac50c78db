"""Qwen3-Next through the model, the gated delta rule's two forms, the
expert layer that holds a share of the experts, and the engine's paged
and multi-state recurrent caches; the plain reference is
``benchmarks/families/qwen3_next.py``.  Float32, seeded, tiny widths."""
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
from benchmarks.families import qwen3_next as family  # noqa: E402
from paddle_tpu.core.autograd import no_grad  # noqa: E402
from paddle_tpu.distributed.auto_parallel import moe_dispatch as md  # noqa: E402
from paddle_tpu.inference.serving import GenerationEngine  # noqa: E402
from paddle_tpu.inference.serving import attention as att  # noqa: E402
from paddle_tpu.inference.serving.engine import ragged_sample_next  # noqa: E402
from paddle_tpu.models import qwen3_next as program  # noqa: E402
from paddle_tpu.ops import pallas_gated_delta as pgd  # noqa: E402

# the published pattern (delta, delta, delta, attention) at width 64:
# 16 experts top-4 of which all are held, key and value heads of 16
TINY = dict(
    dtype="float32", vocab_size=256, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=10000000, linear_conv_kernel_dim=4, linear_key_head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=16, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=16,
    published_num_experts=16, expert_shard={"chips": 1, "index": 0},
    num_experts_per_tok=4, norm_topk_prob=True,
    max_position_embeddings=512, rms_norm_eps=1e-6, initializer_range=0.02,
    norm_weight_std=0.1, a_range=[1.0, 16.0], dt_range=[0.001, 0.1],
    kv_block_size=8)
ENGINE = dict(max_batch=4, max_model_len=128, block_size=8, prefill_chunk=8,
              num_blocks=64)
# Float32 everywhere, so program and reference differ by the order of
# their sums: the chunked form against the recurrence, the grouped
# buffer against the masked sum, blocks of keys against all keys.  That
# reads 2e-7 to 4e-7 of a row's norm here; the negatives below read
# 3e-3 (a bfloat16 state) to 14 (``w`` for ``1 + w``).
TOL = 1e-5


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
def _dense_distance(model, cfg=TINY, params=None, length=50):
    ids = np.random.default_rng(length).integers(0, 256, (2, length))
    got = model(paddle.to_tensor(ids)).value()
    ref = family.reference_logits(params or _plain.arrays(model), cfg,
                                  jnp.asarray(ids))
    return _worst_row(got, ref)


@pytest.mark.parametrize("length", [5, 50, 100])
def test_model_matches_the_reference(model, length):
    assert _dense_distance(model, length=length) < TOL


def test_layers_and_stacks_are_what_the_config_says(model):
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    assert shapes["model.layers.0.mlp.experts.gate_up"] == (16, 64, 64)
    assert shapes["model.layers.0.mlp.experts.down"] == (16, 32, 64)
    assert shapes["model.layers.3.self_attn.q_proj.weight"] == (64, 128)
    assert shapes["model.layers.2.linear_attn.in_proj_qkv.weight"] \
        == (64, 2 * 32 + 64)
    assert shapes["model.layers.2.linear_attn.conv_weight"] == (4, 128)
    kinds = [s["kind"] for s in model.cache_spec()]
    assert kinds == ["recurrent"] * 3 + ["paged_kv"]
    assert set(model.cache_spec()[0]["states"]) == {"delta", "conv"}
    with pytest.raises(NotImplementedError, match="serving engine"):
        model(paddle.to_tensor(np.zeros((1, 4), np.int64)), use_cache=True)
    # a token keeps between e^-1.6 and e^-0.001 of the state
    mixer = model.model.layers[0].linear_attn
    keep = np.exp(-np.exp(np.asarray(mixer.A_log.value()))
                  * np.log1p(np.exp(np.asarray(mixer.dt_bias.value()))))
    assert (keep > np.exp(-1.7)).all() and (keep < 1).all()


# (b) the engine: chunks of 8, then decode, three requests in flight ----
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
    """Serve ``prompts`` through an engine whose step is tapped; returns
    ``(request ids, sequences, tap rows, engine stats)``."""
    eng = GenerationEngine(model, **{**ENGINE, **engine})
    tap = eng._step_fn = LogitTap(eng)
    rids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    try:
        while eng.has_unfinished():
            eng.step()
        return rids, [eng.result(r) for r in rids], tap.rows, eng.stats()
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
        # a requeued request keeps its id and absolute positions
        got = np.stack([rows[(rid, pos)]
                        for pos in range(len(prompt), len(seq))])
        worst = max(worst, _worst_row(got, ref[len(prompt) - 1:-1]))
    return worst


def test_engine_matches_the_reference(model):
    """Prefill in chunks of 8, then 12 decode steps, three requests of
    unequal length in flight."""
    prompts = _prompts((5, 29, 18))
    served = step_logits(model, prompts)
    _, sequences, _, stats = served
    assert all(len(s) == len(p) + 12 for s, p in zip(sequences, prompts))
    assert served_distance(model, prompts, served) < TOL
    assert stats["prefill_chunks"] == 1 + 4 + 3
    assert stats["state_resets"] == 3 and stats["state_slots"] == 4
    # three delta layers: a float32 state a value head and three inputs
    # of the convolution, five slots (the pad slot)
    assert stats["state_pool_bytes"] == 3 * 5 * (4 * 16 * 16 * 4
                                                 + 3 * 128 * 4)
    carried = stats["decode_rows_carried"] + stats["prompt_tokens_carried"]
    assert stats["moe_assignments"] == stats["moe_assignments_routed"] \
        == 4 * 4 * carried
    assert 0 < stats["kv_blocks_read_full"] <= stats["kv_table_slots"]
    assert stats["kv_blocks_read_window"] == 0


def test_a_reused_slot_and_a_preemption_start_from_zero(model):
    """One row: the requests run one after the other in the same slot.
    Then a pool that cannot hold what three grow to: one is evicted and
    comes back through its first chunk."""
    prompts = _prompts((20, 13), seed=4)
    served = step_logits(model, prompts, max_batch=1)
    assert served_distance(model, prompts, served) < TOL
    assert served[3]["state_resets"] == 2
    prompts = _prompts((30, 30, 30), seed=6)
    served = step_logits(model, prompts, new_tokens=30, num_blocks=14)
    assert served[3]["state_resets"] > 3
    assert served_distance(model, prompts, served) < TOL


# (c) the share adds up -------------------------------------------------
@pytest.mark.parametrize("use_pallas", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(model, use_pallas,
                                                   monkeypatch):
    """The routed halves of an expert layer held as experts 0-3, 4-7,
    8-11, 12-15 of 16, and the shared expert counted once, sum to the
    uncut layer's output and to the reference's."""
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
            x, w["mlp.router.weight"], w["mlp.experts.gate_up"][lo:hi],
            w["mlp.experts.down"][lo:hi], carried, top_k=4,
            norm_topk=True, held=held, use_pallas=use_pallas)
        return np.asarray(y), np.asarray(counters)

    whole, counted = routed((0, 16))
    parts = [routed((lo, lo + 4)) for lo in range(0, 16, 4)]
    assert np.abs(sum(p for p, _ in parts) - whole).max() < 1e-6
    assert counted[0] == counted[4] == 4 * 20
    assert sum(c[0] for _, c in parts) == counted[0]
    assert all(c[4] == counted[4] for _, c in parts)
    assert all(c[1] <= 4 for _, c in parts)
    stacks = tuple((w[k], None) for k in family._STACKS)
    low = lambda a, scale=None: a.astype(jnp.float32)      # noqa: E731
    with jax.default_matmul_precision("highest"):
        ref = family.expert_ffn(x, w, stacks, TINY, low)
        shared = jax.nn.sigmoid(x @ w["mlp.shared_expert_gate.weight"]) \
            * family.swiglu(x, w["mlp.shared_expert.gate_proj.weight"],
                            w["mlp.shared_expert.up_proj.weight"],
                            w["mlp.shared_expert.down_proj.weight"])
        ref_parts = [family.expert_ffn(
            x, w, tuple((w[k][lo:lo + 4], None) for k in family._STACKS),
            TINY, low, held=(lo, lo + 4)) - shared
            for lo in range(0, 16, 4)]
    live = np.asarray(carried)[:, None]
    assert np.abs(whole + np.asarray(shared) - np.asarray(ref))[
        np.asarray(carried)].max() < 1e-6
    assert (whole[~np.asarray(carried)] == 0).all()
    for (part, _), ref_part in zip(parts, ref_parts):
        assert np.abs(part - np.asarray(ref_part) * live).max() < 1e-6


def test_a_model_that_holds_a_quarter_matches_the_reference_of_it():
    """The whole model as chip 2 of 4 (experts 8-11 of 16), against the
    reference given the same share."""
    cfg = {**TINY, "num_experts": 4,
           "expert_shard": {"chips": 4, "index": 2}}
    paddle.seed(9)
    part = family.build(cfg)
    assert part.config.held_experts == (8, 12)
    assert tuple(part.model.layers[0].mlp.experts.gate_up.shape) \
        == (4, 64, 64)
    assert _dense_distance(part, cfg) < TOL
    prompts = _prompts((21, 10), seed=8)
    served = step_logits(part, prompts, new_tokens=6)
    assert served_distance(part, prompts, served, cfg) < TOL
    assert 0 < served[3]["moe_assignments"] \
        < served[3]["moe_assignments_routed"]


def test_softmax_router_renormalises_and_breaks_ties_low():
    logits = jnp.log(jnp.asarray([[0.1, 0.4, 0.1, 0.4]]))
    idx, w = md.softmax_topk_router(logits, 3)
    assert idx.tolist() == [[1, 3, 0]]
    np.testing.assert_allclose(np.asarray(w), [[4 / 9, 4 / 9, 1 / 9]],
                               rtol=1e-6)
    _, raw = md.softmax_topk_router(logits, 3, norm_topk=False)
    np.testing.assert_allclose(np.asarray(raw), [[0.4, 0.4, 0.1]],
                               rtol=1e-6)


# (d) the chunked form against the recurrence ---------------------------
def _recurrence(q, k, v, g, beta, state):
    """The rule a token at a time, in float64."""
    q, k, v, g, beta, state = (np.asarray(a, np.float64)
                               for a in (q, k, v, g, beta, state))
    rep = v.shape[1] // q.shape[1]
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True)  # noqa: E731
                                 + 1e-6)
    q = np.repeat(unit(q) / np.sqrt(q.shape[-1]), rep, 1)
    k = np.repeat(unit(k), rep, 1)
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        state = np.exp(g[t])[:, None, None] * state
        delta = beta[t][:, None] * (
            v[t] - np.einsum("hde,hd->he", state, k[t]))
        state = state + k[t][:, :, None] * delta[:, None, :]
        out[t] = np.einsum("hde,hd->he", state, q[t])
    return out, state


def _delta_inputs(tokens, hk=2, hv=4, dk=16, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(tokens, hk, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(tokens, hv, dv)), jnp.float32)
    # exp(g) from 0.5 to 0.999, head by head and token by token
    g = jnp.asarray(np.log(rng.uniform(0.5, 0.999, (tokens, hv))),
                    jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (tokens, hv)), jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("block", [64, 16, 8])
def test_chunk_composite_matches_the_recurrence(block):
    """200 tokens in a 256-row chunk, then a second chunk that starts
    from the first's state."""
    q, k, v, g, beta = _delta_inputs(256 + 64)
    zero = jnp.zeros((4, 16, 16), jnp.float32)
    first = tuple(a[:256] for a in (q, k, v, g, beta))
    o1, s1 = pgd.gated_delta_chunk_ref(*first, zero, 200, block)
    ref_o, ref_s = _recurrence(*(a[:200] for a in first), zero)
    assert np.abs(np.asarray(o1)[:200] - ref_o).max() < 2e-5
    assert np.abs(np.asarray(s1) - ref_s).max() < 2e-5
    second = tuple(a[256:] for a in (q, k, v, g, beta))
    o2, s2 = pgd.gated_delta_chunk_ref(*second, s1, 64, block)
    ref_o2, ref_s2 = _recurrence(*second, ref_s)
    assert np.abs(np.asarray(o2) - ref_o2).max() < 2e-5
    assert np.abs(np.asarray(s2) - ref_s2).max() < 2e-5


def test_kernels_match_their_composites_in_interpret_mode(monkeypatch):
    """Both kernels at the kernel's own tile sizes (heads of 128, sub-
    chunks of 64) against the composites, in place on a pool; the chunk
    kernel's inverse by blocks against the triangular solve."""
    monkeypatch.setattr(pgd, "_interpret", lambda: True)
    q, k, v, g, beta = _delta_inputs(128, hk=1, hv=2, dk=128, dv=128,
                                     seed=3)
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(4, 2, 128, 128)) * 0.1, jnp.float32)
    for first in (0, 1):
        got_o, got_pool = pgd.gated_delta_rule_fwd(
            q, k, v, g, beta, pool, 2, 100, first)
        ref_o, ref_s = pgd.gated_delta_chunk_ref(
            q, k, v, g, beta, pool[2] * (1 - first), 100)
        assert np.abs(np.asarray(got_o - ref_o))[:100].max() < 2e-5
        assert np.abs(np.asarray(got_pool[2] - ref_s)).max() < 2e-5
        assert (np.asarray(got_pool)[[0, 1, 3]]
                == np.asarray(pool)[[0, 1, 3]]).all()
    slots = jnp.asarray([3, 0, 1], jnp.int32)
    got_o, got_pool = pgd.gated_delta_rule_step_fwd(
        q[:3], k[:3], v[:3], g[:3], beta[:3], pool, slots)
    ref_o, ref_pool = pgd.gated_delta_step_ref(
        q[:3], k[:3], v[:3], g[:3], beta[:3], pool, slots)
    assert np.abs(np.asarray(got_o - ref_o)).max() < 2e-5
    assert np.abs(np.asarray(got_pool - ref_pool)).max() < 2e-5


def test_step_composite_continues_the_chunk(model):
    q, k, v, g, beta = _delta_inputs(40, seed=5)
    zero = jnp.zeros((4, 16, 16), jnp.float32)
    ref_o, ref_s = _recurrence(q, k, v, g, beta, zero)
    _, state = pgd.gated_delta_chunk_ref(
        *(a[:32] for a in (q, k, v, g, beta)), zero, 32)
    pool = jnp.zeros((3, 4, 16, 16), jnp.float32).at[2].set(state)
    for t in range(32, 40):
        o, pool = pgd.gated_delta_step_ref(
            *(a[t:t + 1] for a in (q, k, v, g, beta)), pool,
            jnp.asarray([2], jnp.int32))
        assert np.abs(np.asarray(o[0]) - ref_o[t]).max() < 2e-5
    assert np.abs(np.asarray(pool[2]) - ref_s).max() < 2e-5


# (e) negatives: each has to fail the comparison it is aimed at ---------
def _bfloat16_state(model, monkeypatch):
    spec = model.cache_spec()
    for s in spec:
        if s["kind"] == "recurrent":
            s["states"] = {**s["states"], "delta": {
                **s["states"]["delta"], "dtype": "bfloat16"}}
    monkeypatch.setattr(model, "cache_spec", lambda: spec)
    return {}


def _slot_not_reset(model, monkeypatch):
    """The first-chunk flag never reaches the layers; one row, so the
    second request runs in the slot the first one left."""
    real = att.RaggedCacheView.stage_state

    def stage(self, dec_index, row_slots, row_pos, meta):
        meta = np.asarray(meta).copy()
        meta[3] = 0
        return real(self, dec_index, row_slots, row_pos, meta)

    monkeypatch.setattr(att.RaggedCacheView, "stage_state", stage)
    return {"max_batch": 1}


def _conv_state_dropped(model, monkeypatch):
    """Every chunk convolves after nothing, as a first chunk does."""
    real = att._gated_delta_update_impl

    def update(x, g, beta, conv_w, pool, conv_pool, *rest, **how):
        out, pool, _ = real(x, g, beta, conv_w, pool,
                            jnp.zeros_like(conv_pool), *rest, **how)
        return out, pool, conv_pool

    monkeypatch.setattr(att, "_gated_delta_update_impl", update)
    return {}


@pytest.mark.parametrize("fault", [_bfloat16_state, _slot_not_reset,
                                   _conv_state_dropped])
def test_a_fault_in_the_served_state_fails_the_tolerance(
        model, fault, monkeypatch):
    prompts = _prompts((26, 19), seed=4)
    engine = fault(model, monkeypatch)
    served = step_logits(model, prompts, **engine)
    assert served_distance(model, prompts, served) > 20 * TOL


def _rope_on_every_lane(model):
    return {**TINY, "partial_rotary_factor": 1.0}, None


def _w_for_one_plus_w(model):
    """The reference handed ``w - 1`` computes ``x rsqrt(.) w``."""
    params = _plain.arrays(model)
    return TINY, {k: a - 1.0 if k.endswith("layernorm.weight")
                  or k.endswith("_norm.weight") or k == "model.norm.weight"
                  else a for k, a in params.items()}


@pytest.mark.parametrize("fault", [_rope_on_every_lane, _w_for_one_plus_w])
def test_a_fault_in_the_equations_fails_the_dense_comparison(model, fault):
    cfg, params = fault(model)
    assert _dense_distance(model, cfg, params) > 20 * TOL
