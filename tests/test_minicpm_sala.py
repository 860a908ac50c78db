"""MiniCPM-SALA on the CPU at tiny widths: the program against the plain
reference of ``benchmarks/families/minicpm_sala.py`` on logits, for the
dense forward and for prefill-in-chunks-then-decode through the
``GenerationEngine``'s paged and recurrent caches; the two forms of the
lightning recurrence against the token-by-token one; state slots,
preemption and the prefix cache standing aside.

Tolerances.  Program and reference both compute in float32 here, in
different orders (chunked against token by token, gathered blocks
against whole keys): relative L2 of a logits row under ``TOL`` = 1e-4
(measured: 2e-7 to 3e-6).  A bfloat16 state (2**-8 a product) or a
dropped selection term moves a row by 1e-3 or more, and two tests below
show that it fails.  The window, top-k and dense length shrink with the
widths so that a context of 150 tokens prunes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.core.autograd import no_grad
from paddle_tpu.inference.serving import GenerationEngine
from paddle_tpu.inference.serving import attention as serving_attention
from paddle_tpu.inference.serving.engine import ragged_sample_next
from paddle_tpu.ops import pallas_lightning as pll
from paddle_tpu.ops import pallas_sparse as pls

from benchmarks.families import _plain, minicpm_sala as family

TOL = 1e-4
CFG = dict(
    vocab_size=97, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=10000,
    scale_emb=12, scale_depth=1.4, published_layers=32, dim_model_base=16,
    dtype="float32",
    sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                       window_size=32, init_blocks=1, dense_len=64, topk=2))
SIZES = pls.SparseSizes(8, 4, 16, 32, 1, 64, 2)
ENGINE = dict(max_batch=4, block_size=16, num_blocks=64, max_model_len=256,
              prefill_chunk=32)


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    return family.build(CFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 97, n).tolist() for n in (150, 70, 33)]


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
        return ragged_sample_next(logits, view.last_index, seeds,
                                  view.sample_pos, *controls)


def served_logits(model, prompts, new_tokens=10, **engine):
    """Serve ``prompts`` through an engine whose step is tapped; returns
    ``(outputs, tap rows, engine stats)``."""
    eng = GenerationEngine(model, **{**ENGINE, **engine})
    tap = eng._step_fn = LogitTap(eng)
    ids = [eng.add_request(p, max_new_tokens=new_tokens) for p in prompts]
    while eng.has_unfinished():
        eng.step()
    outs = [eng.result(i) for i in ids]
    stats = eng.stats()
    eng.close()
    return ids, outs, tap.rows, stats


def worst_row(model, ids, outs, prompts, rows, cfg=CFG):
    """The largest relative L2 between a served logits row and the
    reference's full forward over the request's final sequence."""
    params, worst = _plain.arrays(model), 0.0
    for rid, out, prompt in zip(ids, outs, prompts):
        ref = np.asarray(family.reference_logits(
            params, cfg, np.asarray(out)[None]))[0]
        for pos in range(len(prompt), len(out)):
            # a requeued request keeps its id and absolute positions
            worst = max(worst, rel_l2(rows[(rid, pos)], ref[pos - 1]))
    return worst


# ---------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------
def test_dense_forward_matches_reference_logits(model):
    ids = np.random.default_rng(1).integers(0, 97, (2, 200))
    out = model(paddle.to_tensor(ids)).numpy()
    ref = np.asarray(family.reference_logits(_plain.arrays(model), CFG, ids))
    assert out.shape == ref.shape == (2, 200, 97)
    assert rel_l2(out, ref) < TOL


def test_engine_prefill_then_decode_matches_reference_logits(model, prompts):
    """150 tokens in chunks of 32 cross four chunk boundaries; past 64
    tokens the selection prunes (2 of up to 5 candidate blocks)."""
    ids, outs, rows, stats = served_logits(model, prompts)
    assert all(len(o) == len(p) + 10 for o, p in zip(outs, prompts))
    assert worst_row(model, ids, outs, prompts, rows) < TOL
    assert stats["state_resets"] == 3 and stats["state_slots_live"] == 0
    assert 0 < stats["sparse_blocks_selected"] \
        < stats["sparse_blocks_visible"]


def test_compiled_engine_decodes_the_reference_greedy_tokens(model, prompts):
    eng = GenerationEngine(model, **ENGINE)
    outs = eng.generate(prompts, max_new_tokens=10)
    assert eng.stats()["step_compiles"] == 1
    eng.close()
    params = _plain.arrays(model)
    for out, prompt in zip(outs, prompts):
        ref = np.asarray(family.reference_logits(
            params, CFG, np.asarray(out)[None]))[0]
        assert out[len(prompt):] == \
            ref.argmax(-1)[len(prompt) - 1:-1].tolist()


def test_a_bfloat16_state_fails_the_tolerance(model, prompts, monkeypatch):
    spec = model.cache_spec()
    for s in spec:
        if s["kind"] == "recurrent":
            s["states"] = {"state": {**s["states"]["state"],
                                     "dtype": "bfloat16"}}
    monkeypatch.setattr(model, "cache_spec", lambda: spec)
    ids, outs, rows, _ = served_logits(model, prompts[:1])
    assert worst_row(model, ids, outs, prompts[:1], rows) > 3 * TOL


def test_a_dropped_selection_term_fails_the_tolerance(model, prompts):
    """The reference without the initial block: what a program that
    left it out would compute."""
    ids, outs, rows, _ = served_logits(model, prompts[:1])
    dropped = {**CFG, "sparse_config": {**CFG["sparse_config"],
                                        "init_blocks": 0}}
    assert worst_row(model, ids, outs, prompts[:1], rows, dropped) > 3 * TOL


# ---------------------------------------------------------------------
# state slots, preemption, the prefix cache
# ---------------------------------------------------------------------
def test_preempted_request_recomputes_its_state(model, prompts):
    """A pool that admits all three (18 of 20 blocks) and cannot hold
    what they grow to (24): one is evicted, loses its blocks and its
    state slot, and comes back through its first chunk."""
    ids, outs, rows, stats = served_logits(model, prompts, new_tokens=40,
                                           num_blocks=20)
    _, whole, _, _ = served_logits(model, prompts, new_tokens=40)
    assert outs == whole
    assert stats["state_resets"] > 3          # a first chunk ran again
    assert worst_row(model, ids, outs, prompts, rows) < TOL


def test_state_slots_are_freed_and_zeroed_on_reuse(model, prompts):
    """One row, so one slot: the requests run one after the other in the
    same slot, each from a zero state, and read what they read alone."""
    eng = GenerationEngine(model, **{**ENGINE, "max_batch": 1})
    outs = eng.generate(prompts[1:], max_new_tokens=6)
    assert eng.cache.free_state_slots == 1 and not eng.cache._slot_of
    state = eng.cache.layer_state(1, "state")._value
    assert float(jnp.abs(state[1]).max()) > 0     # the slot was used
    eng.close()
    for prompt, out in zip(prompts[1:], outs):
        alone = GenerationEngine(model, **ENGINE)
        assert alone.generate([prompt], max_new_tokens=6) == [out]
        alone.close()


def test_prefix_cache_stands_aside_for_recurrent_layers(model, prompts):
    obs.enable(True)
    try:
        counter = obs.get_registry().counter(
            "prefix_cache.bypassed_recurrent")
        before = counter.value
        eng = GenerationEngine(model, **ENGINE, prefix_cache=True)
        shared = prompts[0][:96]
        outs = eng.generate([shared + [1, 2, 3], shared + [4, 5, 6]],
                            max_new_tokens=4)
        stats = eng.stats()
        eng.close()
    finally:
        obs.enable(False)
    assert not eng.cache.prefix_cache
    assert stats["prefix_hit_rate"] == 0.0
    assert stats["prefix_bypassed_recurrent"] == 2
    assert counter.value - before == 2
    assert len(outs[0]) == len(outs[1]) == 103


def test_step_counters_reach_the_registry(model, prompts):
    obs.enable(True)
    try:
        reg = obs.get_registry()
        names = ("state.resets", "sparse.blocks_selected",
                 "sparse.blocks_visible", "sparse.dense_rows")
        before = {n: reg.counter(n).value for n in names}
        eng = GenerationEngine(model, **ENGINE)
        eng.generate(prompts[:2], max_new_tokens=5)
        stats = eng.stats()
        eng.close()
        moved = {n: reg.counter(n).value - before[n] for n in names}
    finally:
        obs.enable(False)
    assert moved["state.resets"] == 2
    assert moved["sparse.blocks_selected"] == stats["sparse_blocks_selected"]
    assert moved["sparse.blocks_visible"] == stats["sparse_blocks_visible"]
    # the 70-token prompt decodes past dense_len 64: no dense row there
    assert moved["sparse.dense_rows"] == stats["sparse_dense_rows"] == 0
    assert stats["state_pool_bytes"] == 2 * 5 * 4 * 16 * 16 * 4
    assert stats["compressed_pool_bytes"] == 2 * 5 * 2 * 128 * 16 * 4


def test_stateful_model_refuses_what_it_cannot_roll_back(model):
    with pytest.raises(ValueError, match="rolled back"):
        GenerationEngine(model, **ENGINE, speculative=2)
    eng = GenerationEngine(model, **ENGINE)
    eng.add_request(list(range(20)), max_new_tokens=2)
    eng.step()
    with pytest.raises(NotImplementedError, match="per-request state"):
        eng.cache.export_sequence("req0")
    eng.close()


def test_gpt_takes_its_geometry_from_the_cache_spec():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64))
    assert gpt.cache_spec() == [{"kind": "paged_kv", "num_kv_heads": 4,
                                 "head_dim": 8}] * 2
    eng = GenerationEngine(gpt, max_batch=2, num_blocks=8, block_size=8)
    k, _ = eng.cache.layer_pools(1)
    assert k.shape == [9, 8, 4 * 8] and eng.cache.state_slots == 0
    assert eng.cache.prefix_cache
    eng.close()


# ---------------------------------------------------------------------
# the lightning recurrence: chunked and one-step against token by token
# ---------------------------------------------------------------------
def recurrence(q, k, v, state, slopes):
    """``S_t = lam S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt d``."""
    lam = np.exp(-np.asarray(slopes))[:, None, None]
    out, state = [], np.asarray(state, np.float64)
    for t in range(q.shape[0]):
        state = lam * state + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hd,hde->he", q[t] / np.sqrt(q.shape[-1]),
                             state))
    return np.stack(out), state


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(5)
    return [rng.standard_normal((80, 4, 16)).astype(np.float32)
            for _ in range(3)]


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["composite", "pallas_interpret"])
def test_chunked_form_crosses_a_chunk_boundary(qkv, kernel):
    """80 tokens as a 48-token chunk and a 32-token one whose buffer
    has 16 rows of padding, against the recurrence in one go."""
    q, k, v = qkv
    slopes = tuple(float(s) for s in pll.decay_slopes(4))
    want_o, want_s = recurrence(q, k, v, np.zeros((4, 16, 16)), slopes)
    pool = jnp.asarray(np.random.default_rng(6).standard_normal(
        (3, 4, 16, 16)), jnp.float32)            # a used slot: not zero
    pad = lambda a: jnp.pad(jnp.asarray(a), ((0, 48 - len(a)), (0, 0),  # noqa: E731,E501
                                             (0, 0)))
    outs = []
    with jax.enable_x64(False):
        for lo, hi, first in ((0, 48, 1), (48, 80, 0)):
            args = [pad(a[lo:hi]) for a in (q, k, v)]
            if kernel:
                o, pool = pll.lightning_attention_fwd(
                    *args, pool, 2, hi - lo, first, slopes, block=16)
            else:
                start = pool[2] * (0.0 if first else 1.0)
                o, st = pll.lightning_chunk_ref(*args, start, slopes,
                                                hi - lo, block=16)
                pool = pool.at[2].set(st)
            outs.append(np.asarray(o)[:hi - lo])
    assert rel_l2(np.concatenate(outs), want_o) < 1e-5
    assert rel_l2(pool[2], want_s) < 1e-5


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["composite", "pallas_interpret"])
def test_one_step_form_follows_a_chunk(qkv, kernel):
    q, k, v = qkv
    slopes = tuple(float(s) for s in pll.decay_slopes(4))
    want_o, want_s = recurrence(q[:50], k[:50], v[:50],
                                np.zeros((4, 16, 16)), slopes)
    _, state = pll.lightning_chunk_ref(
        *(jnp.asarray(a[:48]) for a in (q, k, v)),
        jnp.zeros((4, 16, 16), jnp.float32), slopes, 48, block=16)
    pool = jnp.zeros((4, 4, 16, 16), jnp.float32).at[3].set(state)
    step = pll.lightning_attention_step if kernel else pll.lightning_step_ref
    with jax.enable_x64(False):
        for t in (48, 49):
            # row 0 idles on the pad slot, row 1 is the sequence
            rows = [jnp.stack([jnp.zeros((4, 16), jnp.float32),
                               jnp.asarray(a[t])]) for a in (q, k, v)]
            o, pool = step(*rows, pool, jnp.array([0, 3], jnp.int32), slopes)
            assert rel_l2(o[1], want_o[t]) < 1e-5
    assert rel_l2(pool[3], want_s) < 1e-5
    assert float(jnp.abs(pool[1:3]).max()) == 0.0    # other slots untouched


# ---------------------------------------------------------------------
# the selector
# ---------------------------------------------------------------------
def test_score_kernel_matches_its_composite():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((3, 8, 16)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((4, 2, 64, 16)), jnp.float32)
    slots = jnp.array([2, 0, 3], jnp.int32)
    t = jnp.array([150, -1, 9], jnp.int32)
    with jax.enable_x64(False):
        got = pls.sparse_select_scores(q, ck, slots, t, SIZES,
                                       use_pallas=True)
    want = pls.sparse_select_scores(q, ck, slots, t, SIZES)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert float(want[1].sum()) == 0.0               # an idle row
    # eight heads' softmaxes over the visible keys sum to eight
    assert abs(float(want[0].sum()) - 8.0) < 1e-4


@pytest.mark.parametrize("context", [1, 16, 64, 65, 100, 113, 150, 256])
def test_selected_count_is_what_the_selection_selects(context):
    """The counters' host arithmetic against the device's selection, and
    the selection against the reference's."""
    rng = np.random.default_rng(context)
    scores = jnp.asarray(rng.random((1, 64)), jnp.float32)
    t = jnp.array([context - 1], jnp.int32)
    sel = np.asarray(pls.select_blocks(scores, t, 16, SIZES))[0]
    assert (int(sel.sum()), (context - 1) // 16 + 1) == \
        pls.selected_count(context, SIZES)
    ref = np.asarray(family.select_blocks(scores, t, 16, tuple(SIZES)))[0]
    assert (sel == ref).all()
    tables, ctx = pls.selected_tables(
        scores[None], t, jnp.arange(100, 116, dtype=jnp.int32)[None],
        SIZES, SIZES.table_width(256))
    assert int(ctx[0, 0]) == (sel.sum() - 1) * 16 + (context - 1) % 16 + 1
    assert np.asarray(tables[0, 0])[:sel.sum()].tolist() == \
        (100 + np.flatnonzero(sel)).tolist()


def test_table_width_covers_window_top_k_and_dense():
    published = pls.SparseSizes()
    # 1 initial + 64 chosen + 33 blocks that a 2,048-token window can
    # touch when it is not block aligned = 98; dense rows read 128
    assert published.table_width(20480) == 128
    assert published._replace(dense_len=0).table_width(20480) == 98
    assert max(pls.selected_count(c, published)[0]
               for c in range(8193, 12000)) == 98


def test_grouped_decode_reads_the_selected_blocks_only():
    """Two KV heads, four query heads each, a table of two blocks out of
    five: against plain attention over exactly those tokens."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    pools = [jnp.asarray(rng.standard_normal((5, 4, 2 * 16)), jnp.float32)
             for _ in range(2)]
    tables = jnp.array([[[3, 1], [4, 2]], [[2, 0], [0, 0]]], jnp.int32)
    ctx = jnp.array([[6, 7], [3, 0]], jnp.int32)     # row 1, head 1 idles
    out = np.asarray(serving_attention.grouped_decode_attention(
        q, *pools, tables, ctx, use_pallas=False))
    for r in range(2):
        for g in range(2):
            n = int(ctx[r, g])
            if not n:
                assert not out[r, 4 * g:4 * g + 4].any()
                continue
            k, v = (np.concatenate([np.asarray(p[b, :, 16 * g:16 * g + 16])
                                    for b in
                                    np.asarray(tables[r, g])])[:n]
                    for p in pools)
            s = np.asarray(q[r, 4 * g:4 * g + 4]) @ k.T / 4.0
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ v
            assert rel_l2(out[r, 4 * g:4 * g + 4], want) < 1e-5
