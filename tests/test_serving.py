"""LLM serving: paged KV cache with COW prefix caching, ragged
attention, chunked-prefill continuous batching, GenerationEngine, and
the seeded sampling ops.

CPU tier-1: the ragged attention runs its pure-XLA fallback here (the
Pallas kernel itself is covered in interpret mode by
tests/test_pallas_kernels.py), so these tests exercise the exact
semantics the TPU path serves.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatchingScheduler,
                                          GenerationEngine, NgramProposer,
                                          PagedKVCache, PrefillChunk,
                                          Request, SpeculativeConfig,
                                          StreamEvent, TokenStream,
                                          VictimPolicy)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.serve

VOCAB = 97


@pytest.fixture(autouse=True)
def _serving_env(monkeypatch):
    for var in ("PADDLE_TPU_HBM_BUDGET", "PADDLE_TPU_MEMORY_GUARD",
                "PADDLE_TPU_KV_BLOCK_SIZE", "PADDLE_TPU_MAX_BATCH",
                "PADDLE_TPU_PIPELINE_DEPTH", "PADDLE_TPU_PREFIX_CACHE",
                "PADDLE_TPU_PREFILL_CHUNK", "PADDLE_TPU_SPEC_K",
                "PADDLE_TPU_SPEC_DRAFT", "PADDLE_TPU_STREAM_QUEUE"):
        monkeypatch.delenv(var, raising=False)
    yield


@pytest.fixture(scope="module")
def gpt_mini():
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
    paddle.seed(7)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, VOCAB, size=n)) for n in lengths]


def _dense_generate(model, prompt, **kwargs):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, **kwargs)
    return np.asarray(out.numpy())[0].tolist()


# ---------------------------------------------------------------------
# cache manager
# ---------------------------------------------------------------------
def test_kv_cache_alloc_append_free():
    c = PagedKVCache(num_layers=2, num_heads=2, head_dim=8,
                     block_size=4, num_blocks=10, max_model_len=40,
                     register=False)
    assert c.free_blocks == 10
    assert c.table_width == 10
    assert c.allocate("a", 6)               # 2 blocks
    assert c.blocks_in_use == 2 and c.length("a") == 6
    with pytest.raises(KeyError):
        c.allocate("a", 1)
    # slots are contiguous within a block, block 0 never handed out
    slots = c.slot_mapping("a", 0, 6)
    assert slots.dtype == np.int32 and len(slots) == 6
    assert all(s >= c.block_size for s in slots)  # pad block excluded
    assert slots[1] == slots[0] + 1
    # append crosses a block boundary at 8 -> 9 tokens
    assert c.append("a", 2) and len(c._tables["a"]) == 2
    assert c.append("a", 1) and len(c._tables["a"]) == 3
    table = c.block_table("a")
    assert table.shape == (10,) and table[3] == 0  # padded with block 0
    # exhaust the pool, then free returns everything
    assert not c.allocate("b", 100)
    assert c.allocate("c", 4 * 7)
    assert c.free_blocks == 0 and not c.append("a", 4)
    assert c.free("c") == 7
    assert c.free("a") == 3 and c.free_blocks == 10
    assert c.free("a") == 0                 # double-free is a no-op
    assert c.high_water == 10
    s = c.stats()
    assert s["num_blocks"] == 10 and s["high_water"] == 10


def test_kv_cache_truncate_rolls_back_reserved_slots():
    c = PagedKVCache(num_layers=1, num_heads=2, head_dim=8,
                     block_size=4, num_blocks=8, max_model_len=32,
                     register=False)
    assert c.allocate("a", 5)              # 2 blocks
    assert c.append("a", 3) and c.length("a") == 8
    assert c.append("a", 1) and len(c._tables["a"]) == 3
    c.truncate("a", 5)
    assert c.length("a") == 5 and len(c._tables["a"]) == 2
    assert c.free_blocks == 6
    with pytest.raises(ValueError):
        c.truncate("a", 9)
    assert "a" in c and "b" not in c
    # the rolled-back slots are reusable immediately
    assert c.append("a", 4) and c.length("a") == 9


def test_kv_cache_budget_sizing(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_HBM_BUDGET", "1M")
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, register=False, hbm_fraction=0.5)
    # 2 * 1 * 1 * 4 * 8 * 4B = 256 B/block; 512K budget share -> 2048
    assert c.bytes_per_block == 256
    assert c.num_blocks - 1 == 2048
    monkeypatch.setenv("PADDLE_TPU_KV_BLOCK_SIZE", "32")
    c2 = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                      num_blocks=4, register=False)
    assert c2.block_size == 32


def test_kv_cache_resident_line_item(monkeypatch):
    """The pool registers as a named memory-guard line item: programs
    that do NOT carry the pool get charged; the serving steps (which
    take the pool as state) see the line item but skip the double
    charge."""
    from paddle_tpu.memory.guard import last_estimate
    c = PagedKVCache(num_layers=1, num_heads=2, head_dim=8,
                     block_size=4, num_blocks=8, max_model_len=16)
    try:
        fn = paddle.jit.to_static(lambda x: x * 2.0)
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        fn(x)
        fn(x)
        est = last_estimate()
        names = [n for n, _ in est.buffers]
        assert "kv cache blocks" in names
        assert est.resident_bytes == c.pool_bytes
        assert est.total_bytes >= c.pool_bytes
    finally:
        c.close()
    fn2 = paddle.jit.to_static(lambda x: x + 1.0)
    fn2(x)
    fn2(x)
    est = last_estimate()
    assert "kv cache blocks" not in [n for n, _ in est.buffers]


# ---------------------------------------------------------------------
# ragged attention's reference vs dense attention
# ---------------------------------------------------------------------
def test_paged_attention_matches_dense():
    """`_ragged_ref` over one-token segments (every row a decode step)
    reading through block tables, against dense single-query attention
    over each sequence's own prefix."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving.attention import _ragged_ref
    from paddle_tpu.nn.functional.flash_attention import _sdpa_ref
    from paddle_tpu.ops.pallas_ragged import ragged_segments

    rng = np.random.RandomState(3)
    H, D, bs, block_q = 4, 16, 4, 8
    ctxs = [9, 3, 1]
    W = 4
    kd = rng.randn(len(ctxs), max(ctxs), H, D).astype(np.float32)
    vd = rng.randn(len(ctxs), max(ctxs), H, D).astype(np.float32)
    q = rng.randn(len(ctxs), 1, H, D).astype(np.float32)
    # scatter the dense K/V into a pool via per-sequence block tables
    kp = np.zeros((16, bs, H * D), np.float32)
    vp = np.zeros_like(kp)
    tables = np.zeros((len(ctxs), W), np.int32)
    nxt = 1
    for i, ctx in enumerate(ctxs):
        for t in range(ctx):
            if t % bs == 0:
                tables[i, t // bs] = nxt
                nxt += 1
            blk, off = tables[i, t // bs], t % bs
            kp[blk, off] = kd[i, t].reshape(-1)
            vp[blk, off] = vd[i, t].reshape(-1)
    # one token a sequence: each fills row 0 of a q-block of its own
    sid, qs, qv, offsets, rows = ragged_segments([1] * len(ctxs), ctxs,
                                                 block_q)
    flat = np.zeros((rows, H, D), np.float32)
    flat[offsets] = q[:, 0]
    out = _ragged_ref(jnp.asarray(flat), jnp.asarray(kp), jnp.asarray(vp),
                      jnp.asarray(tables), jnp.asarray(np.array(ctxs)),
                      jnp.asarray(sid), jnp.asarray(qs), jnp.asarray(qv),
                      block_q, 1.0 / np.sqrt(D))
    for i, ctx in enumerate(ctxs):
        # dense single-query attention over that sequence's prefix
        ref = _sdpa_ref(jnp.asarray(q[i:i + 1]),
                        jnp.asarray(kd[i:i + 1, :ctx]),
                        jnp.asarray(vd[i:i + 1, :ctx]),
                        None, False, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out[offsets[i]]),
                                   np.asarray(ref[0, 0]), rtol=2e-5,
                                   atol=2e-6)
    # the padding rows of each q-block see nothing: exact zeros
    pad = np.setdiff1d(np.arange(rows), offsets)
    assert float(np.abs(np.asarray(out)[pad]).sum()) == 0.0


# ---------------------------------------------------------------------
# COW prefix cache
# ---------------------------------------------------------------------
def test_prefix_cache_hash_hit_and_refcounts():
    c = PagedKVCache(num_layers=1, num_heads=2, head_dim=8,
                     block_size=4, num_blocks=10, max_model_len=40,
                     register=False)
    p = list(range(1, 13))                     # 3 full blocks
    assert c.allocate("a", 12, tokens=p)
    assert c.cached_prefix_len("a") == 0       # cold cache
    c.commit_prefix("a", p)
    # same prompt again: the first two blocks are shared; the reuse cap
    # (num_tokens - 1) keeps the last block computed for logits
    assert c.allocate("b", 12, tokens=p)
    assert c.cached_prefix_len("b") == 8
    assert c.shared_blocks == 2
    s = c.stats()
    assert s["logical_blocks"] == 6 and s["physical_blocks"] == 4
    assert c.prefix_hit_rate == pytest.approx(8 / 24)
    # a third reader piles onto the same physical blocks
    assert c.allocate("d", 12, tokens=p)
    assert c.blocks_in_use == 5 and c.shared_blocks == 2


def test_prefix_cache_cow_split_on_write():
    c = PagedKVCache(num_layers=1, num_heads=2, head_dim=8,
                     block_size=4, num_blocks=10, max_model_len=40,
                     register=False)
    p = list(range(1, 13))
    assert c.allocate("a", 12, tokens=p)
    c.commit_prefix("a", p)
    assert c.allocate("b", 12, tokens=p)       # shares blocks 0 and 1
    shared = c._tables["b"][1]
    assert c._tables["a"][1] == shared
    # roll b back into the shared block, then write: the write must
    # COW-split instead of corrupting a's copy
    c.truncate("b", 6)
    assert c.shared_blocks == 2                # truncate never splits
    assert c.append("b", 1)
    assert c.cow_splits == 1 and c.stats()["cow_splits"] == 1
    assert c._tables["a"][1] == shared         # a keeps the original
    assert c._tables["b"][1] != shared
    assert c._tables["b"][0] == c._tables["a"][0]  # block 0 still shared


def test_prefix_cache_eviction_order_children_first():
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, num_blocks=8, max_model_len=32,
                     register=False)
    p = list(range(1, 13))
    assert c.allocate("a", 12, tokens=p)
    c.free("a", tokens=p)                      # all 3 full blocks parked
    assert c.free_blocks == 8 and len(c._cached_free) == 3
    # pressure evicts the chain TIP first, parents last — a shorter
    # shared prefix survives as long as possible
    assert c.allocate("big", 24)               # 6 blocks: evicts one
    assert len(c._cached_free) == 2
    assert c.allocate("b", 5, tokens=p[:5])    # root block still hits
    assert c.cached_prefix_len("b") == 4


def test_prefix_cache_truncate_of_shared_block():
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, num_blocks=10, max_model_len=40,
                     register=False)
    p = list(range(1, 13))
    assert c.allocate("a", 12, tokens=p)
    c.commit_prefix("a", p)
    assert c.allocate("b", 12, tokens=p)
    used = c.blocks_in_use
    c.truncate("b", 4)    # drops b's private tail AND one shared block
    # the shared block just lost a reference — a still reads it
    assert c.length("a") == 12 and c.shared_blocks == 1
    assert c.blocks_in_use == used - 1         # only the private block
    assert c._ref[c._tables["a"][1]] == 1
    # freeing a parks its (still-indexed) blocks instead of losing them
    c.free("a", tokens=p)
    assert c.allocate("d", 12, tokens=p)
    assert c.cached_prefix_len("d") == 8


def test_prefix_cache_disabled_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "0")
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, num_blocks=10, max_model_len=40,
                     register=False)
    p = list(range(1, 13))
    assert c.allocate("a", 12, tokens=p)
    c.commit_prefix("a", p)
    assert c.allocate("b", 12, tokens=p)
    assert c.cached_prefix_len("b") == 0 and c.shared_blocks == 0


# ---------------------------------------------------------------------
# scheduler policy
# ---------------------------------------------------------------------
def test_scheduler_admission_chunking_and_preemption_order():
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, num_blocks=6, max_model_len=24,
                     register=False)
    s = ContinuousBatchingScheduler(c, max_batch=2, prefill_chunk=4)
    a, b, d = (Request("a", [1] * 6), Request("b", [2] * 6),
               Request("d", [3] * 6))
    for r in (a, b, d):
        s.submit(r)
    # oldest first; admission respects the free-block budget
    act, req = s.next_action()
    assert act == "admit" and req is a
    s.begin_prefill(a)
    # admission is serialized behind in-flight prefill: the next action
    # is a's first chunk, not b's admission
    act, (chunk, decodes) = s.next_action()
    assert act == "step" and chunk == PrefillChunk(a, 0, 4)
    assert decodes == []
    a.num_computed = 4
    act, (chunk, decodes) = s.next_action()
    assert chunk == PrefillChunk(a, 4, 2)      # ragged tail chunk
    a.num_computed = 6                         # prefill complete
    act, req = s.next_action()
    assert act == "admit" and req is b
    s.begin_prefill(b)
    # batch full (max_batch=2): b's chunk rides with a's decode in ONE
    # unified step — no separate prefill/decode programs
    act, (chunk, decodes) = s.next_action()
    assert act == "step" and chunk.request is b and decodes == [a]
    b.num_computed = 6
    # youngest running is the preemption victim
    assert s.preempt_youngest() is b
    s.requeue(b, [42, 43])
    assert s.waiting[0] is b and b.prompt[-2:] == [42, 43]
    assert b.preemptions == 1 and b.num_computed == 0
    # a prompt that can never fit raises instead of livelocking
    s.finish(a)
    big = Request("big", [1] * 23)
    s.waiting.clear()
    s.submit(big)
    c.allocate("hog", 24 - c.block_size)
    try:
        with pytest.raises(RuntimeError):
            while True:
                act, req = s.next_action()
                if act != "admit":
                    break
                s.begin_prefill(req)
    finally:
        c.free("hog")


def test_scheduler_requeue_preserves_prefix_credit():
    """Satellite: a preempted request re-enters with its still-cached
    prefix blocks instead of re-prefilling from token 0."""
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, num_blocks=8, max_model_len=32,
                     register=False)
    s = ContinuousBatchingScheduler(c, max_batch=2, prefill_chunk=8)
    a = Request("a", list(range(1, 9)), max_new_tokens=4)
    s.submit(a)
    act, req = s.next_action()
    assert act == "admit"
    s.begin_prefill(a)
    a.num_computed = 8                   # both full blocks written
    s.requeue(a, [99])                   # preempted after one token
    assert "a" not in c and a.num_computed == 0
    # re-admission: the written blocks were hash-indexed on free, so
    # allocate() shares them and prefill skips the cached prefix
    act, req = s.next_action()
    assert act == "admit" and req is a
    s.begin_prefill(a)
    assert a.cached_prefix == 8 and a.num_computed == 8
    assert a.prompt == list(range(1, 9)) + [99]


# ---------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------
def test_engine_greedy_parity_and_bounded_compiles(gpt_mini):
    """Greedy decoding through the engine (paged cache, chunked
    prefill, continuous batching, any packing) is token-for-token
    identical to sequential per-request dense-cache generation, and the
    whole mixed workload runs through ONE compiled unified step
    program — the pow2 bucket-compile family is gone."""
    prompts = _prompts((3, 7, 12, 5, 30, 9), seed=0)
    base = [_dense_generate(gpt_mini, p, max_new_tokens=6)
            for p in prompts]
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64, prefill_chunk=16)
    try:
        res = eng.generate(prompts, max_new_tokens=6)
        assert res == base
        s = eng.stats()
        assert s["step_compiles"] <= 2
        assert s["blocks_in_use"] == 0        # everything freed
        assert s["high_water"] > 0
    finally:
        eng.close()


def test_engine_shared_prefix_burst_hits_cache(gpt_mini):
    """A burst sharing one system prompt pays ~one prefill: every
    request after the first reuses the shared blocks (greedy output
    still exactly matches the dense path)."""
    rng = np.random.RandomState(11)
    shared = list(rng.randint(1, VOCAB, size=16))   # 4 full 4-blocks
    prompts = [shared + list(rng.randint(1, VOCAB, size=3 + i))
               for i in range(4)]
    base = [_dense_generate(gpt_mini, p, max_new_tokens=5)
            for p in prompts]
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           block_size=4, max_model_len=64,
                           prefill_chunk=16)
    try:
        res = eng.generate(prompts, max_new_tokens=5)
        assert res == base
        n = len(prompts)
        assert eng.cache._hit_tokens >= (n - 1) * len(shared)
        s = eng.stats()
        assert s["prefix_hit_rate"] > 0.5
        assert s["step_compiles"] <= 2
    finally:
        eng.close()


def test_engine_greedy_preemption_invariant(gpt_mini):
    """Regression: a decode round aborted by preemption (next action
    flips to the victim's re-prefill) must roll back the KV slots it
    reserved for the surviving rows — a leak silently advances their
    context past the real tokens and they attend over unwritten
    slots.  Tiny prompts admit together under the admission
    watermark; DECODE GROWTH (3 rows x ~24 tokens vs 8 blocks of 4)
    then overflows the pool and forces preemption."""
    prompts = _prompts((2, 3, 4, 3), seed=3)
    ref_eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=1,
                               max_model_len=64)
    try:
        ref = [ref_eng.generate([p], max_new_tokens=20)[0]
               for p in prompts]
    finally:
        ref_eng.close()
    eng = GenerationEngine(gpt_mini, num_blocks=8, block_size=4,
                           max_batch=3, max_model_len=64)
    try:
        ids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        got = [eng.result(i) for i in ids]
        preempted = sum(eng._results[i].preemptions for i in ids)
        assert preempted > 0, "pool was sized to force preemption"
        assert got == ref
        # every non-preempted survivor ran with a clean context
        assert eng.stats()["blocks_in_use"] == 0
    finally:
        eng.close()


def test_engine_sampling_schedule_invariant(gpt_mini):
    """Seeded sampling keys on (request seed, absolute position), so a
    preempted, repacked, tiny-pool run draws the same tokens as an
    unconstrained sequential run.  Sized like the greedy preemption
    test: decode growth, not admission pressure, overflows the pool."""
    prompts = _prompts((2, 3, 4, 2, 3, 4), seed=1)
    kw = dict(max_new_tokens=20, do_sample=True, top_k=20, top_p=0.9,
              temperature=0.8)
    ref_eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=1,
                               max_model_len=64)
    try:
        ref = [ref_eng.generate([p], seed=100 + i, **kw)[0]
               for i, p in enumerate(prompts)]
    finally:
        ref_eng.close()

    eng = GenerationEngine(gpt_mini, num_blocks=8, block_size=4,
                           max_batch=3, max_model_len=64)
    try:
        ids = [eng.add_request(p, seed=100 + i, **kw)
               for i, p in enumerate(prompts)]
        while eng.has_unfinished():
            eng.step()
        res = [eng.result(i) for i in ids]
        preempted = sum(eng._results[i].preemptions for i in ids)
        assert preempted > 0, "pool was sized to force preemption"
        assert res == ref
    finally:
        eng.close()


def test_engine_eos_and_step_results(gpt_mini):
    prompts = _prompts((12,), seed=0)
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=2,
                           max_model_len=64)
    try:
        full = eng.generate(prompts, max_new_tokens=8)[0]
    finally:
        eng.close()
    L = len(prompts[0])
    eos = full[L + 3]
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=2,
                           max_model_len=64)
    try:
        eng.add_request(prompts[0], max_new_tokens=8, eos_token_id=eos,
                        request_id="r")
        finished = []
        while eng.has_unfinished():
            finished += eng.step()
        assert [r.id for r in finished] == ["r"]
        out = eng.result("r")
        assert out == full[:full.index(eos, L) + 1]
        assert out[-1] == eos and len(out) < len(full)
    finally:
        eng.close()


def test_engine_rejects_bad_requests(gpt_mini):
    eng = GenerationEngine(gpt_mini, num_blocks=16, max_batch=2,
                           max_model_len=32)
    try:
        with pytest.raises(ValueError):
            eng.add_request([])
        with pytest.raises(ValueError):
            eng.add_request(list(range(1, 40)))   # >= max_model_len
    finally:
        eng.close()


# ---------------------------------------------------------------------
# sampling ops
# ---------------------------------------------------------------------
@pytest.mark.parametrize("columns", [None, 2])
def test_ragged_sample_next_greedy_matches_argmax(columns):
    """`_ragged_sample_impl`, greedy: the argmax of the flat row each
    sequence (1-D ``last_index``) or each of its columns (``[S, C]``,
    the speculative verify) names."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving.engine import _ragged_sample_impl
    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(1, 12, 11).astype(np.float32))
    last = np.array([3, 0, 10], np.int32)
    if columns:
        last = np.stack([last, last + 1], axis=1)
    z = np.asarray(logits)[0]
    want = z[last].argmax(-1)
    got = _ragged_sample_impl(
        logits, jnp.asarray(last), jnp.zeros(3, jnp.int32),
        jnp.zeros(last.shape, jnp.int32),
        jnp.zeros(3, bool), jnp.zeros(3, jnp.int32),
        jnp.ones(3, jnp.float32), jnp.ones(3, jnp.float32))
    assert got.shape == last.shape
    assert np.asarray(got).tolist() == want.tolist()


def _sampler_case(mix, columns):
    """Logits and per-row controls of one sampler call: ``mix`` picks
    which rows sample, ``columns`` the ``[S, C]`` form of the index."""
    import jax.numpy as jnp
    vocab, rows, flat = 301, 5, 24
    rng = np.random.RandomState(11)
    logits = jnp.asarray(3.0 * rng.randn(1, flat, vocab).astype(np.float32))
    last = rng.choice(flat - 2, size=rows, replace=False).astype(np.int32)
    pos = np.arange(7, 7 + rows, dtype=np.int64)
    if columns:
        last = np.stack([last + j for j in range(columns)], axis=1)
        pos = np.stack([pos + j for j in range(columns)], axis=1)
    do_sample = {"greedy": np.zeros(rows, bool),
                 "sampling": np.ones(rows, bool),
                 "mixed": np.arange(rows) % 2 == 1,
                 "temperature0": np.ones(rows, bool)}[mix]
    temp = np.zeros(rows, np.float32) if mix == "temperature0" \
        else np.linspace(0.6, 1.4, rows).astype(np.float32)
    return (logits, jnp.asarray(last),
            jnp.asarray(np.arange(100, 100 + rows, dtype=np.int32)),
            jnp.asarray(pos), jnp.asarray(do_sample),
            jnp.asarray(np.array([0, 5, 40, 1, 0], np.int32)),      # top_k
            jnp.asarray(np.array([1.0, 0.9, 0.5, 1.0, 0.3], np.float32)),
            jnp.asarray(temp))


def _primitives(jaxpr):
    """Names of every primitive under ``jaxpr``, sub-jaxprs included."""
    import jax
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("columns", [None, 3])
def test_ragged_sample_greedy_branch_has_no_filter(columns):
    """One ``cond`` in the sampler: the branch a step without a sampling
    row takes holds the argmax and none of the filter or the draw; the
    gather of the read rows stays outside it."""
    import jax
    from paddle_tpu.inference.serving.engine import _ragged_sample_impl
    jaxpr = jax.make_jaxpr(_ragged_sample_impl)(
        *_sampler_case("mixed", columns)).jaxpr
    assert list(_primitives(jaxpr)).count("cond") == 1
    (cond,) = (e for e in jaxpr.eqns if e.primitive.name == "cond")
    greedy, sampling = (list(_primitives(b.jaxpr))
                        for b in cond.params["branches"])
    filter_ops = ("sort", "cumsum", "scatter", "random_bits")
    assert "argmax" in greedy
    assert not [n for n in greedy
                if n.startswith(filter_ops) or n == "gather"]
    for op in filter_ops:
        assert any(n.startswith(op) for n in sampling), op
    outside = [e.primitive.name for e in jaxpr.eqns]
    assert "gather" in _primitives(jaxpr)
    assert not [n for n in outside if n.startswith(filter_ops)]


@pytest.mark.parametrize("columns", [None, 3])
@pytest.mark.parametrize("mix", ["greedy", "sampling", "mixed",
                                 "temperature0"])
def test_ragged_sample_gate_matches_ungated(mix, columns):
    """Tokens of the gated sampler are those of `_filter_and_draw` on
    the same rows, for every mix of greedy and sampling rows."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving.engine import (_filter_and_draw,
                                                     _ragged_sample_impl)
    logits, last, seeds, pos, do_sample, top_k, top_p, temp = case = \
        _sampler_case(mix, columns)
    C = columns or 1
    rep = lambda a: jnp.repeat(a, C, axis=0)  # noqa: E731
    want = _filter_and_draw(
        logits[0, last.reshape(-1)].astype(jnp.float32), rep(seeds),
        pos.reshape(-1), rep(do_sample), rep(top_k), rep(top_p),
        rep(temp)).reshape(last.shape)
    got = _ragged_sample_impl(*case)
    assert got.dtype == want.dtype and got.shape == last.shape
    assert np.asarray(got).tolist() == np.asarray(want).tolist()
    greedy = np.asarray(logits)[0][np.asarray(last)].argmax(-1)
    differs = (np.asarray(got) != greedy).any()
    assert differs == (mix in ("sampling", "mixed"))


def test_engine_sampler_counters(gpt_mini):
    """`sampler_steps` counts the steps packed, `sampler_filter_steps`
    those that held a live sampling row: none of a greedy run, and of a
    mixed run only the steps before the last sampling row finished."""
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64)
    held = []
    pack = eng._control_tensors

    def recording(reqs, n):
        held.append(any(r is not None and r.do_sample
                        and r.temperature > 0 for r in reqs))
        return pack(reqs, n)

    eng._control_tensors = recording
    try:
        prompts = _prompts((5, 9, 3), seed=3)
        eng.generate(prompts, max_new_tokens=6)
        s = eng.stats()
        assert s["sampler_steps"] == len(held) > 0
        assert s["sampler_filter_steps"] == 0 and not any(held)

        del held[:]
        eng.add_request(prompts[0], max_new_tokens=14)
        eng.add_request(prompts[1], max_new_tokens=4, do_sample=True,
                        top_k=10, temperature=0.7, seed=5)
        eng.add_request(prompts[2], max_new_tokens=14, do_sample=True,
                        temperature=0.0)
        while eng.has_unfinished():
            eng.step()
        d = {k: eng.stats()[k] - s[k]
             for k in ("sampler_steps", "sampler_filter_steps")}
        assert d["sampler_steps"] == len(held)
        assert 0 < d["sampler_filter_steps"] == sum(held) < len(held)
    finally:
        eng.close()


def test_top_p_sampling_deterministic_under_seed():
    from paddle_tpu.incubate.nn.functional import top_p_sampling
    rng = np.random.RandomState(9)
    x = paddle.to_tensor(
        np.abs(rng.randn(4, 50)).astype(np.float32))
    ps = paddle.to_tensor(np.full((4,), 0.8, np.float32))
    s1, i1 = top_p_sampling(x, ps, seed=123)
    s2, i2 = top_p_sampling(x, ps, seed=123)
    assert np.array_equal(np.asarray(i1._value), np.asarray(i2._value))
    assert np.allclose(np.asarray(s1._value), np.asarray(s2._value))
    assert i1.shape == [4, 1] and s1.shape == [4, 1]
    # drawn ids are inside each row's nucleus (prob above the cut)
    p = np.asarray(x._value)
    p = p / p.sum(-1, keepdims=True)
    for b in range(4):
        order = np.argsort(-p[b])
        cum = np.cumsum(p[b][order])
        nucleus = set(order[(cum - p[b][order]) < 0.8].tolist())
        assert int(np.asarray(i1._value)[b, 0]) in nucleus
    # generator-threaded path (seed=-1) advances global state
    paddle.seed(77)
    _, a = top_p_sampling(x, ps)
    _, b = top_p_sampling(x, ps)
    paddle.seed(77)
    _, a2 = top_p_sampling(x, ps)
    assert np.array_equal(np.asarray(a._value), np.asarray(a2._value))


def test_top_p_sampling_threshold():
    from paddle_tpu.incubate.nn.functional import top_p_sampling
    x = paddle.to_tensor(np.array(
        [[0.5, 0.3, 0.15, 0.05]], np.float32))
    ps = paddle.to_tensor(np.array([1.0], np.float32))
    seen = set()
    for seed in range(20):
        _, ids = top_p_sampling(x, ps, threshold=0.2, seed=seed)
        seen.add(int(np.asarray(ids._value)[0, 0]))
    assert seen <= {0, 1}      # candidates below the threshold dropped


# ---------------------------------------------------------------------
# scheduler policy hooks
# ---------------------------------------------------------------------
def test_victim_policy_hook_overrides_default():
    """Satellite: preemption-victim selection is a pluggable policy;
    youngest-first is merely the default implementation."""
    c = PagedKVCache(num_layers=1, num_heads=1, head_dim=8,
                     block_size=4, num_blocks=8, max_model_len=32,
                     register=False)

    class OldestFirst(VictimPolicy):
        def select_victim(self, candidates):
            return min(candidates, key=lambda r: r.arrival)

    s = ContinuousBatchingScheduler(c, max_batch=2, prefill_chunk=8,
                                    victim_policy=OldestFirst())
    a, b = Request("a", [1] * 4), Request("b", [2] * 4)
    for r in (a, b):
        s.submit(r)
        act, req = s.next_action()
        assert act == "admit"
        s.begin_prefill(req)
        req.num_computed = len(req.prompt)
    assert s.select_victim() is a              # policy, not youngest
    assert s.preempt_youngest() is a           # alias routes through it
    assert s.select_victim(exclude=(a,)) is b


# ---------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------
def test_ngram_proposer_lookup():
    p = NgramProposer(n=3)
    h = [1, 2, 3, 9, 1, 2, 3]
    # trailing [1,2,3] last occurred at the start; propose what followed
    assert p._propose(h, 4) == [9, 1, 2, 3]
    assert p._propose(h, 2) == [9, 1]          # kmax caps the proposal
    assert p._propose([5, 6, 7], 4) == []      # no earlier occurrence
    assert p._propose(h, 0) == []


def test_engine_spec_greedy_parity_ngram(gpt_mini):
    """Tentpole: greedy speculative output is BIT-IDENTICAL to the
    non-speculative engine (same model, same prompts), drafts actually
    flow, and the verify path adds no compiled programs."""
    prompts = _prompts((3, 7, 12, 5, 9), seed=0)
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64, prefill_chunk=16)
    try:
        base = eng.generate(prompts, max_new_tokens=10)
    finally:
        eng.close()
    spec = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                            max_model_len=64, prefill_chunk=16,
                            speculative=SpeculativeConfig(k=3,
                                                          method="ngram"))
    try:
        got = spec.generate(prompts, max_new_tokens=10)
        s = spec.stats()
        assert got == base
        assert s["tokens_drafted"] > 0
        assert s["step_compiles"] <= 3
        assert s["blocks_in_use"] == 0
    finally:
        spec.close()


def test_engine_spec_greedy_parity_draft_model(gpt_mini):
    """Draft-model speculation (self-draft -> near-100% accept): still
    bit-identical, accept counters run, and target + draft stay within
    the <= 3 compiled-programs budget."""
    prompts = _prompts((3, 7, 12, 5), seed=2)
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64, prefill_chunk=16)
    try:
        base = eng.generate(prompts, max_new_tokens=10)
    finally:
        eng.close()
    # the bundled model drafts for itself: every greedy draft matches
    spec = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                            max_model_len=64, prefill_chunk=16,
                            speculative=gpt_mini)
    try:
        got = spec.generate(prompts, max_new_tokens=10)
        s = spec.stats()
        assert got == base
        assert s["tokens_drafted"] > 0
        assert s["tokens_accepted"] == s["tokens_drafted"]
        assert s["spec_accept_rate"] == 1.0
        assert s["step_compiles"] <= 3
        # the draft pool is a separate line item and frees cleanly
        assert spec.proposer.worker.cache.blocks_in_use == 0
    finally:
        spec.close()


def test_engine_spec_full_rejection_rolls_back(gpt_mini):
    """Satellite: a proposer that is ALWAYS wrong forces the full
    rejection path every step — output must still be identical and the
    paged cache must roll back cleanly (no leaked blocks)."""
    prompts = _prompts((3, 7, 5), seed=4)
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64)
    try:
        base = eng.generate(prompts, max_new_tokens=8)
    finally:
        eng.close()

    class AlwaysWrong(NgramProposer):
        def propose_batch(self, items):
            return {req.id: [(int(h[-1]) + 1) % VOCAB] * kmax
                    for req, h, kmax in items}

    spec = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                            max_model_len=64,
                            speculative=SpeculativeConfig(k=3,
                                                          method="ngram"))
    spec.proposer = AlwaysWrong()
    try:
        got = spec.generate(prompts, max_new_tokens=8)
        s = spec.stats()
        assert got == base
        assert s["tokens_drafted"] > 0 and s["tokens_accepted"] == 0
        assert s["blocks_in_use"] == 0        # every reject rolled back
    finally:
        spec.close()


def test_engine_spec_preemption_invariant(gpt_mini):
    """Satellite: preemption mid-speculation — a tiny pool forces
    evictions while rows carry multi-token verify segments; the victim
    re-enters with prefix credit and output matches the unconstrained
    engine exactly."""
    prompts = _prompts((2, 3, 4, 3), seed=3)
    ref = GenerationEngine(gpt_mini, num_blocks=64, max_batch=1,
                           max_model_len=64)
    try:
        base = [ref.generate([p], max_new_tokens=20)[0] for p in prompts]
    finally:
        ref.close()
    eng = GenerationEngine(gpt_mini, num_blocks=8, block_size=4,
                           max_batch=3, max_model_len=64,
                           speculative=SpeculativeConfig(k=3,
                                                         method="ngram"))
    try:
        ids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        got = [eng.result(i) for i in ids]
        preempted = sum(eng._results[i].preemptions for i in ids)
        assert preempted > 0, "pool was sized to force preemption"
        assert got == base
        assert eng.stats()["blocks_in_use"] == 0
    finally:
        eng.close()


def test_engine_spec_sampling_parity(gpt_mini):
    """Seeded sampling keys on absolute position, so acceptance-by-
    token-matching preserves the exact sampled sequence too."""
    prompts = _prompts((3, 8, 5), seed=6)
    kw = dict(max_new_tokens=10, do_sample=True, top_k=20,
              temperature=0.9)
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64)
    try:
        base = eng.generate(prompts, seed=42, **kw)
    finally:
        eng.close()
    spec = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                            max_model_len=64, speculative=gpt_mini)
    try:
        assert spec.generate(prompts, seed=42, **kw) == base
    finally:
        spec.close()


def test_engine_spec_env_knob(gpt_mini, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SPEC_K", "2")
    eng = GenerationEngine(gpt_mini, num_blocks=32, max_batch=2,
                           max_model_len=64)
    try:
        assert eng.spec is not None and eng.spec.k == 2
        assert eng.spec_cols == 3 and eng.proposer is not None
    finally:
        eng.close()
    monkeypatch.setenv("PADDLE_TPU_SPEC_K", "0")
    eng2 = GenerationEngine(gpt_mini, num_blocks=32, max_batch=2,
                            max_model_len=64)
    try:
        assert eng2.spec is None and eng2.proposer is None
    finally:
        eng2.close()


# ---------------------------------------------------------------------
# streaming delivery
# ---------------------------------------------------------------------
def test_token_stream_bounded_drop_oldest():
    st = TokenStream("r", maxlen=3)
    for i in range(5):
        st.put(100 + i, i)
    assert st.dropped == 2 and len(st) == 3
    evs = st.drain()
    assert [e.token for e in evs] == [102, 103, 104]
    assert [e.index for e in evs] == [2, 3, 4]   # gap marks the drop
    assert st.drain() == [] and not st.done
    st.close()
    (term,) = st.drain()
    assert term.finished and term.token is None
    assert st.done
    st.put(9, 9)                                # closed: ignored
    assert st.drain() == []


def test_engine_generate_stream_yields_tokens_in_order(gpt_mini):
    """Satellite: stream=True yields every generated token as a
    StreamEvent, per request in commit order, matching the non-stream
    output exactly (speculative engine: tokens appear as accepted)."""
    prompts = _prompts((3, 7, 5), seed=8)
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64)
    try:
        base = eng.generate(prompts, max_new_tokens=8)
    finally:
        eng.close()
    spec = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                            max_model_len=64,
                            speculative=SpeculativeConfig(k=3,
                                                          method="ngram"))
    try:
        ids = {}
        toks = {}
        finished = set()
        for ev in spec.generate(prompts, max_new_tokens=8, stream=True):
            assert isinstance(ev, StreamEvent)
            if ev.token is not None:
                toks.setdefault(ev.request_id, []).append(ev.token)
                assert ev.index == len(toks[ev.request_id]) - 1
            if ev.finished:
                finished.add(ev.request_id)
        ids = sorted(toks, key=lambda r: int(r[3:]))   # req0, req1, ...
        assert [toks[i] for i in ids] == \
            [base[j][len(prompts[j]):] for j in range(len(prompts))]
        assert finished == set(ids)
        assert spec._streams == {}            # streams cleaned up
    finally:
        spec.close()
