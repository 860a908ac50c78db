"""Tiered KV cache: HBM → host-RAM spill/promote (ISSUE 14 tentpole a).

Covers the pool-level tiering contract directly on PagedKVCache:

  * a refcount-0 indexed block evicted from the HBM LRU park lands in
    the host ring and promotes back BIT-IDENTICAL (f32 and int8 — the
    int8 path must carry its per-slot dequant scale tables along);
  * host-resident chain links count as prefix hits
    (``prefix_match_tokens`` / ``host_hit_rate``) and allocation
    charges them a fresh physical block;
  * the host tier is a named memory-guard line item that is NOT part
    of the device budget, and ``stats()`` splits hbm/host counts;
  * the truncate-regrow stale guard: a sequence cut mid-block and
    regrown with different tokens can never hand its old chain hash —
    in either tier — to a later allocation (the bugfix rider);
  * the serving_smoke tiering scenario (tiny HBM pool, alternating
    shared prefixes → host hit rate > 0 within the compile budget)
    runs green, gating the end-to-end story in tier-1.
"""
import importlib.util
import os
import sys
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import memory
from paddle_tpu.inference.serving import (GenerationEngine, PagedKVCache,
                                          kv_blocks_scatter)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tiering_env(monkeypatch):
    for var in ("PADDLE_TPU_HBM_BUDGET", "PADDLE_TPU_MEMORY_GUARD",
                "PADDLE_TPU_KV_BLOCK_SIZE", "PADDLE_TPU_PREFIX_CACHE",
                "PADDLE_TPU_KV_TIERING", "PADDLE_TPU_KV_HOST_BUDGET"):
        monkeypatch.delenv(var, raising=False)
    yield


def _cache(dtype="float32", num_blocks=8, **kw):
    return PagedKVCache(2, 2, 4, dtype=dtype, block_size=4,
                        num_blocks=num_blocks, max_model_len=64,
                        register=False, tiering=True, **kw)


def _pattern(cache, n_blocks, seed):
    """Deterministic per-layer K/V (and scale) payloads for n blocks."""
    rng = np.random.RandomState(seed)
    # blocks as they lie in the pool: [bs, H * D]
    shape = (n_blocks, cache.block_size,
             cache.num_heads * cache.head_dim)
    if cache.quantized:
        k = [rng.randint(-127, 128, size=shape).astype(np.int8)
             for _ in range(cache.num_layers)]
        v = [rng.randint(-127, 128, size=shape).astype(np.int8)
             for _ in range(cache.num_layers)]
        sshape = (n_blocks, cache.block_size, cache.scale_lanes)
        ks = [rng.rand(*sshape).astype(np.float32)
              for _ in range(cache.num_layers)]
        vs = [rng.rand(*sshape).astype(np.float32)
              for _ in range(cache.num_layers)]
        return k, v, ks, vs
    k = [rng.standard_normal(shape).astype(np.float32)
         for _ in range(cache.num_layers)]
    v = [rng.standard_normal(shape).astype(np.float32)
         for _ in range(cache.num_layers)]
    return k, v, None, None


def _write(cache, seq_id, seed, start=0):
    """Fill a sequence's blocks from ``start`` on (an engine never
    rewrites blocks below the cached prefix)."""
    blocks = list(cache._tables[seq_id])[start:]
    k, v, ks, vs = _pattern(cache, len(blocks), seed)
    kv_blocks_scatter(cache, blocks, k, v, ks, vs)
    return (k, v, ks, vs)


def _read_blocks(cache, blocks):
    idx = np.asarray(blocks, np.int32)
    k = [np.asarray(kp._value)[idx] for kp, _ in cache._pools]
    v = [np.asarray(vp._value)[idx] for _, vp in cache._pools]
    ks = [np.asarray(s._value)[idx] for s, _ in cache._scales]
    vs = [np.asarray(s._value)[idx] for _, s in cache._scales]
    return k, v, ks, vs


def _tokens(seed, n=16):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(1, 96, size=n)]


def _spill_roundtrip(dtype):
    cache = _cache(dtype=dtype)
    ta, tb, td = _tokens(1), _tokens(2), _tokens(3)
    assert cache.host is not None and cache.host.num_slots >= 4

    assert cache.allocate("a", 16, tokens=ta)
    want = _write(cache, "a", seed=11)
    cache.free("a", tokens=ta)          # 4 blocks park, indexed

    # two fresh 4-block sequences exhaust the 8-block pool: taking the
    # last blocks evicts "a"'s parked chain into the host ring
    assert cache.allocate("b", 16, tokens=tb)
    assert cache.allocate("d", 16, tokens=td)
    assert cache.host_spills == 4
    assert cache.host.used_slots == 4
    cache.free("b")                      # no tokens: nothing indexed,
    cache.free("d")                      # nothing to spill later

    # re-allocating "a"'s prompt promotes the host chain back (3 of 4
    # blocks: the leave-one-to-compute cap) bit-identically
    assert cache.allocate("a2", 16, tokens=ta)
    assert cache.host_promotes == 3
    assert cache.cached_prefix_len("a2") == 12
    assert cache.host_hit_rate > 0
    got_k, got_v, got_ks, got_vs = _read_blocks(
        cache, cache._tables["a2"][:3])
    for layer in range(cache.num_layers):
        np.testing.assert_array_equal(got_k[layer],
                                      want[0][layer][:3])
        np.testing.assert_array_equal(got_v[layer],
                                      want[1][layer][:3])
        if cache.quantized:
            np.testing.assert_array_equal(got_ks[layer],
                                          want[2][layer][:3])
            np.testing.assert_array_equal(got_vs[layer],
                                          want[3][layer][:3])
    s = cache.stats()
    assert s["host_spills"] == 4 and s["host_promotes"] == 3
    assert s["hbm_blocks"] == cache.num_blocks - 1
    assert s["host_blocks"] == cache.host.num_slots


def test_spill_evict_promote_bit_identical_f32():
    _spill_roundtrip("float32")


def test_spill_evict_promote_bit_identical_int8():
    _spill_roundtrip("int8")


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_exported_block_serves_the_same_tokens(dtype):
    """A sequence written by the step's scatter, exported block by
    block with `kv_blocks_gather` and imported into other blocks of
    another pool with `kv_blocks_scatter`: a block travels as it lies
    (``[block_size, H * D]``, a token a row), and attention over the
    second pool gives every decode and prefill row what it gave over
    the first."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import kv_blocks_gather
    from paddle_tpu.inference.serving.attention import (
        _kv_scatter_impl, _kv_scatter_quant_impl, _ragged_ref)
    from paddle_tpu.ops.pallas_ragged import ragged_segments
    rng = np.random.RandomState(4)
    H, D, bs, n = 2, 4, 4, 14
    a, b = _cache(dtype=dtype), _cache(dtype=dtype, num_blocks=12)
    assert a.allocate("s", n)
    assert b.allocate("other", 9) and b.allocate("s", n)
    assert a._tables["s"] != b._tables["s"]
    new = [jnp.asarray(rng.randn(1, n, H, D), jnp.float32) for _ in "kv"]
    slots = jnp.asarray(a.slot_mapping("s", 0, n), jnp.int32)
    for layer in range(a.num_layers):
        pools = [t._value for t in a.layer_pools(layer)]
        if a.quantized:
            scales = [t._value for t in a.layer_scales(layer)]
            written = _kv_scatter_quant_impl(*pools, *scales, *new, slots)
        else:
            written = _kv_scatter_impl(*pools, *new, slots)
        for t, value in zip(a.layer_pools(layer)
                            + (a.layer_scales(layer) or ()), written):
            t._inplace_update(value)
    # token 5 of the sequence is row 5 % bs of its block, heads side by
    # side, in the pool and on the host alike
    k, v, ks, vs = kv_blocks_gather(a, a._tables["s"])
    assert k[0].shape == (len(a._tables["s"]), bs, H * D)
    if not a.quantized:
        np.testing.assert_array_equal(
            np.asarray(k[0])[5 // bs, 5 % bs],
            np.asarray(new[0])[0, 5].reshape(H * D))
    host = [part and [np.asarray(x) for x in part]
            for part in (k, v, ks, vs)]
    kv_blocks_scatter(b, b._tables["s"], *host)

    # the last three tokens as a prefill chunk, then one decode row
    sid, qs, qv, _, rows = ragged_segments([3], [n], 8)
    q = jnp.asarray(rng.randn(rows, H, D), jnp.float32)

    def attend(cache):
        kp, vp = (t._value for t in cache.layer_pools(0))
        sc = cache.layer_scales(0)
        return np.asarray(_ragged_ref(
            q, kp, vp, jnp.asarray([cache.block_table("s")], jnp.int32),
            jnp.asarray([n], jnp.int32), jnp.asarray(sid), jnp.asarray(qs),
            jnp.asarray(qv), 8, 0.5,
            k_scales=sc and sc[0]._value, v_scales=sc and sc[1]._value))
    assert np.abs(attend(a)[:3]).max() > 0.05
    np.testing.assert_array_equal(attend(a), attend(b))


def test_host_tier_is_host_line_item_not_device_charge():
    cache = PagedKVCache(2, 2, 4, dtype="float32", block_size=4,
                         num_blocks=8, max_model_len=64,
                         resident_name="kv tier test", tiering=True)
    try:
        device = dict((n, b) for n, b, _ in memory.resident_items())
        host = dict(memory.host_resident_items())
        assert "kv tier test" in device
        assert "kv tier test host tier" in host
        assert "kv tier test host tier" not in device
        assert host["kv tier test host tier"] == cache.host.nbytes
    finally:
        cache.close()
    assert "kv tier test" not in dict(
        (n, b) for n, b, _ in memory.resident_items())
    assert "kv tier test host tier" not in dict(
        memory.host_resident_items())


def test_no_budget_no_tier():
    cache = PagedKVCache(2, 2, 4, dtype="float32", block_size=4,
                         num_blocks=8, max_model_len=64, register=False,
                         tiering=False)
    assert cache.host is None
    ta = _tokens(1)
    assert cache.allocate("a", 16, tokens=ta)
    cache.free("a", tokens=ta)
    for sid, seed in (("b", 2), ("d", 3)):
        assert cache.allocate(sid, 16, tokens=_tokens(seed))
    assert cache.host_spills == 0
    assert cache.stats()["host_blocks"] == 0


def test_truncate_regrow_never_promotes_stale_host_block():
    """The bugfix rider: cut a promoted sequence mid-block, regrow it
    with different tokens, and verify the OLD chain hash is gone from
    both tiers — a later allocation with the original prompt must stop
    at the cut, never claim the rewritten bytes."""
    cache = _cache()
    ta = _tokens(1)
    assert cache.allocate("a", 16, tokens=ta)
    want = _write(cache, "a", seed=11)
    cache.free("a", tokens=ta)
    for sid, seed in (("b", 2), ("d", 3)):
        assert cache.allocate(sid, 16, tokens=_tokens(seed))
    assert cache.host_spills == 4
    cache.free("b")
    cache.free("d")

    assert cache.allocate("s", 16, tokens=ta)
    assert cache.host_promotes == 3
    gen0 = cache._commit_gen
    old_h2 = cache._hash_of.get(cache._tables["s"][1])
    assert old_h2 is not None

    # cut INTO block 2 (6 = 1.5 blocks) and regrow with new tokens
    cache.truncate("s", 6)
    assert cache._commit_gen == gen0 + 1
    assert old_h2 not in cache._by_hash
    assert old_h2 not in cache._host_of
    assert cache.append("s", 10)
    _write(cache, "s", seed=99, start=1)  # regrown bytes differ
    regrown = ta[:6] + _tokens(5)[:10]
    cache.free("s", tokens=regrown)

    # the ORIGINAL prompt may reuse block 1 only: the old block-2 hash
    # must be gone from both tiers, so the chain stops at the cut
    assert cache.allocate("w", 16, tokens=ta)
    assert cache.cached_prefix_len("w") <= 4
    got_k, _, _, _ = _read_blocks(cache, cache._tables["w"][:1])
    np.testing.assert_array_equal(got_k[0], want[0][0][:1])
    # and the regrown chain is served under its NEW hash, new bytes
    assert cache.prefix_match_tokens(regrown) >= 8


def test_prefix_match_counts_host_links():
    cache = _cache()
    ta = _tokens(1)
    assert cache.allocate("a", 16, tokens=ta)
    _write(cache, "a", seed=4)
    cache.free("a", tokens=ta)
    assert cache.prefix_match_tokens(ta) == 16   # all HBM-parked
    for sid, seed in (("b", 2), ("d", 3)):
        assert cache.allocate(sid, 16, tokens=_tokens(seed))
    assert cache.host_spills == 4
    # the chain now lives in the host ring; the DP/disagg router must
    # still see this pool as the warm target
    assert cache.prefix_match_tokens(ta) == 16


def test_engine_tiering_parity_and_host_hits():
    """Engine-level: a tiny HBM pool alternating two shared prefixes
    serves from the host tier with output identical to a roomy run."""
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
    paddle.seed(7)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(5)
    p1 = list(rng.randint(1, 97, size=16))
    p2 = list(rng.randint(1, 97, size=16))
    prompts = [(p1 if i % 2 == 0 else p2)
               + list(rng.randint(1, 97, size=3)) for i in range(6)]

    roomy = GenerationEngine(model, num_blocks=128, max_batch=1,
                             block_size=4, max_model_len=64)
    try:
        ref = [roomy.generate([p], max_new_tokens=6)[0] for p in prompts]
    finally:
        roomy.close()
    eng = GenerationEngine(model, num_blocks=8, block_size=4,
                           max_batch=1, max_model_len=64,
                           kv_tiering=True)
    try:
        got = [eng.generate([p], max_new_tokens=6)[0] for p in prompts]
        s = eng.stats()
        assert got == ref
        assert s["host_spills"] > 0 and s["host_promotes"] > 0
        assert s["host_hit_rate"] > 0
        assert s["blocks_in_use"] == 0
    finally:
        eng.close()


def test_serving_smoke_tiering_scenario(monkeypatch):
    """Gate the end-to-end smoke scenario (tiny HBM budget, shared
    prefix burst → host hit rate > 0, within the compile budget) in
    tier-1."""
    from paddle_tpu.observability import timeline
    monkeypatch.setenv("PADDLE_TPU_OBS", "1")
    monkeypatch.setattr(timeline, "_enabled", None)
    spec = importlib.util.spec_from_file_location(
        "serving_smoke", os.path.join(ROOT, "scripts",
                                      "serving_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    args = types.SimpleNamespace(seed=7, requests=16)
    mod._tiering(args)


def test_spill_dma_failure_degrades_to_miss():
    """kv.dma_fail during spill: after the bounded retry the evicted
    block is simply not host-cached — a later request is a miss, never
    a crash — and the reserved host slot is returned."""
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.fault_tolerance import FaultPlan, inject

    cache = _cache()
    ta, tb, td = _tokens(1), _tokens(2), _tokens(3)
    assert cache.allocate("a", 16, tokens=ta)
    _write(cache, "a", seed=11)
    cache.free("a", tokens=ta)          # 4 blocks park, indexed

    obs.enable(True)
    try:
        c0 = obs.get_registry().counter("serving.kv_dma_fail").value
        # 4 spill DMAs x (1 try + 1 retry) all dropped
        fp = FaultPlan().add("kv.dma_fail", "drop", count=8)
        with inject(fp):
            assert cache.allocate("b", 16, tokens=tb)
            assert cache.allocate("d", 16, tokens=td)
        assert cache.host_spills == 0
        assert cache.host.used_slots == 0   # reserved slots given back
        assert obs.get_registry().counter(
            "serving.kv_dma_fail").value - c0 == 4
        instants = [e for e in obs.get_timeline().events()
                    if e.name == "kv.dma_fail"]
        assert instants and instants[-1].attrs["dir"] == "spill"
    finally:
        obs.disable()

    cache.free("b")
    cache.free("d")
    # the evicted chain never made it to host: plain miss on reuse
    assert cache.allocate("a2", 16, tokens=ta)
    assert cache.cached_prefix_len("a2") == 0
    assert cache.host_promotes == 0


def test_promote_dma_failure_degrades_to_shorter_prefix():
    """kv.dma_fail during promote: the suspect host entry is dropped and
    the allocate re-walk transparently sees a shorter cached prefix; the
    engine recomputes those tokens and never observes the failure."""
    from paddle_tpu.distributed.fault_tolerance import FaultPlan, inject

    cache = _cache()
    ta, tb, td = _tokens(1), _tokens(2), _tokens(3)
    assert cache.allocate("a", 16, tokens=ta)
    _write(cache, "a", seed=11)
    cache.free("a", tokens=ta)
    assert cache.allocate("b", 16, tokens=tb)
    assert cache.allocate("d", 16, tokens=td)
    assert cache.host_spills == 4
    cache.free("b")
    cache.free("d")
    host_used = cache.host.used_slots

    # the FIRST promote DMA dies (try + retry); the chain re-walk stops
    # at the dropped link, so the whole prefix degrades to a miss
    fp = FaultPlan().add("kv.dma_fail", "drop", count=2)
    with inject(fp):
        assert cache.allocate("a2", 16, tokens=ta)
    assert cache.cached_prefix_len("a2") == 0
    assert cache.host_promotes == 0
    assert cache.host.used_slots == host_used - 1  # bad entry dropped
    # the sequence's blocks are ordinary scratch: write/free still work
    _write(cache, "a2", seed=21)
    cache.free("a2")
    assert cache.allocate("e", 16, tokens=_tokens(5))
