"""Int8 quantized serving: dequant-fused matmul epilogue, int8 paged
KV cache with per-slot scales, lifecycle edges, and parity gates.

Numerics contract: the int8 variants add ZERO numeric drift over their
float counterparts — the kernel and the XLA fallback each produce
bit-identical output to themselves fed a pre-dequantized float pool,
and the int8 matmul fallback bit-matches the interpret-mode kernel
under jit.  Kernel-vs-fallback stays inside the float path's existing
1-ulp tolerance.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.ops.pallas_fused as pf
import paddle_tpu.ops.pallas_ragged as pr
from paddle_tpu.inference.serving import (DataParallelEngine,
                                          GenerationEngine)
from paddle_tpu.inference.serving.attention import (_ragged_ref,
                                                    kv_cache_scatter_quant)
from paddle_tpu.inference.serving.kv_cache import PagedKVCache
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.quantization import (convert_to_int8,
                                     greedy_match_ratio, logits_cosine,
                                     quantize_weight_int8)

pytestmark = pytest.mark.quant

VOCAB = 97


@pytest.fixture(autouse=True)
def _quant_env(monkeypatch):
    for var in ("PADDLE_TPU_HBM_BUDGET", "PADDLE_TPU_KV_BLOCK_SIZE",
                "PADDLE_TPU_KV_DTYPE", "PADDLE_TPU_WEIGHT_DTYPE",
                "PADDLE_TPU_PREFIX_CACHE"):
        monkeypatch.delenv(var, raising=False)
    yield


@pytest.fixture(scope="module")
def gpt_mini():
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32,
                    num_hidden_layers=2, num_attention_heads=4,
                    max_position_embeddings=64)
    paddle.seed(7)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, VOCAB, size=n)) for n in lengths]


# ---------------------------------------------------------------------
# int8 matmul epilogue: kernel/fallback parity + grads
# ---------------------------------------------------------------------
def _int8_linear_inputs(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = rng.normal(size=(k, n)).astype(np.float32)
    wq_t, s_t = quantize_weight_int8(w, axis=1)
    b = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    return x, jnp.asarray(wq_t.numpy()), jnp.asarray(s_t.numpy()), b


@pytest.mark.parametrize("shape", [(64, 128, 256), (33, 96, 200)])
def test_int8_matmul_fallback_bit_matches_kernel(shape):
    """The jitted XLA dequant fallback (post-dot scale, same op order)
    bit-matches the interpret-mode Pallas kernel, aligned or not."""
    m, k, n = shape
    x, wq, s, b = _int8_linear_inputs(m, k, n)

    def ref(x, wq, s, b):
        z = jax.lax.dot_general(
            x.astype(jnp.float32), wq.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        z = z * s.astype(jnp.float32) + b.astype(jnp.float32)
        return pf._act_f32(z, "gelu_tanh").astype(x.dtype)

    out_k = pf.fused_linear_act_int8(x, wq, s, b, "gelu_tanh")
    out_r = jax.jit(ref)(x, wq, s, b)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_int8_matmul_grads_match_dequant_autodiff():
    """dx/dscale/db from the custom vjp agree with autodiff through
    the explicitly dequantized float matmul."""
    x, wq, s, b = _int8_linear_inputs(32, 64, 128, seed=1)

    def fused(x, s, b):
        return pf.fused_linear_act_int8(x, wq, s, b, "gelu_tanh").sum()

    def dense(x, s, b):
        w = wq.astype(jnp.float32) * s[None, :]
        z = x @ w + b
        return pf._act_f32(z, "gelu_tanh").sum()

    g_f = jax.grad(fused, argnums=(0, 1, 2))(x, s, b)
    g_d = jax.grad(dense, argnums=(0, 1, 2))(x, s, b)
    for got, want in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_int8_matmul_block_plan_exports():
    plan = pf.matmul_epilogue_block_plan(512, 768, 3072,
                                         dtype=jnp.bfloat16,
                                         weight_dtype=jnp.int8)
    assert plan["weight_dtype"] == "int8"
    names = [op[0] for op in plan["operands"]]
    assert "scale" in names
    w = dict((op[0], op) for op in plan["operands"])["w"]
    assert np.dtype(w[3]).itemsize == 1


# ---------------------------------------------------------------------
# int8 ragged attention: zero added drift over the float path
# ---------------------------------------------------------------------
def _ragged_case(seed=0):
    rng = np.random.default_rng(seed)
    H, D, bs, W, S, NB = 4, 64, 16, 4, 3, 16
    bq = pr.ragged_q_block(jnp.float32)
    q = jnp.asarray(rng.normal(size=(3 * bq, H, D)).astype(np.float32))
    kp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, H * D)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(NB, bs, H * D)),
                     jnp.int8)
    lanes = pr.KV_SCALE_LANES
    ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(NB, bs, lanes))
                     .astype(np.float32))
    vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(NB, bs, lanes))
                     .astype(np.float32))
    bt = jnp.asarray(rng.integers(1, NB, size=(S, W)), jnp.int32)
    cl = jnp.asarray([37, 12, 50], jnp.int32)
    sid = jnp.asarray([0, 1, 2], jnp.int32)
    qs = jnp.asarray([30, 11, 40], jnp.int32)
    qv = jnp.asarray([7, 1, 8], jnp.int32)
    return bq, q, kp, vp, ks, vs, bt, cl, sid, qs, qv


def test_int8_ragged_kernel_bit_matches_float_kernel_on_dequant():
    bq, q, kp, vp, ks, vs, bt, cl, sid, qs, qv = _ragged_case()
    kf = kp.astype(jnp.float32) * ks[..., :1]
    vf = vp.astype(jnp.float32) * vs[..., :1]
    out_i8 = pr.ragged_paged_attention(q, kp, vp, bt, cl, sid, qs, qv,
                                       k_scales=ks, v_scales=vs)
    out_f = pr.ragged_paged_attention(q, kf, vf, bt, cl, sid, qs, qv)
    np.testing.assert_array_equal(np.asarray(out_i8), np.asarray(out_f))


def test_int8_ragged_fallback_bit_matches_float_fallback_on_dequant():
    bq, q, kp, vp, ks, vs, bt, cl, sid, qs, qv = _ragged_case(1)
    kf = kp.astype(jnp.float32) * ks[..., :1]
    vf = vp.astype(jnp.float32) * vs[..., :1]
    scale = float(q.shape[-1]) ** -0.5
    ref = jax.jit(functools.partial(_ragged_ref, block_q=bq,
                                    scale=scale))
    r_i8 = ref(q, kp, vp, bt, cl, sid, qs, qv,
               k_scales=ks, v_scales=vs)
    r_f = ref(q, kf, vf, bt, cl, sid, qs, qv)
    np.testing.assert_array_equal(np.asarray(r_i8), np.asarray(r_f))
    # kernel vs fallback stays inside the float path's tolerance
    out_k = pr.ragged_paged_attention(q, kp, vp, bt, cl, sid, qs, qv,
                                      k_scales=ks, v_scales=vs,
                                      scale=scale)
    np.testing.assert_allclose(np.asarray(r_i8), np.asarray(out_k),
                               atol=1e-5)


def test_int8_ragged_block_plan_exports_scales():
    plan = pr.ragged_block_plan(8, 64, 16, num_q_blocks=8,
                                num_blocks=64, kv_dtype=jnp.int8)
    assert plan["kv_dtype"] == "int8"
    names = [op[0] for op in plan["operands"]]
    assert "k_scales" in names and "v_scales" in names


def test_scatter_quant_deterministic_and_bounded():
    """Per-slot quantization is a pure function (failover replay needs
    bit-identity) with codes in [-127, 127] and bounded dequant
    error."""
    rng = np.random.default_rng(3)
    NB, H, bs, D, lanes = 4, 2, 4, 8, pr.KV_SCALE_LANES
    kp = jnp.zeros((NB, bs, H * D), jnp.int8)
    ks = jnp.zeros((NB, bs, lanes), jnp.float32)
    new = jnp.asarray(rng.normal(size=(5, H, D)).astype(np.float32))
    slots = jnp.asarray([4, 5, 6, 7, 8], jnp.int32)
    outs = [kv_cache_scatter_quant(kp, kp, ks, ks, new, new, slots)
            for _ in range(2)]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    qk, _, sk, _ = outs[0]
    qk, sk = np.asarray(qk), np.asarray(sk)
    assert np.abs(qk).max() <= 127
    for i, s in enumerate([4, 5, 6, 7]):
        tok = qk[s // bs, s % bs].reshape(H, D).astype(np.float32) \
            * sk[s // bs, s % bs, 0]
        np.testing.assert_allclose(tok, np.asarray(new[i]),
                                   atol=np.abs(np.asarray(new[i])).max()
                                   / 127 + 1e-7)


# ---------------------------------------------------------------------
# int8 paged KV cache lifecycle
# ---------------------------------------------------------------------
def _int8_cache(**kw):
    args = dict(num_layers=1, num_heads=2, head_dim=8, block_size=4,
                num_blocks=10, max_model_len=40, register=False,
                dtype="int8")
    args.update(kw)
    return PagedKVCache(**args)


def test_int8_cache_carries_scale_tables():
    c = _int8_cache()
    assert c.quantized and c.scale_lanes == pr.KV_SCALE_LANES
    ks, vs = c.layer_scales(0)
    assert ks._value.shape == (c.num_blocks, c.block_size,
                               c.scale_lanes)
    assert str(ks._value.dtype) == "float32"
    # float pools carry none
    f = PagedKVCache(num_layers=1, num_heads=2, head_dim=8,
                     block_size=4, num_blocks=10, register=False)
    assert f.layer_scales(0) is None
    assert "int8" in c.stats()["kv_dtype"]


def test_int8_cow_split_copies_scale_table():
    """A COW split must copy the per-slot scale rows with the block:
    an int8 payload is meaningless under the wrong scales."""
    c = _int8_cache()
    p = list(range(1, 13))
    assert c.allocate("a", 12, tokens=p)
    c.commit_prefix("a", p)
    assert c.allocate("b", 12, tokens=p)
    shared = c._tables["b"][1]
    # stamp recognizable data into the shared block's pool + scales
    k, v = c.layer_pools(0)
    ks, vs = c.layer_scales(0)
    k._inplace_update(k._value.at[shared].set(42))
    ks._inplace_update(ks._value.at[shared].set(0.625))
    c.truncate("b", 6)
    assert c.append("b", 1)                    # forces the COW split
    assert c.cow_splits == 1
    new = c._tables["b"][1]
    assert new != shared
    np.testing.assert_array_equal(np.asarray(k._value[new]),
                                  np.asarray(k._value[shared]))
    np.testing.assert_array_equal(np.asarray(ks._value[new]),
                                  np.asarray(ks._value[shared]))
    assert float(ks._value[new].max()) == 0.625


def test_int8_cache_truncate_rolls_back_reserved_slots():
    c = _int8_cache(num_blocks=8, max_model_len=32)
    assert c.allocate("a", 5)
    assert c.append("a", 3) and c.length("a") == 8
    assert c.append("a", 1) and len(c._tables["a"]) == 3
    c.truncate("a", 5)
    assert c.length("a") == 5 and len(c._tables["a"]) == 2
    assert c.free_blocks == 6
    assert c.append("a", 4) and c.length("a") == 9


def test_prefix_hash_includes_kv_dtype():
    """bf16 and int8 caches must never alias prefix blocks: the chain
    hash seeds with the pool element dtype."""
    ci = _int8_cache()
    cf = PagedKVCache(num_layers=1, num_heads=2, head_dim=8,
                      block_size=4, num_blocks=10, max_model_len=40,
                      register=False, dtype="float32")
    toks = tuple(range(1, 5))
    assert ci._chain_hash(None, toks) != cf._chain_hash(None, toks)
    # same dtype still hashes identically (the reuse path is intact)
    ci2 = _int8_cache()
    assert ci._chain_hash(None, toks) == ci2._chain_hash(None, toks)


def test_int8_pool_admits_1_8x_blocks_at_fixed_budget(monkeypatch):
    """The memory-guard byte charge follows the ELEMENT dtype, so the
    same HBM budget admits ~2x int8 blocks (floor 1.8x: the per-slot
    scale tables eat a little of the 2x)."""
    monkeypatch.setenv("PADDLE_TPU_HBM_BUDGET", "64M")
    kw = dict(num_layers=2, num_heads=4, head_dim=32, block_size=16,
              register=False, hbm_fraction=0.5)
    bf16 = PagedKVCache(dtype="bfloat16", **kw)
    int8 = PagedKVCache(dtype="int8", **kw)
    assert int8.num_blocks >= 1.8 * bf16.num_blocks
    # byte accounting: int8 block = payload + scale-table overhead
    HD = 4 * 32
    assert bf16.bytes_per_block == 2 * 2 * 16 * HD * 2
    assert int8.bytes_per_block == 2 * 2 * 16 * (HD + 4)
    assert int8.stats()["bytes_per_block"] == int8.bytes_per_block


def test_int8_pool_registers_scale_buffers_with_guard():
    c = _int8_cache(register=True)
    try:
        names = [t.name for t in c.pool_tensors()]
        assert any("k_scale" in n for n in names)
        assert any("v_scale" in n for n in names)
    finally:
        c.close()


# ---------------------------------------------------------------------
# engine end-to-end parity
# ---------------------------------------------------------------------
@pytest.mark.slow
def test_engine_int8_kv_greedy_parity(gpt_mini):
    """Covered inside tier-1 by TestQuantSmokeGate (kv_only scenario);
    kept as a focused repro outside the smoke harness."""
    prompts = _prompts((3, 7, 12, 5, 9), seed=2)
    ref_eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                               max_model_len=64)
    try:
        want = ref_eng.generate(prompts, max_new_tokens=6)
    finally:
        ref_eng.close()
    eng = GenerationEngine(gpt_mini, num_blocks=64, max_batch=3,
                           max_model_len=64, kv_cache_dtype="int8")
    try:
        got = eng.generate(prompts, max_new_tokens=6)
        assert "int8" in eng.cache.stats()["kv_dtype"]
    finally:
        eng.close()
    assert greedy_match_ratio(want, got) >= 0.95


@pytest.mark.slow
def test_engine_int8_weights_parity_and_logits_cosine():
    """Covered inside tier-1 by TestQuantSmokeGate (weight_only
    scenario + cosine); kept as a focused repro."""
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=64,
                    num_hidden_layers=2, num_attention_heads=4,
                    max_position_embeddings=64)
    paddle.seed(11)
    mf = GPTForCausalLM(cfg)
    mf.eval()
    paddle.seed(11)
    mq = GPTForCausalLM(cfg)
    mq.eval()
    convert_to_int8(mq)
    prompts = _prompts((4, 9, 6), seed=5)
    ids = paddle.to_tensor(np.array([prompts[1]], np.int64))
    assert logits_cosine(mf(ids), mq(ids)) >= 0.99
    ref = GenerationEngine(mf, num_blocks=64, max_batch=3,
                           max_model_len=64)
    try:
        want = ref.generate(prompts, max_new_tokens=6)
    finally:
        ref.close()
    eng = GenerationEngine(mq, num_blocks=64, max_batch=3,
                           max_model_len=64)
    try:
        got = eng.generate(prompts, max_new_tokens=6)
    finally:
        eng.close()
    assert greedy_match_ratio(want, got) >= 0.95


def test_engine_env_knobs_select_int8(monkeypatch):
    """Both env knobs on one engine: the cache quantizes AND every
    Linear carries int8 codes, and the engine still decodes."""
    monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "int8")
    monkeypatch.setenv("PADDLE_TPU_WEIGHT_DTYPE", "int8")
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32,
                    num_hidden_layers=1, num_attention_heads=2,
                    max_position_embeddings=64)
    paddle.seed(1)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = GenerationEngine(m, num_blocks=16, max_batch=2,
                           max_model_len=64)
    try:
        assert eng.cache.quantized
        linears = [l for l in m.sublayers()
                   if isinstance(l, nn.Linear)]
        assert linears and all(
            getattr(l, "weight_q", None) is not None for l in linears)
        # decode-under-both-knobs parity is the smoke gate's job
        # (TestQuantSmokeGate runs the full E2E); here we only pin the
        # env -> state mapping without paying an engine compile
    finally:
        eng.close()


@pytest.mark.slow
def test_failover_replay_bit_identical_with_int8_cache(gpt_mini):
    """PR 12's replica-kill failover replay stays bit-identical when
    the paged cache is int8: per-slot quantization is deterministic,
    so replayed prefills reproduce codes AND scales exactly.  (slow:
    the determinism core is covered in tier-1 by
    test_scatter_quant_deterministic_and_bounded + the smoke gate.)"""
    from paddle_tpu.distributed.fault_tolerance import FaultPlan, inject
    rng = np.random.RandomState(3)
    shared = list(rng.randint(1, VOCAB, size=16))
    prompts = [shared + list(rng.randint(1, VOCAB, size=2 + i % 4))
               for i in range(4)]

    def dp():
        return DataParallelEngine(gpt_mini, dp=2, num_blocks=128,
                                  max_batch=4, block_size=8,
                                  max_model_len=64,
                                  kv_cache_dtype="int8")

    ref = dp()
    try:
        want = ref.generate(prompts, max_new_tokens=6)
    finally:
        ref.close()
    plan = FaultPlan.parse("serve.replica_down.dp0:kill:after=2,count=1")
    eng = dp()
    try:
        with inject(plan):
            got = eng.generate(prompts, max_new_tokens=6)
        s = eng.stats()
    finally:
        eng.close()
    assert got == want
    assert s["failovers"] == 1 and s["replays"] > 0


def _load_script(fname, modname):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", fname)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# CI gate: the quant smoke runs green inside tier-1
# ---------------------------------------------------------------------
def _load_quant_smoke():
    return _load_script("quant_smoke.py", "quant_smoke_cli")


class TestQuantSmokeGate:
    def test_all_scenarios_pass(self, capsys):
        smoke = _load_quant_smoke()
        ok, report = smoke.run(seed=7, max_new_tokens=4)
        capsys.readouterr()
        assert ok, report
        assert report["both"]["greedy_match"] >= 0.95
        assert report["weight_only"]["logits_cosine"] >= 0.99
        assert report["capacity"]["ratio"] >= 1.8
