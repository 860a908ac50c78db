"""Lazy eager mode (SURVEY.md §7 "dygraph without per-op sync"):
ops defer into a segment buffer and flush as one compiled program at
sync points; forward, backward (deferred VJP residuals) and gradient
accumulation all stay in the buffer.  Parity against immediate eager
is exact (same impls, same order)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
import paddle_tpu.nn.functional as F
from paddle_tpu.core import lazy


@pytest.fixture(autouse=True)
def _clean_lazy_state():
    yield
    lazy.enable_lazy(False)
    lazy._tls.buffer.pending.clear()


def test_lazy_defers_until_read():
    with paddle.incubate.lazy_eager():
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        y = x + 1
        z = paddle.matmul(y, y)
        assert isinstance(z._value, lazy.LazyValue)
        assert len(lazy._tls.buffer.pending) >= 2
        # aval surface works without forcing
        assert z.shape == [4, 4]
        assert str(z.dtype) == "paddle.float32"
        assert isinstance(z._value, lazy.LazyValue)
        val = z.numpy()                      # sync point
        assert len(lazy._tls.buffer.pending) == 0
        np.testing.assert_allclose(val, np.full((4, 4), 16.0))


def test_lazy_backward_parity():
    a_np = np.random.RandomState(0).randn(3, 3).astype(np.float32)
    with paddle.incubate.lazy_eager():
        a = paddle.to_tensor(a_np, stop_gradient=False)
        loss = paddle.matmul(a, a).sum()
        loss.backward()
        assert isinstance(a.grad._value, lazy.LazyValue)
        g = a.grad.numpy()
    b = paddle.to_tensor(a_np, stop_gradient=False)
    paddle.matmul(b, b).sum().backward()
    np.testing.assert_allclose(g, b.grad.numpy(), rtol=1e-6)


def _train(model_fn, data_fn, lazy_on, steps=4):
    import contextlib
    paddle.seed(7)
    m = model_fn()
    opt = optimizer.Adam(learning_rate=1e-3, parameters=m.parameters())
    cm = paddle.incubate.lazy_eager() if lazy_on else \
        contextlib.nullcontext()
    losses = []
    with cm:
        for i in range(steps):
            x, y = data_fn(i)
            loss = F.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
    return losses


def test_lazy_lenet_train_parity():
    from paddle_tpu.vision.models import LeNet

    def data(i):
        rng = np.random.RandomState(i)
        return (paddle.to_tensor(
                    rng.randn(8, 1, 28, 28).astype(np.float32)),
                paddle.to_tensor(
                    rng.randint(0, 10, (8,)).astype(np.int64)))

    ref = _train(lambda: LeNet(num_classes=10), data, False)
    got = _train(lambda: LeNet(num_classes=10), data, True)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)


def test_lazy_gpt_train_parity():
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=32,
                    use_flash_attention=False)
    crit = GPTPretrainingCriterion()

    def data(i):
        rng = np.random.RandomState(i)
        ids = rng.randint(0, 128, (2, 16)).astype(np.int64)
        return paddle.to_tensor(ids), paddle.to_tensor(ids)

    def train(lazy_on):
        import contextlib
        paddle.seed(3)
        m = GPTForCausalLM(cfg)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=m.parameters())
        cm = paddle.incubate.lazy_eager() if lazy_on else \
            contextlib.nullcontext()
        out = []
        with cm:
            for i in range(3):
                x, y = data(i)
                loss = crit(m(x), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                out.append(float(loss))
        return out

    np.testing.assert_allclose(train(True), train(False),
                               rtol=1e-5, atol=1e-7)


def test_lazy_control_flow_forces():
    """Python control flow on a lazy value is a sync point."""
    with paddle.incubate.lazy_eager():
        x = paddle.to_tensor(np.float32(2.0))
        y = x * 3
        if float(y) > 5.0:          # forces
            z = y + 1
        assert float(z) == 7.0


def test_lazy_amp_autocast():
    lin = nn.Linear(8, 8)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 8).astype(np.float32))
    with paddle.incubate.lazy_eager():
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            out = lin(x)
        assert out.dtype == paddle.bfloat16
        loss = out.sum()
        loss.backward()
        assert lin.weight.grad is not None
        g = lin.weight.grad.numpy()
    assert np.isfinite(g.astype(np.float32)).all()


def test_lazy_to_static_interop():
    """Entering a to_static trace forces pending lazy state cleanly."""
    from paddle_tpu import jit

    paddle.seed(0)
    m = nn.Linear(4, 4)
    x = paddle.to_tensor(np.random.RandomState(1)
                         .randn(2, 4).astype(np.float32))
    with paddle.incubate.lazy_eager():
        # mutate a param lazily first
        m.weight.set_value(m.weight * 1.5)
        st = jit.to_static(m)
        out = st(x)
        ref = m(x)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   np.asarray(ref.numpy()),
                                   rtol=1e-5, atol=1e-6)


def test_lazy_auto_flush_bound():
    """A loop that never reads values still flushes at the node cap."""
    old = lazy._AUTO_FLUSH_NODES
    lazy._AUTO_FLUSH_NODES = 32
    try:
        with paddle.incubate.lazy_eager():
            x = paddle.to_tensor(np.float32(1.0))
            for _ in range(64):
                x = x + 1
            # flush happens on the record AFTER the cap is reached, so
            # the bound is <= cap (boundary moved by prune-safe flush)
            assert len(lazy._tls.buffer.pending) <= 32
            assert float(x) == 65.0
    finally:
        lazy._AUTO_FLUSH_NODES = old


def test_lazy_dropout_stays_deferred():
    """RNG ops (function-valued closure cells) must record lazily, not
    force a full-buffer sync per call (r4 review finding)."""
    paddle.seed(0)
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(8, 8).astype(np.float32))
    with paddle.incubate.lazy_eager():
        h = x * 2.0
        d = F.dropout(h, p=0.5, training=True)
        assert isinstance(d._value, lazy.LazyValue), \
            "dropout forced the lazy buffer"
        assert len(lazy._tls.buffer.pending) >= 2
        out = d.numpy()
    kept = out != 0
    np.testing.assert_allclose(out[kept],
                               (x.numpy() * 4.0)[kept], rtol=1e-6)


def test_lazy_flush_error_is_preserved():
    """A failed flush must surface the real cause on later reads, not a
    bare 'did not materialize' (r4 review finding)."""
    with paddle.incubate.lazy_eager():
        a = paddle.to_tensor(np.ones((2, 2), np.float32))
        b = paddle.to_tensor(np.ones((3, 3), np.float32))
        # shape-incompatible matmul records fine under eval_shape? no —
        # it raises at record; instead build a legal graph and poison
        # the node's run to simulate an execution-time failure
        c = a + 1.0
        node = c._value.node

        def boom(*ins):
            raise ValueError("injected flush failure")
        node.run = boom
        with pytest.raises(ValueError, match="injected"):
            c.numpy()
        # the value is permanently poisoned with the original cause
        with pytest.raises(RuntimeError, match="segment failed"):
            c._value.force()


def test_lazy_to_static_with_pending_state():
    """Process-wide lazy + to_static'd TRAIN step: the step MUTATES
    params (backward + opt.step), so after the discovery run the state
    tensors hold pending LazyValues, and lower()/compiled calls must
    force them (r4: 'Triggering __jax_array__ during abstractification'
    — reproduced pre-fix exactly by this test)."""
    from paddle_tpu import jit, optimizer

    paddle.seed(0)
    m = nn.Sequential(nn.Linear(6, 6), nn.Tanh(), nn.Linear(6, 6))
    opt = optimizer.Adam(learning_rate=1e-2,
                         parameters=m.parameters())
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(4, 6).astype(np.float32))
    y = paddle.to_tensor(np.random.RandomState(1)
                         .randn(4, 6).astype(np.float32))

    def train_step(xb, yb):
        loss = F.mse_loss(m(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    with paddle.incubate.lazy_eager():
        st = jit.to_static(train_step)
        losses = [float(st(x, y)) for _ in range(4)]
    assert losses[-1] < losses[0]

    # also: a pending mutation made OUTSIDE then read through the
    # compiled executor path
    from paddle_tpu import static
    paddle.enable_static()
    try:
        with paddle.incubate.lazy_eager():
            main = static.Program()
            startup = static.Program()
            with static.program_guard(main, startup):
                xv = static.data("x", [2, 6], "float32")
                lin = nn.Linear(6, 6)
                out = lin(xv)
            doubled = lin.weight * 2.0
            assert isinstance(doubled._value, lazy.LazyValue)
            lin.weight._value = doubled._value
            exe = static.Executor()
            got = exe.run(main, feed={"x": np.zeros((2, 6), np.float32)},
                          fetch_list=[out])[0]
            assert np.isfinite(got).all()
    finally:
        paddle.disable_static()


# ---------------------------------------------------------------------
# whole-step capture + fingerprinted executable reuse
# ---------------------------------------------------------------------
def test_lazy_lenet_full_state_bit_parity():
    """The whole-step segment must track per-op eager after 3 full
    train steps (fwd + bwd + fused update): losses BIT-identical, every
    parameter and Adam accumulator within a few ulp of its array's
    largest element.

    State is not held to bit equality: XLA's CPU backend (jaxlib 0.9)
    contracts a multiply that feeds an add into one FMA when both sit
    in one program, so the fused step rounds Adam's
    ``b1*m + (1-b1)*g`` once where per-op programs round twice —
    ``jax.jit(whole)`` vs split jits reproduces it with no paddle code,
    the caveat the BN test below and to_static already carry."""
    import contextlib
    from paddle_tpu.vision.models import LeNet

    def train(lazy_on, steps=3):
        paddle.seed(7)
        m = LeNet(num_classes=10)
        opt = optimizer.Adam(learning_rate=1e-3,
                             parameters=m.parameters())
        rng = np.random.RandomState(1)
        img = paddle.to_tensor(
            rng.randn(8, 1, 28, 28).astype(np.float32))
        lab = paddle.to_tensor(
            rng.randint(0, 10, (8,)).astype(np.int64))
        cm = paddle.incubate.lazy_eager() if lazy_on else \
            contextlib.nullcontext()
        losses = []
        with cm:
            for _ in range(steps):
                loss = F.cross_entropy(m(img), lab)
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
            params = [np.asarray(p.numpy()) for p in m.parameters()]
            # in parameter order: generated names differ between the
            # two runs, so sorting by name would pair unlike arrays
            accs = [np.asarray(d[p.name].numpy())
                    for _, d in sorted(opt._accumulators.items())
                    for p in m.parameters() if p.name in d]
        return losses, params, accs

    l_ref, p_ref, a_ref = train(False)
    l_got, p_got, a_got = train(True)
    assert l_got == l_ref                     # exact, not allclose
    assert len(a_got) == len(a_ref) > 0
    eps = np.finfo(np.float32).eps
    for got, ref in zip(p_got + a_got, p_ref + a_ref):
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=8 * eps * np.abs(ref).max())


def test_lazy_fused_bn_segment_close_parity():
    """BatchNorm models: fusing fwd+bwd into ONE program lets XLA round
    the BN backward reductions differently than per-op programs (pure
    jax.jit(whole) vs split jits reproduces this with no paddle code
    involved), so the guarantee is tight allclose, not bit-equality —
    the same caveat to_static carries."""
    import contextlib

    def train(lazy_on, steps=3):
        paddle.seed(3)
        m = nn.Sequential(nn.Conv2D(3, 8, 3), nn.BatchNorm2D(8),
                          nn.ReLU(), nn.Flatten(), nn.Linear(8 * 6 * 6, 5))
        opt = optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                 parameters=m.parameters())
        rng = np.random.RandomState(0)
        img = paddle.to_tensor(
            rng.randn(4, 3, 8, 8).astype(np.float32))
        lab = paddle.to_tensor(
            rng.randint(0, 5, (4,)).astype(np.int64))
        cm = paddle.incubate.lazy_eager() if lazy_on else \
            contextlib.nullcontext()
        losses = []
        with cm:
            for _ in range(steps):
                loss = F.cross_entropy(m(img), lab)
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
            stats = [np.asarray(b.numpy()) for b in m.buffers()]
        return losses, stats

    l_ref, s_ref = train(False)
    l_got, s_got = train(True)
    np.testing.assert_allclose(l_got, l_ref, rtol=1e-5, atol=1e-6)
    for got, ref in zip(s_got, s_ref):        # running stats track
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_lazy_cross_thread_flush():
    """A tensor recorded on one thread may be read from another
    (checkpoint / logging threads): force() flushes the buffer that
    OWNS the node, not the reader's thread-local buffer."""
    import threading

    with paddle.incubate.lazy_eager():
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        y = x * 2.0 + 1.0
        assert isinstance(y._value, lazy.LazyValue)
        box = {}

        def reader():
            box["val"] = np.asarray(y.numpy())
            box["pending_here"] = len(lazy._tls.buffer.pending)

        t = threading.Thread(target=reader)
        t.start()
        t.join()
        np.testing.assert_allclose(box["val"], np.full((4, 4), 3.0))
        assert box["pending_here"] == 0       # worker's own buffer
        assert len(lazy._tls.buffer.pending) == 0, \
            "producer's buffer was not flushed by the cross-thread read"


def test_lazy_watermark_env_rereads(monkeypatch):
    """PADDLE_TPU_LAZY_MAX_NODES is re-read at enable_lazy(), so jobs
    retune the watermark without a restart; a loop that never reads
    values flushes at the cap."""
    old = lazy._AUTO_FLUSH_NODES
    monkeypatch.setenv("PADDLE_TPU_LAZY_MAX_NODES", "16")
    try:
        with paddle.incubate.lazy_eager():
            assert lazy._AUTO_FLUSH_NODES == 16
            before = lazy.stats["flushes"]
            x = paddle.to_tensor(np.float32(1.0))
            for _ in range(40):
                x = x + 1
            assert len(lazy._tls.buffer.pending) <= 16
            assert lazy.stats["flushes"] > before
            assert float(x) == 41.0
    finally:
        lazy._AUTO_FLUSH_NODES = old


def test_lazy_control_flow_flush_counts():
    """Value-dependent control flow is a real sync point: the branch
    condition flushes the pending segment (counted), and ops recorded
    after it start a fresh segment."""
    with paddle.incubate.lazy_eager():
        before = lazy.stats["flushes"]
        x = paddle.to_tensor(np.float32(2.0))
        y = x * 3
        if float(y) > 5.0:                    # forces a flush
            z = y + 1
        assert lazy.stats["flushes"] == before + 1
        assert isinstance(z._value, lazy.LazyValue)
        assert float(z) == 7.0


def test_lazy_fingerprint_hit_and_shape_miss():
    """Same structure + same leaf avals -> pure cache hit (no retrace);
    a leaf SHAPE change is a different fingerprint -> compile."""
    def step(n):
        x = paddle.to_tensor(np.ones((n, n), np.float32))
        return float((x * 2.0 + 1.0).sum())

    with paddle.incubate.lazy_eager():
        step(4)
        s0 = dict(lazy.stats)
        assert step(4) == step(4)             # two replays
        s1 = dict(lazy.stats)
        assert s1["cache_hits"] - s0["cache_hits"] == 2
        assert s1["compiles"] == s0["compiles"], "replay retraced"
        step(5)                               # shape change
        s2 = dict(lazy.stats)
        assert s2["compiles"] == s1["compiles"] + 1
        assert s2["cache_hits"] == s1["cache_hits"]


def test_lazy_scalar_hoist_no_thrash():
    """Bare python scalars are hoisted to weak-typed traced leaves, so a
    CHANGING scalar (lr schedules, loss scales) replays the same
    executable instead of fingerprinting a new segment per value."""
    x = paddle.to_tensor(np.ones((3, 3), np.float32))

    def step(k):
        # one code shape for warmup and loop: the liveness mask (which
        # outputs materialize) is part of the fingerprint, so the
        # warmup must hold references exactly like the replay does
        return float((x * k).sum())

    with paddle.incubate.lazy_eager():
        step(2.0)                             # compile once
        s0 = dict(lazy.stats)
        for k in (3.0, 4.5, 7.25):
            assert step(k) == 9 * k
        s1 = dict(lazy.stats)
        assert s1["compiles"] == s0["compiles"], \
            "changing python scalar retraced the segment"
        assert s1["cache_hits"] - s0["cache_hits"] == 3


def test_eager_fwd_cache_lru_eviction(monkeypatch):
    """The per-op jit cache evicts least-recently-USED past the cap
    (the old insert-stop silently disabled caching for every op past
    the first N), and evictions are counted into stats + registry."""
    from paddle_tpu.core import dispatch
    from paddle_tpu import observability as obs
    from paddle_tpu.observability.registry import get_registry

    saved = list(dispatch._eager_fwd_cache.items())
    dispatch._eager_fwd_cache.clear()
    monkeypatch.setattr(dispatch, "_EAGER_JIT_MAX", 4)
    ev0 = dispatch.cache_evictions["fwd"]
    try:
        with obs.enabled_scope():
            reg0 = get_registry().counter("eager.cache_evictions").value
            with paddle.no_grad():
                for n in range(1, 7):         # 6 distinct signatures
                    t = paddle.to_tensor(np.ones((n,), np.float32))
                    (t + 1.0).numpy()
                assert len(dispatch._eager_fwd_cache) <= 4
                assert dispatch.cache_evictions["fwd"] >= ev0 + 2
                # LRU not FIFO: touching an old entry keeps it alive
                keys = list(dispatch._eager_fwd_cache)
                t = paddle.to_tensor(np.ones((3,), np.float32))
                (t + 1.0).numpy()             # hit -> moves to back
                assert list(dispatch._eager_fwd_cache)[-1] in keys
            reg1 = get_registry().counter("eager.cache_evictions").value
            assert reg1 > reg0
    finally:
        dispatch._eager_fwd_cache.clear()
        dispatch._eager_fwd_cache.update(saved)


@pytest.mark.serve
def test_lazy_traced_model_serves_through_engine():
    """A model whose params were mutated under lazy mode (pending
    LazyValues in the weights) serves through GenerationEngine
    unchanged: the engine's trace forces pending state cleanly."""
    from paddle_tpu.inference.serving import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
    paddle.seed(7)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 97, size=n)) for n in (3, 7, 5)]

    with paddle.incubate.lazy_eager():
        # identity-rescale every param lazily (the optimizer's in-place
        # rebind path): weights now hold pending LazyValues when the
        # engine first traces the model
        for p in model.parameters():
            p._inplace_update((p * 1.0)._value)
        assert any(isinstance(p._value, lazy.LazyValue)
                   for p in model.parameters())
        ref = []
        for p in prompts:
            ids = paddle.to_tensor(np.asarray([p], np.int64))
            ref.append(np.asarray(
                model.generate(ids, max_new_tokens=6).numpy())[0]
                .tolist())
        eng = GenerationEngine(model, num_blocks=64, max_batch=3,
                               max_model_len=64, prefill_chunk=16)
        try:
            got = eng.generate(prompts, max_new_tokens=6)
        finally:
            eng.close()
    assert got == ref


def test_lazy_prunes_dead_intermediates():
    """Intermediates with no external reference at flush time must NOT
    be materialized as program outputs (buffer-reuse/DCE inside the
    replay executable; returning every intermediate was a 10x+ step
    cost at GPT scale) — while referenced values still materialize."""
    with paddle.incubate.lazy_eager():
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        a = x * 2.0
        held = x * 5.0                 # stays referenced via `held`
        node, idx = a._value.node, a._value.out_index
        b = a * 3.0 + 1.0              # consumes a internally
        del a
        np.testing.assert_allclose(np.asarray(b.numpy()),
                                   np.full((4, 4), 7.0))
        assert node.outs[idx]._concrete is None, \
            "dead intermediate was materialized"
        # `held` was externally referenced -> materialized by the flush
        assert held._value._concrete is not None or \
            np.asarray(held.numpy()).sum() == 80.0


# ---------------------------------------------------------------------
# RNN / dynamic-model sweep: recurrent python loops are the lazy
# tier's stress case — every timestep records ops into the segment, so
# whole-step capture must still flush once per sync point, replay one
# cached fingerprint at steady state, and leave the TPU205 segment
# audit clean (fixed shapes => no thrash).
# ---------------------------------------------------------------------
def _rnn_model(kind):
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            if kind == "simple":
                self.rnn = nn.SimpleRNN(8, 16)
            elif kind == "lstm":
                self.rnn = nn.LSTM(8, 16)
            elif kind == "gru":
                self.rnn = nn.GRU(8, 16)
            else:
                self.rnn = nn.GRU(8, 16, direction="bidirect")
            self.head = nn.Linear(32 if kind == "bigru" else 16, 4)

        def forward(self, x):
            y, _ = self.rnn(x)
            return self.head(paddle.mean(y, axis=1))
    return Net()


@pytest.mark.parametrize("kind", ["simple", "lstm", "gru", "bigru"])
def test_lazy_rnn_sweep_flush_counts_and_clean_audit(kind):
    from paddle_tpu import analysis
    from paddle_tpu.core.lazy import _segment_history

    paddle.seed(11)
    m = _rnn_model(kind)
    opt = optimizer.Adam(learning_rate=1e-3, parameters=m.parameters())
    rng = np.random.RandomState(5)
    mark = len(_segment_history)
    steps, flushes, hits0 = 4, [], lazy.stats["cache_hits"]
    with paddle.incubate.lazy_eager():
        for i in range(steps):
            x = paddle.to_tensor(rng.randn(2, 6, 8).astype(np.float32))
            y = paddle.to_tensor(rng.randint(0, 4, (2,)).astype(np.int64))
            before = lazy.stats["flushes"]
            loss = F.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            float(loss)                           # the step's one sync
            flushes.append(lazy.stats["flushes"] - before)
    # whole-step capture: exactly one flush per training step
    assert flushes == [1] * steps, flushes
    # steady state replays the cached executable, not a recompile
    assert lazy.stats["cache_hits"] - hits0 >= steps - 1
    # fixed shapes + static op stream => the TPU205 audit stays clean
    fresh = list(_segment_history)[mark:]
    diags = analysis.recompile.audit_segment_cache(history=fresh, threshold=2)
    assert diags == [], [d.message for d in diags]


@pytest.mark.parametrize("kind", ["lstm", "gru"])
def test_lazy_rnn_parity_against_immediate(kind):
    """Same recurrent step, lazy vs immediate eager: exact same impls
    in the same order, so losses agree to float tolerance."""
    def data(i):
        rng = np.random.RandomState(i)
        return (paddle.to_tensor(rng.randn(2, 6, 8).astype(np.float32)),
                paddle.to_tensor(rng.randint(0, 4, (2,)).astype(np.int64)))

    ref = _train(lambda: _rnn_model(kind), data, False)
    got = _train(lambda: _rnn_model(kind), data, True)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)


def test_lazy_rnn_shape_drift_flags_tpu205():
    """The negative control: a recurrent loop fed a NEW sequence length
    every step recompiles the whole segment each time — exactly what
    the TPU205 audit exists to name."""
    from paddle_tpu import analysis
    from paddle_tpu.core.lazy import _segment_history

    paddle.seed(12)
    m = _rnn_model("gru")
    rng = np.random.RandomState(9)
    mark = len(_segment_history)
    with paddle.incubate.lazy_eager():
        for t in (4, 5, 6):                      # drifting seq length
            x = paddle.to_tensor(rng.randn(2, t, 8).astype(np.float32))
            float(paddle.mean(m(x)))
    fresh = list(_segment_history)[mark:]
    diags = analysis.recompile.audit_segment_cache(history=fresh, threshold=2)
    assert any(d.code == "TPU205" for d in diags), \
        "shape drift across steps must flag TPU205"
