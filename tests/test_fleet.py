"""Fleet parity tests on the 8-device CPU mesh: DP / TP / sharding / MoE
train with the same losses as a single-device run (SURVEY.md §4's
loss-parity strategy)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.communication import group as group_mod


def _reset_mesh(mesh=None):
    dist.env.set_global_mesh(mesh)
    group_mod._default_group = None


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    _reset_mesh(None)


def _mlp(seed):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                         nn.Linear(32, 4))


def _train(model, steps, make_batch, opt=None, wrap=None):
    opt = opt or optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    run = wrap(model) if wrap else model
    losses = []
    for i in range(steps):
        x, y = make_batch(i)
        out = run(paddle.to_tensor(x))
        loss = paddle.nn.functional.mse_loss(out, paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


def _batches(i):
    rng = np.random.RandomState(100 + i)
    return (rng.randn(8, 16).astype(np.float32),
            rng.randn(8, 4).astype(np.float32))


def test_data_parallel_loss_parity():
    ref = _train(_mlp(0), 10, _batches)
    _reset_mesh(Mesh(np.array(jax.devices()[:8]), ("dp",)))
    got = _train(_mlp(0), 10, _batches,
                 wrap=lambda m: dist.DataParallel(m))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_sharding_stage2_loss_parity():
    from paddle_tpu.distributed.fleet.meta_parallel.sharding import \
        group_sharded
    ref_m = _mlp(1)
    ref_opt = optimizer.Adam(learning_rate=0.01,
                             parameters=ref_m.parameters())
    ref = _train(ref_m, 10, _batches, opt=ref_opt)

    _reset_mesh(Mesh(np.array(jax.devices()[:8]), ("dp",)))
    m = _mlp(1)
    opt = optimizer.Adam(learning_rate=0.01, parameters=m.parameters())
    wrapped, opt2, _ = group_sharded.group_sharded_parallel(
        m, opt, level="os_g")
    got = _train(wrapped, 10, _batches, opt=opt2)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_sharding_stage3_loss_parity():
    from paddle_tpu.distributed.fleet.meta_parallel.sharding import \
        group_sharded
    ref_m = _mlp(2)
    ref_opt = optimizer.Adam(learning_rate=0.01,
                             parameters=ref_m.parameters())
    ref = _train(ref_m, 10, _batches, opt=ref_opt)

    _reset_mesh(Mesh(np.array(jax.devices()[:8]), ("dp",)))
    m = _mlp(2)
    opt = optimizer.Adam(learning_rate=0.01, parameters=m.parameters())
    wrapped, opt2, _ = group_sharded.group_sharded_parallel(
        m, opt, level="p_g_os")
    got = _train(wrapped, 10, _batches, opt=opt2)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


class _TPBlock(nn.Layer):
    """Column→Row pair, the Megatron building block."""

    def __init__(self, parallel):
        super().__init__()
        if parallel:
            from paddle_tpu.distributed.fleet.meta_parallel. \
                parallel_layers.mp_layers import (ColumnParallelLinear,
                                                  RowParallelLinear)
            self.fc1 = ColumnParallelLinear(16, 64, has_bias=True,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(64, 4, has_bias=True,
                                         input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(16, 64)
            self.fc2 = nn.Linear(64, 4)
        self.act = nn.ReLU()

    def forward(self, x):
        return self.fc2(self.act(self.fc1(x)))


def test_tensor_parallel_loss_parity():
    paddle.seed(3)
    ref_model = _TPBlock(parallel=False)
    ref = _train(ref_model, 10, _batches)

    _reset_mesh(Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                     ("dp", "mp")))
    paddle.seed(3)   # same seed → identical init draws as the reference
    tp_model = _TPBlock(parallel=True)
    got = _train(tp_model, 10, _batches)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_vocab_parallel_embedding():
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers. \
        mp_layers import VocabParallelEmbedding
    _reset_mesh(Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                     ("dp", "mp")))
    paddle.seed(4)
    emb = VocabParallelEmbedding(64, 8)
    ids = paddle.to_tensor(np.array([[1, 5], [63, 0]], np.int64))
    out = emb(ids)
    np.testing.assert_allclose(
        out.numpy(), emb.weight.numpy()[ids.numpy()], atol=1e-6)


def test_parallel_cross_entropy_shard_map():
    """Vocab-parallel CE inside shard_map matches dense CE."""
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers. \
        mp_layers import ParallelCrossEntropy
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("mp",))
    _reset_mesh(mesh)
    rng = np.random.RandomState(5)
    V = 64  # 8 per shard
    logits = rng.randn(6, V).astype(np.float32)
    labels = rng.randint(0, V, (6,)).astype(np.int64)
    labels[2] = -100  # ignore_index

    pce = ParallelCrossEntropy()

    def f(lg, lb):
        t = Tensor(lg, _internal=True)
        l = Tensor(lb, _internal=True)
        out = pce(t, l)
        return out._value

    got = jax.shard_map(f, mesh=mesh, in_specs=(P(None, "mp"), P(None)),
                        out_specs=P(None), check_vma=False)(
        jnp.asarray(logits), jnp.asarray(labels))

    ref = paddle.nn.functional.cross_entropy(
        paddle.to_tensor(logits), paddle.to_tensor(labels),
        reduction="none", ignore_index=-100)
    np.testing.assert_allclose(np.asarray(got)[:, 0], ref.numpy(),
                               rtol=1e-5, atol=1e-5)


def test_moe_layer_trains():
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.incubate.distributed.models.moe.gate import GShardGate
    paddle.seed(6)
    d_model = 16
    experts = nn.LayerList([
        nn.Sequential(nn.Linear(d_model, 32), nn.ReLU(),
                      nn.Linear(32, d_model)) for _ in range(4)])
    moe = MoELayer(d_model=d_model, experts=experts,
                   gate=GShardGate(d_model, 4, topk=2))
    opt = optimizer.Adam(learning_rate=0.01, parameters=moe.parameters())
    rng = np.random.RandomState(7)
    x = rng.randn(2, 8, d_model).astype(np.float32)
    losses = []
    for _ in range(5):
        out = moe(paddle.to_tensor(x))
        loss = paddle.mean((out - paddle.to_tensor(x)) ** 2)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_pipeline_parallel_loss_parity():
    """(dp=2, pp=4) SPMD GPipe schedule matches single-device training
    (VERDICT r3 item 5: real PP, loss parity on the 8-CPU mesh)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.\
        pp_layers import PipelineLayer

    def build_layers(seed):
        paddle.seed(seed)
        return [l for _ in range(4)
                for l in (nn.Linear(16, 16), nn.Tanh())]

    def batches(i):
        rng = np.random.RandomState(7 + i)
        return (rng.randn(8, 16).astype(np.float32),
                rng.randn(8, 16).astype(np.float32))

    # single-device reference: same 8 layers, full-batch steps
    ref_model = nn.Sequential(*build_layers(3))
    ref = _train(ref_model, 8, batches)

    # pipelined: 4 stages x (Linear, Tanh), 2 microbatches, dp=2
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 4}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2}
    fleet.init(is_collective=True, strategy=strategy)
    mse = lambda o, l: paddle.nn.functional.mse_loss(o, l)
    pl = PipelineLayer(layers=build_layers(3), num_stages=4, loss_fn=mse)
    model = fleet.distributed_model(pl)
    opt = optimizer.SGD(learning_rate=0.1, parameters=pl.parameters())

    losses = []
    for i in range(8):
        x, y = batches(i)
        loss = model.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt)
        losses.append(float(loss))
    # the SPMD engine (not the accumulation fallback) must have run
    assert model._engine not in (None, False), "SPMD PP engine not used"
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)

    # trained params scatter back into the eager layers
    model.eval_batch((paddle.to_tensor(batches(0)[0]),
                      paddle.to_tensor(batches(0)[1])))
    p0 = np.asarray(pl.parameters()[0]._value)
    assert np.abs(p0 - np.asarray(ref_model.parameters()[0]._value)).max() \
        < 1e-3


def test_heterogeneous_pipeline_pp_mp_dp_parity():
    """GPT-shaped PipelineLayer (embedding -> N tp-blocks -> ln + tied
    head) trains with loss parity at dp=2, pp=2, mp=2 on the 8-CPU mesh
    (VERDICT r3 missing #3: heterogeneous stages + PPxTP composition)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.\
        pp_layers import PipelineLayer
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.\
        mp_layers import ColumnParallelLinear, RowParallelLinear

    V, H, FF, S = 32, 16, 32, 6

    class Embed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, H)

        def forward(self, x):
            return self.emb(x)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.c = ColumnParallelLinear(H, FF, has_bias=True,
                                          gather_output=False)
            self.r = RowParallelLinear(FF, H, has_bias=True,
                                       input_is_parallel=True)

        def forward(self, x):
            return x + self.r(paddle.tanh(self.c(x)))

    class Head(nn.Layer):
        def __init__(self, embed):
            super().__init__()
            self.ln = nn.LayerNorm(H)
            self.embed = embed  # tied: grads reach it from BOTH ends

        def forward(self, x):
            return paddle.matmul(self.ln(x), self.embed.emb.weight,
                                 transpose_y=True)

    def build(seed):
        paddle.seed(seed)
        embed = Embed()
        return [embed] + [Block() for _ in range(4)] + [Head(embed)]

    def batches(i):
        rng = np.random.RandomState(31 + i)
        x = rng.randint(0, V, (8, S)).astype(np.int64)
        y = np.roll(x, -1, axis=1)
        return x, y

    def xent(o, l):
        return paddle.nn.functional.cross_entropy(
            o.reshape([-1, V]), l.reshape([-1]))

    # single-device reference (no mesh: mp layers act as plain linears)
    ref_layers = build(5)
    ref_model = nn.Sequential(*ref_layers)
    ref_opt = optimizer.SGD(learning_rate=0.1,
                            parameters=ref_model.parameters())
    ref = []
    for i in range(6):
        x, y = batches(i)
        loss = xent(ref_model(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        ref_opt.step()
        ref_opt.clear_grad()
        ref.append(float(loss))

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 2,
                               "mp_degree": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 4}
    fleet.init(is_collective=True, strategy=strategy)
    pl = PipelineLayer(layers=build(5), num_stages=2, loss_fn=xent)
    model = fleet.distributed_model(pl)
    opt = optimizer.SGD(learning_rate=0.1, parameters=pl.parameters())

    losses = []
    for i in range(6):
        x, y = batches(i)
        loss = model.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt)
        losses.append(float(loss))

    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils import \
        GlobalPipelineEngine
    assert isinstance(model._engine, GlobalPipelineEngine), \
        f"global PP engine not used: {model._engine}"
    # heterogeneity must have been detected (pre=embed, post=head)
    assert model._engine.pre.entries and model._engine.post.entries
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)

    # tied embedding trained identically (grad flowed from both ends)
    model._engine.sync_params_to_layers()
    got_emb = np.asarray(pl.run_function[0][0].emb.weight._value)
    ref_emb = np.asarray(ref_layers[0].emb.weight._value)
    np.testing.assert_allclose(got_emb, ref_emb, rtol=1e-3, atol=1e-4)


def test_pipeline_global_engine_grad_scaler():
    """fp16-style GradScaler rides the global PP engine in-graph:
    found_inf gates the fused update, host evolves the dynamic scale
    (VERDICT r3 weak #3)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.\
        pp_layers import PipelineLayer

    def build(seed):
        paddle.seed(seed)
        return [l for _ in range(2)
                for l in (nn.Linear(16, 16), nn.Tanh())]

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 4}
    fleet.init(is_collective=True, strategy=strategy)
    mse = lambda o, l: paddle.nn.functional.mse_loss(o, l)
    pl = PipelineLayer(layers=build(9), num_stages=2, loss_fn=mse)
    model = fleet.distributed_model(pl)
    opt = optimizer.SGD(learning_rate=0.1, parameters=pl.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                   incr_every_n_steps=2)

    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randn(8, 16).astype(np.float32)
    losses = []
    for i in range(4):
        loss = model.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt,
            scaler=scaler)
        losses.append(float(loss))
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils import \
        GlobalPipelineEngine
    assert isinstance(model._engine, GlobalPipelineEngine), \
        "scaler retired the engine"
    assert losses[-1] < losses[0]
    assert scaler._scale >= 1024.0  # grew (finite grads) or unchanged


def test_interleaved_pipeline_parity_and_schedule():
    """Virtual-stage interleave (VERDICT r4 "next" #5): pp=2, v=2 over a
    GPT-shaped trunk (embed -> 4 blocks -> tied head).  The engine must
    (a) schedule DIFFERENTLY from plain GPipe — n_micro*v + pp - 1
    chunk ticks with per-(tick,slot) phase gathers, (b) stack weights
    (pp, v, ...) round-robin, and (c) match the single-device loss
    curve exactly like the non-interleaved engine does."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.\
        pp_layers import PipelineLayer
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel \
        import PipelineParallelWithInterleave
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils import \
        GlobalPipelineEngine
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils.\
        global_schedule import _interleave_schedule

    V, H, S = 32, 16, 6

    class Embed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, H)

        def forward(self, x):
            return self.emb(x)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(H, 2 * H)
            self.l2 = nn.Linear(2 * H, H)

        def forward(self, x):
            return x + self.l2(paddle.tanh(self.l1(x)))

    class Head(nn.Layer):
        def __init__(self, embed):
            super().__init__()
            self.ln = nn.LayerNorm(H)
            self.embed = embed

        def forward(self, x):
            return paddle.matmul(self.ln(x), self.embed.emb.weight,
                                 transpose_y=True)

    def build(seed):
        paddle.seed(seed)
        embed = Embed()
        return [embed] + [Block() for _ in range(4)] + [Head(embed)]

    def batches(i):
        rng = np.random.RandomState(77 + i)
        x = rng.randint(0, V, (8, S)).astype(np.int64)
        return x, np.roll(x, -1, axis=1)

    def xent(o, l):
        return paddle.nn.functional.cross_entropy(
            o.reshape([-1, V]), l.reshape([-1]))

    ref_layers = build(5)
    ref_model = nn.Sequential(*ref_layers)
    ref_opt = optimizer.SGD(learning_rate=0.1,
                            parameters=ref_model.parameters())
    ref = []
    for i in range(6):
        x, y = batches(i)
        loss = xent(ref_model(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        ref_opt.step()
        ref_opt.clear_grad()
        ref.append(float(loss))

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 2}
    strategy.pipeline_configs = {"accumulate_steps": 4,
                                 "micro_batch_size": 2}
    fleet.init(is_collective=True, strategy=strategy)
    pl = PipelineLayer(layers=build(5), num_stages=2, loss_fn=xent,
                       num_virtual_pipeline_stages=2)
    model = fleet.distributed_model(pl)
    assert isinstance(model, PipelineParallelWithInterleave)
    opt = optimizer.SGD(learning_rate=0.1, parameters=pl.parameters())

    losses = []
    for i in range(6):
        x, y = batches(i)
        loss = model.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt)
        losses.append(float(loss))

    eng = model._engine
    assert isinstance(eng, GlobalPipelineEngine) and eng.n_virtual == 2
    # (b) round-robin (pp, v, ...) stacking: 4 blocks -> 4 chunks
    assert len(eng.chunk_sections) == 4
    assert eng.stacked[0]._value.shape[:2] == (2, 2)
    # (a) schedules differently: interleave tick count vs GPipe's
    inj, _, ext, _, phase = _interleave_schedule(4, 2, 2)
    assert len(inj) == 4 * 2 + 2 - 1  # n_micro*v + pp - 1 = 9
    assert len(inj) != 4 + 2 - 1      # plain GPipe would be 5
    assert phase.max() == 1 and phase.min() == 0
    # (c) loss parity with single-device training
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)

    # tied embedding trained identically through the interleave
    eng.sync_params_to_layers()
    got = np.asarray(pl.run_function[0][0].emb.weight._value)
    np.testing.assert_allclose(
        got, np.asarray(ref_layers[0].emb.weight._value),
        rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------
# Trunk-detection hardening (VERDICT r4 weak #6 / next #9)

def test_trunk_fingerprint_catches_array_buffer_callable_attrs():
    """Stages that differ only via an ndarray mask, a registered buffer,
    or a callable attr must produce DIFFERENT signatures (previously
    these escaped the fingerprint and could silently merge)."""
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils.\
        global_schedule import _entry_signature

    def make(mask=None, buf=None, hook=None):
        paddle.seed(0)
        l = nn.Linear(4, 4)
        if mask is not None:
            l.mask = np.asarray(mask, np.float32)
        if buf is not None:
            l.register_buffer("aux", paddle.to_tensor(
                np.asarray(buf, np.float32)))
        if hook is not None:
            l.post_fn = hook
        return (l, None)

    base = _entry_signature(make())
    assert _entry_signature(make()) == base  # deterministic
    assert _entry_signature(make(mask=[1, 0, 1, 1])) != base
    assert _entry_signature(make(mask=[1, 0, 1, 1])) == \
        _entry_signature(make(mask=[1, 0, 1, 1]))
    assert _entry_signature(make(mask=[1, 1, 1, 1])) != \
        _entry_signature(make(mask=[1, 0, 1, 1]))
    assert _entry_signature(make(buf=[0.0, 0.0])) != base
    assert _entry_signature(make(hook=lambda x: x * 2)) != base

    # registered forward hooks run in __call__ and change stage math
    paddle.seed(0)
    hooked = nn.Linear(4, 4)
    hooked.register_forward_post_hook(lambda m, i, o: o * 0.5)
    assert _entry_signature((hooked, None)) != base

    # closure-captured constants distinguish factory-made callables
    def factory(c):
        return lambda x: x * c

    paddle.seed(0)
    a, b = nn.Linear(4, 4), nn.Linear(4, 4)
    a.post_fn, b.post_fn = factory(1.0), factory(0.5)
    assert _entry_signature((a, None)) != _entry_signature((b, None))
    b.post_fn = factory(1.0)
    assert _entry_signature((a, None)) == _entry_signature((b, None))

    # functools.partial bound args distinguish too
    import functools
    a.post_fn = functools.partial(lambda x, c: x * c, c=2.0)
    b.post_fn = functools.partial(lambda x, c: x * c, c=3.0)
    assert _entry_signature((a, None)) != _entry_signature((b, None))


def test_trunk_deep_post_section_found_loudly(caplog):
    """A >8-layer post section is legitimate: the bounded fast path
    misses it, the unbounded retry finds it and warns."""
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils.\
        global_schedule import _find_trunk

    sigs = ["A"] * 8 + [f"tail{i}" for i in range(12)]
    assert _find_trunk(sigs, 4) is None                  # bounded miss
    pre, body, post = _find_trunk(sigs, 4, max_edge=len(sigs))
    assert (pre, body, post) == (0, 8, 12)


def test_trunk_chunks_always_structurally_identical():
    """The invariant behind every split _find_trunk returns: cutting the
    body into n_stages chunks yields IDENTICAL chunks (all stages run
    the template's code).  A (A B)x6 body over 4 stages can't pipeline
    whole (reps=6 not divisible) — the finder may shrink to a valid
    sub-body, but never return differing chunks; a body with no
    periodic sub-run at all is rejected outright."""
    from paddle_tpu.distributed.fleet.meta_parallel.pp_utils.\
        global_schedule import _find_trunk

    def chunks_of(sigs, n_stages):
        split = _find_trunk(sigs, n_stages)
        if split is None:
            return None
        pre, body, post = split
        assert body % n_stages == 0
        per = body // n_stages
        seg = sigs[pre:pre + body]
        return [tuple(seg[i * per:(i + 1) * per])
                for i in range(n_stages)]

    cks = chunks_of(["A", "B"] * 6, 4)          # shrinks to a sub-body
    assert cks is not None and len(set(cks)) == 1
    # multi-layer period dividing evenly: per-chunk = 2 periods
    assert chunks_of(["A", "B"] * 8, 4) == [("A", "B", "A", "B")] * 4
    # no periodic run long enough for 8 stages anywhere in 12 layers
    assert _find_trunk(["A", "B", "C"] * 4, 8) is None


def test_pipeline_mask_stage_falls_back_never_wrong(caplog):
    """END-TO-END adversarial case: a trunk stage that differs ONLY by a
    plain ndarray attr that changes its math.  The engine must refuse
    the merge (loud fallback to the eager accumulation path) and the
    numerics must match the single-device reference exactly."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.\
        pp_layers import PipelineLayer

    class Scale(nn.Layer):
        def __init__(self, mask):
            super().__init__()
            self.mask = np.asarray(mask, np.float32)  # plain attr

        def forward(self, x):
            return x * paddle.to_tensor(self.mask)

    masks = [np.ones(16, np.float32) for _ in range(4)]
    masks[2] = np.full(16, 0.5, np.float32)       # stage 2 differs

    def build_layers(seed):
        paddle.seed(seed)
        return [l for s in range(4)
                for l in (nn.Linear(16, 16), Scale(masks[s]))]

    def batches(i):
        rng = np.random.RandomState(31 + i)
        return (rng.randn(8, 16).astype(np.float32),
                rng.randn(8, 16).astype(np.float32))

    ref_model = nn.Sequential(*build_layers(5))
    ref = _train(ref_model, 4, batches)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 4}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "micro_batch_size": 2}
    fleet.init(is_collective=True, strategy=strategy)
    mse = lambda o, l: paddle.nn.functional.mse_loss(o, l)
    pl = PipelineLayer(layers=build_layers(5), num_stages=4, loss_fn=mse)
    model = fleet.distributed_model(pl)
    opt = optimizer.SGD(learning_rate=0.1, parameters=pl.parameters())

    losses = []
    for i in range(4):
        x, y = batches(i)
        loss = model.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt)
        losses.append(float(loss))
    # the SPMD engines must have REFUSED this model (loud fallback) ...
    assert model._engine is False, "engine merged mask-differing stages"
    # ... and the fallback numerics are exact
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)


def test_tensor_parallel_wrapper_preserves_mp_sharding():
    """TensorParallel must not reshard mp-placed weights back to
    replicated (DataParallel's blanket replication did), while still
    replicating plain params."""
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.meta_parallel import TensorParallel

    _reset_mesh(Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                     ("dp", "mp")))
    mesh = dist.env.global_mesh()
    model = nn.Sequential(nn.Linear(4, 8), nn.Linear(8, 2))
    # place one weight on the mp axis by hand (mp_layers' role)
    w = model[0].weight
    w._value = jax.device_put(w._value,
                              NamedSharding(mesh, P(None, "mp")))
    tp = TensorParallel(model)
    assert not model[0].weight._value.sharding.is_fully_replicated, \
        "mp-sharded weight was clobbered back to replicated"
    assert model[1].weight._value.sharding.is_fully_replicated
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    out = tp(x)
    assert list(out.shape) == [8, 2]
