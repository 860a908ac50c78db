"""Hidden dropout drawn inside the LayerNorm-residual kernels: the
kernels against the float32 composite under their own written-out
mask (interpret mode: the hash stands in for the chip's generator),
and the op that carries them through the generator, the three tiers,
`clone(for_test=True)`, AMP and the models."""
import collections
import contextlib
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu import static
from paddle_tpu.framework.random import default_generator
from paddle_tpu.models import BertConfig
from paddle_tpu.models.bert import BertForMaskedLM
from paddle_tpu.nn.functional.flash_attention import _kernel_seed
from paddle_tpu.ops import pallas_fused as pf
from paddle_tpu.ops import pallas_gate
from paddle_tpu.ops import pallas_kernels as pk

P = 0.1


def _inputs(rows, n, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = (jax.random.normal(ks[0], (rows, n)) * 2).astype(dtype)
    r = jax.random.normal(ks[1], (rows, n)).astype(dtype)
    g = (jax.random.normal(ks[2], (n,)) + 1).astype(dtype)
    b = jax.random.normal(ks[3], (n,)).astype(dtype)
    return x, r, g, b


def _masked_ref(x, r, g, b, keep, p, eps=1e-5):
    """The float32 composite under an explicit mask, rounded to the
    stream's dtype where the kernel rounds (the output)."""
    f32 = jnp.float32
    s = jnp.where(keep, x.astype(f32) / (1.0 - p), 0.0) + r.astype(f32)
    mu = jnp.mean(s, -1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), -1, keepdims=True)
    out = (s - mu) * jax.lax.rsqrt(var + eps)
    return (out * g.astype(f32) + b.astype(f32)).astype(x.dtype)


# -- the kernels ---------------------------------------------------------
def test_keep_rate_at_bert_size():
    """8,192 x 768 words: the share kept is within 3 sigma of 1 - p."""
    with jax.enable_x64(False):
        keep = pf.layer_norm_residual_dropout_keep(
            jnp.array([20261001], jnp.int32), 8192, 768, P)
    assert keep.shape == (8192, 768) and keep.dtype == bool
    sigma = math.sqrt(P * (1 - P) / keep.size)
    assert abs(float(jnp.mean(keep, dtype=jnp.float32)) - (1 - P)) \
        < 3 * sigma


def test_same_seed_same_mask_another_seed_another():
    with jax.enable_x64(False):
        a, again, other = (pf.layer_norm_residual_dropout_keep(
            jnp.array([s], jnp.int32), 600, 768, P) for s in (7, 7, 8))
        # row blocks draw different tiles of the stream
        br = pf._ln_res_block_rows(600, 768, True)
    np.testing.assert_array_equal(a, again)
    both = float(jnp.mean(a & other, dtype=jnp.float32))
    assert abs(both - (1 - P) ** 2) < 0.01          # independent draws
    assert br == 256 and not np.array_equal(a[:88], a[br:br + 88])


@pytest.mark.parametrize("dtype,tol,gtol", [
    (jnp.float32, dict(atol=1e-5, rtol=1e-5), dict(atol=2e-4, rtol=1e-4)),
    (jnp.bfloat16, dict(atol=3e-2, rtol=3e-2), dict(atol=1.5e-1, rtol=6e-2)),
])
@pytest.mark.parametrize("what", ["out", "d_x", "d_residual", "d_gamma",
                                  "d_beta"])
def test_dropout_kernels_match_masked_composite(dtype, tol, gtol, what):
    """Forward and the four gradients equal the float32 composite under
    the kernel's own mask; 600 rows are no multiple of the block (256
    rows of float32, 512 of bfloat16 at this width), so the last tile
    is part padding."""
    rows, n = 600, 768
    args = _inputs(rows, n, dtype)
    seed = jnp.array([1234], jnp.int32)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    with jax.enable_x64(False):
        assert 0 < rows % pf._ln_res_block_rows(rows, n, True, dtype) < rows
        keep = pf.layer_norm_residual_dropout_keep(seed, rows, n, P, dtype)
        fused = lambda *a: pf.fused_layer_norm_residual(  # noqa: E731
            *a, dropout_p=P, seed=seed)
        ref = lambda *a: _masked_ref(*a, keep, P)  # noqa: E731
        if what == "out":
            out = fused(*args)
            assert out.dtype == dtype
            np.testing.assert_allclose(f32(out), f32(ref(*args)), **tol)
            return
        i = ["d_x", "d_residual", "d_gamma", "d_beta"].index(what)
        loss = lambda f: lambda *a: jnp.sum(  # noqa: E731
            jnp.sin(f(*a).astype(jnp.float32)))
        got = jax.grad(loss(fused), i)(*args)
        want = jax.grad(loss(ref), i)(*args)
    assert got.dtype == dtype
    np.testing.assert_allclose(f32(got), f32(want), **gtol)
    if what == "d_x":       # a dropped element passes no gradient
        assert not f32(got)[~np.asarray(keep)].any()


def test_d_x_is_d_residual_under_the_mask():
    args = _inputs(600, 768, jnp.float32)
    seed = jnp.array([5], jnp.int32)
    with jax.enable_x64(False):
        keep = pf.layer_norm_residual_dropout_keep(seed, 600, 768, 0.5)
        dx, dr = jax.grad(lambda *a: jnp.sum(pf.fused_layer_norm_residual(
            *a, dropout_p=0.5, seed=seed) ** 2), (0, 1))(*args)
    np.testing.assert_allclose(dx, jnp.where(keep, dr * 2.0, 0.0),
                               rtol=1e-6)


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


def test_plain_form_traces_no_prng(monkeypatch):
    """dropout_p == 0: no generator primitive and no hash in either
    kernel; above 0, on the chip's path: prng_seed and prng_random_bits
    once in each."""
    for mod in (pk, pf):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    jax.clear_caches()      # the jitted builders key on shapes only
    args = _inputs(64, 256, jnp.bfloat16)
    seed = jnp.array([1], jnp.int32)

    def grads(p):
        return _primitives(jax.make_jaxpr(jax.grad(
            lambda *a: pf.fused_layer_norm_residual(
                *a, dropout_p=p, seed=seed if p else None).astype(
                    jnp.float32).sum(), (0, 1, 2, 3)))(*args).jaxpr,
            collections.Counter())

    with jax.enable_x64(False):
        plain, dropped = grads(0.0), grads(0.1)
    jax.clear_caches()
    assert plain["pallas_call"] == dropped["pallas_call"] == 2
    assert not {"prng_seed", "prng_random_bits", "shift_right_logical",
                "random_bits", "threefry2x32"} & set(plain)
    assert dropped["prng_seed"] == dropped["prng_random_bits"] == 2


# sha256 of the StableHLO that forward plus backward of the plain form
# lower to on the CPU (interpret mode) at the parent commit, cb9b695
_PARENT_PLAIN_STABLEHLO = {
    "bfloat16": "8f8c5fcd53e2d8312fc10a66b4c718c2"
                "b5bc100be308cee02d29ab08c721975f",
    "float32": "4f61c92c215a2843cc7f0a194c5f86de"
               "fc7852490e095966f91f44fec9035016",
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_plain_form_lowers_to_the_parents_program(dtype):
    """At dropout_p == 0 `fused_layer_norm_residual` is the program it
    was before the dropout form existed: every eval() path runs it."""
    avals = [jax.ShapeDtypeStruct((300, 256), dtype)] * 2 \
        + [jax.ShapeDtypeStruct((256,), dtype)] * 2
    with jax.enable_x64(False):
        text = jax.jit(jax.grad(
            lambda *a: pf.fused_layer_norm_residual(*a).astype(
                jnp.float32).sum(), (0, 1, 2, 3))).lower(*avals).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_PLAIN_STABLEHLO[dtype]


def test_dropout_needs_a_seed_and_a_rate_below_one():
    args = _inputs(16, 128, jnp.float32)
    with pytest.raises(ValueError, match="needs a seed"):
        pf.fused_layer_norm_residual(*args, dropout_p=0.1)
    with pytest.raises(ValueError, match=r"\[0, 1\)"):
        pf.fused_layer_norm_residual(*args, dropout_p=1.0,
                                     seed=jnp.array([1], jnp.int32))


@pytest.mark.parametrize("rows,n,dtype,dropout,want", [
    (8192, 768, "float32", False, 256),     # as before the dropout form
    (8192, 768, "bfloat16", False, 256),
    (8192, 768, "float32", True, 256),      # the BERT cells under amp O1
    (8192, 768, "bfloat16", True, 512),     # 768 KiB a streamed block
    (8192, 1024, "bfloat16", True, 384),
    (8192, 4096, "float32", True, 48),
    (600, 768, "bfloat16", True, 512), (20, 768, "float32", True, 32),
])
def test_block_rows_one_source(rows, n, dtype, dropout, want):
    """Forward plan, backward plan and the keep writer number their
    tiles from the same row-block size, the sweep's for the dropout
    form."""
    assert pf._ln_res_block_rows(rows, n, dropout, dtype) == want
    plans = [pf.ln_residual_block_plan(rows, n, dtype, d, dropout)
             for d in ("fwd", "bwd")]
    assert {p["block_rows"] for p in plans} == {want}
    assert plans[0]["grid"] == plans[1]["grid"] == (-(-rows // want),)


# -- the op ---------------------------------------------------------------
@pytest.fixture
def gate_open(monkeypatch):
    """The kernel gate as on a TPU whose probes passed (the kernels
    then run in interpret mode here), with counters on."""
    monkeypatch.setattr(pallas_gate, "pallas_enabled",
                        lambda kernel, manual=False: True)
    with _counting() as counts:
        yield counts
    paddle.disable_static()


@contextlib.contextmanager
def _counting():
    prev = obs.enable(True)
    obs.get_registry().clear()
    try:
        yield lambda: {
            k[len("layer_norm_residual.path."):]: v for k, v in
            obs.get_registry().snapshot()["counters"].items()
            if k.startswith("layer_norm_residual.path.")}
    finally:
        obs.get_registry().clear()
        obs.enable(prev)


def _state():
    return np.asarray(default_generator().state_tensor._value).copy()


def _seed_of_next_call():
    _, sub = jax.random.split(default_generator().state_tensor._value)
    return np.asarray(_kernel_seed(sub))


def _tensors(shape=(2, 24, 128), dtype="float32"):
    x, r, g, b = _inputs(shape[0] * shape[1], shape[2], jnp.float32)
    return [paddle.to_tensor(np.asarray(t).reshape(s)).astype(dtype)
            for t, s in ((x, shape), (r, shape), (g, shape[-1:]),
                         (b, shape[-1:]))]


def test_op_takes_the_kernel_under_the_seeds_mask(gate_open):
    """With the gate open the op runs the dropout kernels under the
    mask `layer_norm_residual_dropout_keep` writes out for the seed the
    call derived; same paddle.seed, same output; backward through the
    tape equals the masked composite's."""
    x, r, g, b = _tensors()
    for t in (x, r, g, b):
        t.stop_gradient = False
    paddle.seed(11)
    seed = _seed_of_next_call()
    out = F.fused_residual_layer_norm(x, r, 128, g, b, dropout_p=0.5)
    (out ** 2).sum().backward()
    paddle.seed(11)
    again = F.fused_residual_layer_norm(x, r, 128, g, b, dropout_p=0.5)
    other = F.fused_residual_layer_norm(x, r, 128, g, b, dropout_p=0.5)
    np.testing.assert_array_equal(out.numpy(), again.numpy())
    assert not np.allclose(out.numpy(), other.numpy())
    assert gate_open() == {"dropout": 3}
    vals = [t._value.reshape(-1, 128) if t.ndim == 3 else t._value
            for t in (x, r, g, b)]
    with jax.enable_x64(False):
        keep = pf.layer_norm_residual_dropout_keep(seed, 48, 128, 0.5)
        ref = _masked_ref(*vals, keep, 0.5)
        want = jax.grad(lambda *a: jnp.sum(
            _masked_ref(*a, keep, 0.5) ** 2), (0, 1, 2, 3))(*vals)
    np.testing.assert_allclose(out.numpy().reshape(-1, 128),
                               np.asarray(ref), atol=1e-5, rtol=1e-5)
    for t, w in zip((x, r, g, b), want):
        np.testing.assert_allclose(t.grad.numpy().reshape(w.shape),
                                   np.asarray(w), atol=2e-4, rtol=1e-4)


def test_composite_draws_one_mask_from_the_calls_key():
    """Off the TPU (gate closed) the same op draws a bernoulli mask
    from its sub-key: what F.dropout then the plain op computed."""
    x, r, g, b = _tensors()
    with _counting() as counts:
        paddle.seed(3)
        fused = F.fused_residual_layer_norm(x, r, 128, g, b, dropout_p=0.3)
        paddle.seed(3)
        two_ops = F.fused_residual_layer_norm(
            F.dropout(x, 0.3), r, 128, g, b)
        assert counts() == {"composite.gate": 2}
    np.testing.assert_allclose(fused.numpy(), two_ops.numpy(), atol=1e-6)


@pytest.mark.parametrize("case,expect", [
    ("train", {"dropout": 1}),
    ("rate_zero", {"plain": 1}),
    ("eval", {"plain": 1}),
    ("no_bias", {"composite.shape": 1}),
    ("two_axes", {"composite.shape": 1}),
    ("downscale_in_infer", {"plain": 1}),
])
def test_path_counts(gate_open, case, expect):
    """One count a build, under the path taken: the draw rides in the
    kernel only for plain upscale-in-train dropout over a norm the
    kernel takes; anything else keeps today's two ops."""
    x, r, g, b = _tensors()
    kw = dict(dropout_p=0.0 if case == "rate_zero" else 0.1,
              training=case != "eval")
    if case == "downscale_in_infer":
        kw["mode"] = case
    s0 = (paddle.seed(1), _state())[1]
    if case == "no_bias":
        F.fused_residual_layer_norm(x, r, 128, g, None, **kw)
    elif case == "two_axes":
        w = paddle.ones([24, 128])
        F.fused_residual_layer_norm(x, r, [24, 128], w, w, **kw)
    else:
        F.fused_residual_layer_norm(x, r, 128, g, b, **kw)
    assert gate_open() == expect
    # a draw happened unless dropout is off
    assert (_state() == s0).all() == (case in ("rate_zero", "eval"))


def test_gate_closed_asks_for_the_dropout_probe(monkeypatch):
    asked = []
    monkeypatch.setattr(pallas_gate, "pallas_enabled",
                        lambda k, manual=False: asked.append(k))
    x, r, g, b = _tensors()
    with _counting() as counts:
        F.fused_residual_layer_norm(x, r, 128, g, b)
        F.fused_residual_layer_norm(x, r, 128, g, b, dropout_p=0.1)
        assert counts() == {"composite.gate": 2}
    assert asked == ["layer_norm_residual", "layer_norm_residual_dropout"]
    assert "layer_norm_residual_dropout" in pallas_gate._PROBES


def test_dropout_probe_compiles_forward_and_backward():
    pallas_gate.reset_probe_cache()
    try:
        res = pallas_gate.probe_kernel("layer_norm_residual_dropout",
                                       force=True)
        assert res.ok, res.error
    finally:
        pallas_gate.reset_probe_cache()


@pytest.mark.parametrize("tier", ["eager", "lazy", "static"])
def test_generator_advances_once_a_call(gate_open, tier):
    """The generator's state moves exactly as the F.dropout call it
    replaces moved it, in every tier."""
    x, r, g, b = _tensors()
    paddle.seed(5)
    s0 = _state()
    F.dropout(x, p=0.5).numpy()
    one_call = _state()
    assert not (s0 == one_call).all()
    paddle.seed(5)
    if tier == "static":
        paddle.enable_static()
        main = static.Program()
        with static.program_guard(main):
            xv = static.data("x", [2, 24, 128], "float32")
            y = F.fused_residual_layer_norm(xv, xv, 128, g, b,
                                            dropout_p=0.5)
        exe = static.Executor()
        fd = {"x": x.numpy()}
        (a,) = exe.run(main, feed=fd, fetch_list=[y])
        np.testing.assert_array_equal(_state(), one_call)
        (c,) = exe.run(main, feed=fd, fetch_list=[y])
        assert not (a == c).all(), "same hidden-dropout mask every run"
        assert gate_open() == {"dropout": 1}            # one build
    else:
        cm = paddle.incubate.lazy_eager() if tier == "lazy" else \
            contextlib.nullcontext()
        with cm:
            F.fused_residual_layer_norm(x, r, 128, g, b,
                                        dropout_p=0.5).numpy()
        np.testing.assert_array_equal(_state(), one_call)


def test_clone_for_test_drops_the_draw(gate_open):
    paddle.enable_static()
    paddle.seed(0)
    x, r, g, b = _tensors()
    main = static.Program()
    with static.program_guard(main):
        xv = static.data("x", [2, 24, 128], "float32")
        y = F.fused_residual_layer_norm(xv, xv, 128, g, b, dropout_p=0.5)
    test_prog = main.clone(for_test=True)
    exe = static.Executor()
    fd = {"x": x.numpy()}
    (a,) = exe.run(test_prog, feed=fd, fetch_list=[y])
    (c,) = exe.run(test_prog, feed=fd, fetch_list=[y])
    np.testing.assert_array_equal(a, c)
    paddle.disable_static()
    np.testing.assert_allclose(
        a, F.fused_residual_layer_norm(x, x, 128, g, b).numpy(), atol=1e-6)
    paddle.enable_static()
    (d,) = exe.run(main, feed=fd, fetch_list=[y])
    (e,) = exe.run(main, feed=fd, fetch_list=[y])
    assert not (d == e).all()


def test_amp_o1_gives_the_op_float32(gate_open):
    """Black-listed as the plain op is: under O1 the bfloat16 sublayer
    output is cast up before the draw, and the op returns float32."""
    x, r, g, b = _tensors()
    with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
        out = F.fused_residual_layer_norm(x.astype("bfloat16"), r, 128, g,
                                          b, dropout_p=0.1)
    assert out.dtype == paddle.float32
    assert gate_open() == {"dropout": 1}


# -- the models -------------------------------------------------------------
_TINY = BertConfig(vocab_size=512, hidden_size=128, num_hidden_layers=12,
                   num_attention_heads=2, intermediate_size=256,
                   max_position_embeddings=64)


def _bert_ids():
    return paddle.to_tensor(np.random.RandomState(0).randint(
        0, 512, (2, 32)).astype(np.int64))


def test_bert_path_counts_train_eval_and_closed_gate(gate_open,
                                                     monkeypatch):
    """A 12-layer BERT build: 24 `dropout` training, 24 `plain` in
    eval(); with the gate closed, 24 composites."""
    paddle.seed(0)
    model = BertForMaskedLM(_TINY)
    model.train()
    model(_bert_ids())
    assert gate_open() == {"dropout": 24}
    obs.get_registry().clear()
    model.eval()
    model(_bert_ids())
    assert gate_open() == {"plain": 24}
    obs.get_registry().clear()
    monkeypatch.setattr(pallas_gate, "pallas_enabled",
                        lambda kernel, manual=False: False)
    model.train()
    model(_bert_ids())
    assert gate_open() == {"composite.gate": 24}


def test_bert_step_advances_the_generator_as_before():
    """25 hidden-dropout draws and 12 attention draws a forward, as with
    nn.Dropout in front of the norm: the state after one training
    forward equals the state after 37 single draws."""
    paddle.seed(0)
    model = BertForMaskedLM(_TINY)
    model.train()
    paddle.seed(9)
    model(_bert_ids())
    after_model = _state()
    paddle.seed(9)
    t = paddle.ones([2, 2])
    for _ in range(25 + 12):
        F.dropout(t, 0.5)
    np.testing.assert_array_equal(after_model, _state())


@pytest.mark.parametrize("layer", ["encoder", "decoder"])
def test_transformer_post_norm_passes_its_rate(gate_open, layer):
    """The post-norm branches hand dropout1/2/3's rate to the norm; a
    Dropout the kernel cannot stand for runs as its own op."""
    x = paddle.to_tensor(np.random.RandomState(1).randn(
        2, 16, 128).astype(np.float32))
    paddle.seed(2)
    if layer == "encoder":
        m, n_norms = nn.TransformerEncoderLayer(128, 2, 256, dropout=0.1), 2
        run = lambda: m(x)  # noqa: E731
    else:
        m, n_norms = nn.TransformerDecoderLayer(128, 2, 256, dropout=0.1), 3
        run = lambda: m(x, x)  # noqa: E731
    m.train()
    run()
    assert gate_open() == {"dropout": n_norms}
    obs.get_registry().clear()
    m.eval()
    a, b = run().numpy(), run().numpy()
    np.testing.assert_array_equal(a, b)
    assert gate_open() == {"plain": 2 * n_norms}
    obs.get_registry().clear()
    m.train()
    m.dropout1.axis = 0                 # a mask shared along an axis
    run()
    assert gate_open() == {"dropout": n_norms - 1, "plain": 1}


def test_incubate_fused_bias_dropout_residual_layer_norm(gate_open):
    """Upstream's public name for the fusion: function and layer, over
    the same op."""
    from paddle_tpu.incubate.nn import FusedBiasDropoutResidualLayerNorm
    from paddle_tpu.incubate.nn.functional import (
        fused_bias_dropout_residual_layer_norm as fused)
    x, r, g, b = _tensors()
    bias = paddle.to_tensor(np.linspace(-1, 1, 128).astype(np.float32))
    out = fused(x, r, bias, g, b, dropout_rate=0.0)
    np.testing.assert_allclose(
        out.numpy(),
        F.fused_residual_layer_norm(x + bias, r, 128, g, b).numpy(),
        atol=1e-6)
    paddle.seed(4)
    seed = _seed_of_next_call()
    dropped = fused(x, r, bias, g, b, dropout_rate=0.5)
    with jax.enable_x64(False):
        keep = pf.layer_norm_residual_dropout_keep(seed, 48, 128, 0.5)
        ref = _masked_ref((x + bias)._value.reshape(48, 128),
                          r._value.reshape(48, 128), g._value, b._value,
                          keep, 0.5)
    np.testing.assert_allclose(dropped.numpy().reshape(48, 128),
                               np.asarray(ref), atol=1e-5, rtol=1e-5)
    layer = FusedBiasDropoutResidualLayerNorm(128, dropout_rate=0.5)
    assert sorted(n for n, _ in layer.named_parameters()) == [
        "linear_bias", "ln_bias", "ln_scale"]
    layer.eval()
    np.testing.assert_allclose(
        layer(x, r).numpy(),
        F.fused_residual_layer_norm(x, r, 128, layer.ln_scale,
                                    layer.ln_bias).numpy(), atol=1e-6)
    assert gate_open() == {"plain": 4, "dropout": 1}
