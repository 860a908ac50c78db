import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


def test_linear():
    lin = nn.Linear(4, 3)
    x = paddle.randn([2, 4])
    y = lin(x)
    assert y.shape == [2, 3]
    np.testing.assert_allclose(
        y.numpy(), x.numpy() @ lin.weight.numpy() + lin.bias.numpy(),
        rtol=1e-5, atol=1e-5)


def test_conv2d():
    conv = nn.Conv2D(3, 8, 3, stride=1, padding=1)
    x = paddle.randn([2, 3, 16, 16])
    y = conv(x)
    assert y.shape == [2, 8, 16, 16]
    y.sum().backward()
    assert conv.weight.grad is not None


def test_conv2d_vs_numpy():
    # 1x1 conv is a matmul over channels
    conv = nn.Conv2D(4, 2, 1, bias_attr=False)
    x = paddle.randn([1, 4, 5, 5])
    y = conv(x)
    w = conv.weight.numpy().reshape(2, 4)
    ref = np.einsum("oc,nchw->nohw", w, x.numpy())
    np.testing.assert_allclose(y.numpy(), ref, rtol=1e-4, atol=1e-5)


def test_conv_transpose():
    deconv = nn.Conv2DTranspose(4, 2, 2, stride=2)
    x = paddle.randn([1, 4, 8, 8])
    y = deconv(x)
    assert y.shape == [1, 2, 16, 16]


def test_pools():
    x = paddle.randn([2, 3, 8, 8])
    assert F.max_pool2d(x, 2, 2).shape == [2, 3, 4, 4]
    assert F.avg_pool2d(x, 2, 2).shape == [2, 3, 4, 4]
    assert F.adaptive_avg_pool2d(x, 1).shape == [2, 3, 1, 1]
    np.testing.assert_allclose(
        F.adaptive_avg_pool2d(x, 1).numpy()[..., 0, 0],
        x.numpy().mean((2, 3)), rtol=1e-5)


def test_batchnorm_train_eval():
    bn = nn.BatchNorm2D(4)
    x = paddle.randn([8, 4, 5, 5])
    bn.train()
    y = bn(x)
    # training output is normalized per-batch
    np.testing.assert_allclose(y.numpy().mean((0, 2, 3)), np.zeros(4),
                               atol=1e-5)
    # running stats moved toward batch stats
    assert not np.allclose(bn._mean.numpy(), np.zeros(4))
    bn.eval()
    y2 = bn(x)
    assert y2.shape == [8, 4, 5, 5]


def test_layernorm_affine():
    ln = nn.LayerNorm(8)
    x = paddle.randn([4, 8])
    y = ln(x)
    np.testing.assert_allclose(y.numpy().mean(-1), np.zeros(4), atol=1e-5)
    y.sum().backward()
    assert ln.weight.grad is not None


def test_embedding():
    emb = nn.Embedding(10, 4)
    idx = paddle.to_tensor([[1, 2], [3, 4]])
    y = emb(idx)
    assert y.shape == [2, 2, 4]
    y.sum().backward()
    assert emb.weight.grad is not None


def test_dropout_modes():
    do = nn.Dropout(0.5)
    x = paddle.ones([100, 100])
    do.train()
    y = do(x)
    frac_zero = float((y.numpy() == 0).mean())
    assert 0.3 < frac_zero < 0.7
    do.eval()
    np.testing.assert_allclose(do(x).numpy(), x.numpy())


def test_activations():
    x = paddle.to_tensor([-1.0, 0.0, 2.0])
    np.testing.assert_allclose(F.relu(x).numpy(), [0, 0, 2])
    np.testing.assert_allclose(F.sigmoid(x).numpy(),
                               1 / (1 + np.exp(-x.numpy())), rtol=1e-5)
    np.testing.assert_allclose(F.leaky_relu(x, 0.1).numpy(),
                               [-0.1, 0, 2], rtol=1e-5)
    assert F.gelu(x).shape == [3]
    assert F.softmax(x).numpy().sum() == pytest.approx(1.0, rel=1e-5)


def test_losses():
    logits = paddle.randn([4, 10])
    labels = paddle.to_tensor([1, 2, 3, 4])
    loss = F.cross_entropy(logits, labels)
    assert loss.shape == []
    lp = np.log(np.exp(logits.numpy()) /
                np.exp(logits.numpy()).sum(-1, keepdims=True))
    ref = -lp[np.arange(4), labels.numpy()].mean()
    np.testing.assert_allclose(loss.numpy(), ref, rtol=1e-5)
    np.testing.assert_allclose(
        F.mse_loss(logits, paddle.zeros_like(logits)).numpy(),
        (logits.numpy() ** 2).mean(), rtol=1e-5)


def test_cross_entropy_ignore_index():
    logits = paddle.randn([4, 5])
    labels = paddle.to_tensor([1, -100, 3, -100])
    loss = F.cross_entropy(logits, labels, ignore_index=-100)
    lp = np.log(np.exp(logits.numpy()) /
                np.exp(logits.numpy()).sum(-1, keepdims=True))
    ref = -(lp[0, 1] + lp[2, 3]) / 2
    np.testing.assert_allclose(loss.numpy(), ref, rtol=1e-5)


@pytest.fixture
def xent_paths():
    """`softmax_cross_entropy.path.*` as counted inside the test, with
    the observability gate on."""
    from paddle_tpu import observability as obs
    prev = obs.enable(True)
    obs.get_registry().clear()
    yield lambda: {
        k[len("softmax_cross_entropy.path."):]: v for k, v in
        obs.get_registry().snapshot()["counters"].items()
        if k.startswith("softmax_cross_entropy.path.")}
    obs.get_registry().clear()
    obs.enable(prev)


@pytest.mark.parametrize("case,expect", [
    ("hard_label", "fused"),
    ("soft_label", "composite.soft_label"),
    ("weight", "composite.weight"),
    ("label_smoothing", "composite.label_smoothing"),
    ("no_softmax", "composite.no_softmax"),
    ("axis", "composite.axis"),
    ("dtype", "composite.dtype"),
    ("vocab_cap", "composite.vocab_cap"),
    ("gate", "composite.gate"),
])
def test_cross_entropy_path_counts(monkeypatch, xent_paths, case, expect):
    """One count a build, under the path taken and, for the composite,
    why: the kernel only for hard labels over the last axis through an
    open gate.  Both paths give the same loss."""
    from paddle_tpu.ops import pallas_gate
    monkeypatch.setattr(pallas_gate, "pallas_enabled",
                        lambda kernel, manual=False: case != "gate")
    rng = np.random.RandomState(0)
    logits = paddle.to_tensor(rng.randn(6, 10).astype(np.float32))
    labels = paddle.to_tensor(np.array([1, 2, -100, 4, 9, 0]))
    kw = {}
    if case == "soft_label":
        labels = F.softmax(paddle.to_tensor(
            rng.randn(6, 10).astype(np.float32)))
        kw["soft_label"] = True
    elif case == "weight":
        kw["weight"] = paddle.to_tensor(
            rng.rand(10).astype(np.float32))
    elif case == "label_smoothing":
        kw["label_smoothing"] = 0.1
    elif case == "no_softmax":
        logits, kw["use_softmax"] = F.softmax(logits), False
    elif case == "axis":
        logits, kw["axis"] = logits.transpose([1, 0]), 0
    elif case == "dtype":
        logits = logits.astype("float64")
    elif case == "vocab_cap":
        logits = paddle.to_tensor(
            rng.randn(6, 128 * 1024 + 1).astype(np.float32))
    loss = F.cross_entropy(logits, labels, **kw)
    assert xent_paths() == {expect: 1}
    if case in ("hard_label", "gate", "dtype"):
        x = logits.numpy().astype(np.float64)
        lp = x - np.log(np.exp(x).sum(-1, keepdims=True))
        keep = [0, 1, 3, 4, 5]
        ref = -lp[keep, labels.numpy()[keep]].mean()
        np.testing.assert_allclose(loss.numpy(), ref, rtol=1e-5)


def test_cross_entropy_path_counts_nothing_with_observability_off():
    from paddle_tpu import observability as obs
    prev = obs.enable(False)
    obs.get_registry().clear()
    try:
        F.cross_entropy(paddle.randn([4, 10]),
                        paddle.to_tensor([1, 2, 3, 4]))
        assert not any(obs.get_registry().snapshot()["counters"].values())
    finally:
        obs.enable(prev)


def test_sequential_layerlist():
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    y = model(paddle.randn([3, 4]))
    assert y.shape == [3, 2]
    assert len(model) == 3
    ll = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
    assert len(ll) == 3
    assert len(list(ll[0].parameters())) == 2


def test_state_dict_roundtrip():
    m1 = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
    m2 = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
    m2.set_state_dict(m1.state_dict())
    x = paddle.randn([2, 4])
    np.testing.assert_allclose(m1(x).numpy(), m2(x).numpy(), rtol=1e-6)


def test_named_parameters():
    model = nn.Sequential(nn.Linear(2, 2), nn.Linear(2, 2))
    names = [n for n, _ in model.named_parameters()]
    assert "0.weight" in names and "1.bias" in names
    assert len(model.parameters()) == 4


def test_multihead_attention():
    mha = nn.MultiHeadAttention(16, 4)
    x = paddle.randn([2, 5, 16])
    y = mha(x, x, x)
    assert y.shape == [2, 5, 16]
    y.sum().backward()
    assert mha.q_proj.weight.grad is not None


def test_transformer_encoder():
    layer = nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0)
    enc = nn.TransformerEncoder(layer, 2)
    x = paddle.randn([2, 5, 16])
    y = enc(x)
    assert y.shape == [2, 5, 16]
    # stacked layers must have independent params
    p0 = enc.layers[0].linear1.weight
    p1 = enc.layers[1].linear1.weight
    assert p0 is not p1


def test_lstm():
    lstm = nn.LSTM(8, 16, num_layers=2)
    x = paddle.randn([4, 6, 8])
    y, (h, c) = lstm(x)
    assert y.shape == [4, 6, 16]
    assert h.shape == [2, 4, 16]
    y.sum().backward()


def test_gru_bidirect():
    gru = nn.GRU(8, 16, direction="bidirect")
    x = paddle.randn([2, 5, 8])
    y, h = gru(x)
    assert y.shape == [2, 5, 32]
    assert h.shape == [2, 2, 16]


def test_sdpa():
    q = paddle.randn([2, 5, 4, 8])
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    assert out.shape == [2, 5, 4, 8]
    # causality: first position attends only to itself
    k = paddle.randn([2, 5, 4, 8])
    v = paddle.randn([2, 5, 4, 8])
    o1 = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    v2 = v.clone()
    v2[:, 4] = paddle.zeros([2, 4, 8])  # change last position value
    o2 = F.scaled_dot_product_attention(q, k, v2, is_causal=True)
    np.testing.assert_allclose(o1[:, 0].numpy(), o2[:, 0].numpy(),
                               rtol=1e-5)


def test_clip_grad_global_norm():
    p = nn.Parameter(np.ones(4, np.float32) * 2)
    (p * paddle.to_tensor([10., 10., 10., 10.])).sum().backward()
    clip = paddle.ClipGradByGlobalNorm(1.0)
    clip([p])
    total = np.linalg.norm(p.grad.numpy())
    np.testing.assert_allclose(total, 1.0, rtol=1e-4)


def test_embedding_out_of_range_raises():
    emb = nn.Embedding(10, 4)
    with pytest.raises(ValueError, match="ids must be in"):
        emb(paddle.to_tensor(np.array([3, 10], np.int64)))
    with pytest.raises(ValueError, match="ids must be in"):
        emb(paddle.to_tensor(np.array([-1, 2], np.int64)))
    emb(paddle.to_tensor(np.array([0, 9], np.int64)))  # bounds OK


def test_round3_layer_fills():
    # Unflatten / PairwiseDistance / ChannelShuffle / losses / clip names
    u = nn.Unflatten(1, [2, 3])
    assert tuple(u(paddle.to_tensor(
        np.zeros((4, 6), np.float32))).shape) == (4, 2, 3)
    d = nn.PairwiseDistance()(
        paddle.to_tensor(np.array([[3.0, 4.0]], np.float32)),
        paddle.to_tensor(np.array([[0.0, 0.0]], np.float32)))
    np.testing.assert_allclose(d.numpy(), [5.0], rtol=1e-4)
    cs = nn.ChannelShuffle(2)
    assert tuple(cs(paddle.to_tensor(
        np.zeros((1, 4, 2, 2), np.float32))).shape) == (1, 4, 2, 2)
    h = nn.HuberLoss(delta=1.0)(
        paddle.to_tensor(np.array([0.0], np.float32)),
        paddle.to_tensor(np.array([3.0], np.float32)))
    np.testing.assert_allclose(float(np.asarray(h.numpy())), 2.5,
                               rtol=1e-6)
    g = nn.GaussianNLLLoss()(
        paddle.to_tensor(np.array([0.0], np.float32)),
        paddle.to_tensor(np.array([1.0], np.float32)),
        paddle.to_tensor(np.array([1.0], np.float32)))
    assert np.isfinite(float(np.asarray(g.numpy())))
    assert nn.ClipGradByGlobalNorm is paddle.ClipGradByGlobalNorm


def test_max_unpool2d_roundtrip():
    x = np.zeros((1, 1, 4, 4), np.float32)
    x[0, 0, 1, 1] = 5.0
    x[0, 0, 2, 3] = 7.0
    t = paddle.to_tensor(x)
    pooled, idx = paddle.nn.functional.max_pool2d(t, 2, return_mask=True)
    unpooled = paddle.nn.functional.max_unpool2d(pooled, idx, 2).numpy()
    assert unpooled[0, 0, 1, 1] == 5.0
    assert unpooled[0, 0, 2, 3] == 7.0
    assert unpooled.sum() >= 12.0  # maxima land back at their positions
    layer = nn.MaxUnPool2D(2)
    np.testing.assert_allclose(layer(pooled, idx).numpy(), unpooled)


def test_max_unpool2d_requires_output_size_when_lossy():
    x = np.zeros((1, 1, 5, 5), np.float32)
    x[0, 0, 2, 3] = 9.0
    t = paddle.to_tensor(x)
    pooled, idx = paddle.nn.functional.max_pool2d(t, 2, return_mask=True)
    # 5x5 pooled by 2 is lossy: with the true output_size the max lands
    # back exactly where it came from
    out = paddle.nn.functional.max_unpool2d(
        pooled, idx, 2, output_size=[5, 5]).numpy()
    assert out[0, 0, 2, 3] == 9.0


def test_nn_layer_fills_round4():
    """Round-4 fills: Softmax2D, MaxUnPool1D/3D, MultiMarginLoss,
    TripletMarginWithDistanceLoss, HSigmoidLoss, BeamSearchDecoder."""
    rng = np.random.RandomState(0)

    # Softmax2D: channel-dim softmax on NCHW
    x = paddle.to_tensor(rng.randn(2, 3, 4, 4).astype(np.float32))
    s = nn.Softmax2D()(x)
    np.testing.assert_allclose(
        np.asarray(s._value).sum(1), np.ones((2, 4, 4)), rtol=1e-5)

    # MaxUnPool1D/3D round-trip the argmax positions
    import paddle_tpu.nn.functional as F
    x1 = paddle.to_tensor(rng.randn(2, 3, 8).astype(np.float32))
    p1, idx1 = F.max_pool1d(x1, 2, stride=2, return_mask=True)
    up1 = nn.MaxUnPool1D(2, stride=2)(p1, idx1)
    assert up1.shape == [2, 3, 8]
    got = np.asarray(up1._value)
    assert np.allclose(got.max(-1), np.asarray(p1._value).max(-1))

    x3 = paddle.to_tensor(rng.randn(1, 2, 4, 4, 4).astype(np.float32))
    p3, idx3 = F.max_pool3d(x3, 2, stride=2, return_mask=True)
    up3 = nn.MaxUnPool3D(2, stride=2)(p3, idx3)
    assert up3.shape == [1, 2, 4, 4, 4]

    # MultiMarginLoss decreases for a confident correct prediction
    logits = paddle.to_tensor(np.array([[3.0, 0.1, 0.1]], np.float32))
    bad = paddle.to_tensor(np.array([[0.1, 3.0, 0.1]], np.float32))
    lab = paddle.to_tensor(np.array([0], np.int64))
    l_good = float(nn.MultiMarginLoss()(logits, lab))
    l_bad = float(nn.MultiMarginLoss()(bad, lab))
    assert l_good < l_bad

    # TripletMarginWithDistanceLoss with a custom distance
    a = paddle.to_tensor(rng.randn(4, 8).astype(np.float32),
                         stop_gradient=False)
    pos = paddle.to_tensor((np.asarray(a._value)
                            + 0.01 * rng.randn(4, 8)).astype(np.float32))
    neg = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))

    def l1_dist(u, v):
        return paddle.sum(paddle.abs(u - v), axis=-1)

    loss = nn.TripletMarginWithDistanceLoss(
        distance_function=l1_dist, margin=0.5)(a, pos, neg)
    loss.backward()
    assert a.grad is not None

    # HSigmoidLoss trains (loss drops on repeated steps)
    paddle.seed(0)
    hs = nn.HSigmoidLoss(feature_size=8, num_classes=6)
    from paddle_tpu import optimizer as opt_mod
    opt = opt_mod.SGD(learning_rate=0.5, parameters=hs.parameters())
    feats = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    labels = paddle.to_tensor(rng.randint(0, 6, (16, 1)).astype(np.int64))
    losses = []
    for _ in range(10):
        loss = paddle.mean(hs(feats, labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_beam_search_decoder():
    """A cell rigged to always prefer token sequences 2,2,...,end: the
    best beam must find them and report correct lengths."""
    from paddle_tpu.nn import BeamSearchDecoder, dynamic_decode

    V, H = 5, 8
    emb = nn.Embedding(V, H)

    class Cell(nn.SimpleRNNCell):
        pass

    paddle.seed(0)
    cell = Cell(H, H)
    proj = nn.Linear(H, V)
    # bias the projection hard toward token 2, then end (3) after step 2
    with paddle.no_grad():
        b = np.zeros(V, np.float32)
        b[2] = 5.0
        proj.bias.set_value(paddle.to_tensor(b))

    dec = BeamSearchDecoder(cell, start_token=0, end_token=3,
                            beam_size=3,
                            embedding_fn=lambda ids: emb(ids),
                            output_fn=lambda h: proj(h))
    init = cell.get_initial_states(paddle.zeros([2, H]))
    seq, lengths = dynamic_decode(dec, inits=init, max_step_num=4)
    assert seq.shape[0] == 2 and seq.shape[1] == 3
    assert seq.shape[2] <= 4
    best = np.asarray(seq._value)[:, 0, :]
    assert (best[:, 0] == 2).all()  # the biased token wins everywhere


def test_beam_search_scores_are_true_log_probs():
    """r4 review: a dropped '-max' term offset each beam's scores by its
    own max logit, corrupting cross-beam ranking.  With a cell whose
    logits differ in scale per input token, the best beam must still be
    the true max-probability sequence (computed by brute force)."""
    from paddle_tpu.nn import BeamSearchDecoder, dynamic_decode
    import itertools

    V, H = 4, 6
    paddle.seed(3)
    emb = nn.Embedding(V, H)
    cell = nn.SimpleRNNCell(H, H)
    proj = nn.Linear(H, V)

    dec = BeamSearchDecoder(cell, start_token=0, end_token=V - 1,
                            beam_size=4,
                            embedding_fn=lambda ids: emb(ids),
                            output_fn=lambda h: proj(h))
    init = cell.get_initial_states(paddle.zeros([1, H]))
    seq, _ = dynamic_decode(dec, inits=init, max_step_num=2)
    best = tuple(np.asarray(seq._value)[0, 0, :].tolist())

    # brute force all length-2 sequences through the same cell
    def logprobs(tok, state):
        out, new_state = cell(emb(paddle.to_tensor(
            np.array([tok], np.int64))), state)
        logits = np.asarray(proj(out)._value)[0].astype(np.float64)
        lp = logits - logits.max()
        lp = lp - np.log(np.exp(lp).sum())
        return lp, new_state

    scores = {}
    lp0, st0 = logprobs(0, init)
    for t1 in range(V):
        lp1, st1 = logprobs(t1, st0)
        if t1 == V - 1:
            scores[(t1,)] = lp0[t1]
            continue
        for t2 in range(V):
            scores[(t1, t2)] = lp0[t1] + lp1[t2]
    brute = max(scores, key=scores.get)
    assert tuple(best[:len(brute)]) == brute, (best, brute, scores)


def test_transformer_decoder_incremental_cache_parity():
    """Decoder cache protocol: step-by-step decode with gen_cache must
    match the full-sequence forward under a causal mask (cache was
    silently ignored; StaticCache was wrongly re-projected)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    d, h, S = 16, 2, 5
    layer = nn.TransformerDecoderLayer(d, h, 32, dropout=0.0)
    dec = nn.TransformerDecoder(layer, 2)
    dec.eval()
    rng = np.random.default_rng(0)
    tgt = paddle.to_tensor(rng.standard_normal((2, S, d)).astype(np.float32))
    mem = paddle.to_tensor(rng.standard_normal((2, 3, d)).astype(np.float32))

    causal = np.triu(np.full((S, S), -1e9, np.float32), 1)
    full = dec(tgt, mem, tgt_mask=paddle.to_tensor(causal)).numpy()

    caches = dec.gen_cache(mem)
    outs = []
    for t in range(S):
        step = paddle.to_tensor(tgt.numpy()[:, t:t + 1])
        out, caches = dec(step, mem, cache=caches)
        outs.append(out.numpy())
    inc = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(inc, full, rtol=1e-4, atol=1e-5)


def test_transformer_encoder_incremental_cache():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    layer = nn.TransformerEncoderLayer(16, 2, 32, dropout=0.0)
    enc = nn.TransformerEncoder(layer, 2)
    enc.eval()
    rng = np.random.default_rng(1)
    src = paddle.to_tensor(rng.standard_normal((2, 4, 16)).astype(np.float32))
    causal = np.triu(np.full((4, 4), -1e9, np.float32), 1)
    full = enc(src, src_mask=paddle.to_tensor(causal)).numpy()
    caches = enc.gen_cache(src)
    outs = []
    for t in range(4):
        step = paddle.to_tensor(src.numpy()[:, t:t + 1])
        out, caches = enc(step, cache=caches)
        outs.append(out.numpy())
    np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                               rtol=1e-4, atol=1e-5)


def test_rnn_sequence_length_matches_torch_packed():
    """LSTM/GRU with sequence_length: bidirectional outputs match
    torch's pack_padded_sequence reference exactly (state freezing +
    within-length reversal)."""
    import numpy as np
    import torch
    import paddle_tpu as paddle
    from paddle_tpu import nn

    rng = np.random.default_rng(0)
    B, T, I, H = 3, 6, 4, 5
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    lens = np.array([6, 3, 5], np.int64)
    for pcls, tcls in [(nn.LSTM, torch.nn.LSTM), (nn.GRU, torch.nn.GRU)]:
        paddle.seed(0)
        pl = pcls(I, H, direction="bidirect")
        th = tcls(I, H, batch_first=True, bidirectional=True)
        tsd = th.state_dict()
        ours = dict(pl.named_parameters())
        for k in tsd:
            tsd[k] = torch.tensor(ours[k].numpy())
        th.load_state_dict(tsd)
        y, _ = pl(paddle.to_tensor(x),
                  sequence_length=paddle.to_tensor(lens))
        packed = torch.nn.utils.rnn.pack_padded_sequence(
            torch.tensor(x), torch.tensor(lens), batch_first=True,
            enforce_sorted=False)
        ty, _ = th(packed)
        ty, _ = torch.nn.utils.rnn.pad_packed_sequence(
            ty, batch_first=True, total_length=T)
        np.testing.assert_allclose(y.numpy(), ty.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)


def test_rnn_cell_wrapper_sequence_length():
    """The generic RNN(cell) wrapper freezes states and zeroes outputs
    past each sequence's end; final state == state at the true end."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    rng = np.random.default_rng(1)
    B, T, I, H = 2, 5, 3, 4
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    lens = np.array([5, 3], np.int64)
    paddle.seed(2)
    cell = nn.GRUCell(I, H)
    rnn = nn.RNN(cell)
    y, h = rnn(paddle.to_tensor(x),
               sequence_length=paddle.to_tensor(lens))
    # padded outputs are zero
    np.testing.assert_allclose(y.numpy()[1, 3:], 0.0)
    # final state of seq 1 == running only its valid prefix
    y2, h2 = rnn(paddle.to_tensor(x[1:, :3]))
    np.testing.assert_allclose(h.numpy()[1], h2.numpy()[0],
                               rtol=1e-5, atol=1e-6)


def test_rnn_cell_wrapper_lstm_sequence_length():
    """LSTM cells carry (h, c): the masked wrapper must freeze the
    tuple structure (the zeros carry follows the cell's own shape)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 5, 3)).astype(np.float32)
    lens = np.array([5, 2], np.int64)
    paddle.seed(4)
    rnn = nn.RNN(nn.LSTMCell(3, 4))
    y, (h, c) = rnn(paddle.to_tensor(x),
                    sequence_length=paddle.to_tensor(lens))
    np.testing.assert_allclose(y.numpy()[1, 2:], 0.0)
    y2, (h2, c2) = rnn(paddle.to_tensor(x[1:, :2]))
    np.testing.assert_allclose(h.numpy()[1], h2.numpy()[0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c.numpy()[1], c2.numpy()[0],
                               rtol=1e-5, atol=1e-6)


def test_loss_parity_vs_torch():
    """Five-loss numerics audit against torch: kl_div, margin_ranking,
    smooth_l1, cosine_embedding, cross_entropy with label smoothing."""
    import numpy as np
    import torch
    import torch.nn.functional as TF
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.default_rng(0)
    a = rng.standard_normal((4, 5)).astype(np.float32)
    b = rng.standard_normal((4, 5)).astype(np.float32)
    lp = np.log(np.abs(a) + 0.1).astype(np.float32)
    tgt = (np.abs(b) / np.abs(b).sum(1, keepdims=True)).astype(np.float32)
    np.testing.assert_allclose(
        F.kl_div(paddle.to_tensor(lp), paddle.to_tensor(tgt),
                 reduction="mean").numpy(),
        TF.kl_div(torch.tensor(lp), torch.tensor(tgt),
                  reduction="mean").numpy(), rtol=1e-5, atol=1e-6)
    lab = np.sign(rng.standard_normal(4)).astype(np.float32)
    np.testing.assert_allclose(
        F.margin_ranking_loss(paddle.to_tensor(a[:, 0]),
                              paddle.to_tensor(a[:, 1]),
                              paddle.to_tensor(lab), margin=0.3).numpy(),
        TF.margin_ranking_loss(torch.tensor(a[:, 0]),
                               torch.tensor(a[:, 1]),
                               torch.tensor(lab), margin=0.3).numpy(),
        rtol=1e-5)
    np.testing.assert_allclose(
        F.smooth_l1_loss(paddle.to_tensor(a), paddle.to_tensor(b)).numpy(),
        TF.smooth_l1_loss(torch.tensor(a), torch.tensor(b)).numpy(),
        rtol=1e-5)
    v1 = rng.standard_normal((4, 6)).astype(np.float32)
    v2 = rng.standard_normal((4, 6)).astype(np.float32)
    y = np.array([1, -1, 1, -1], np.float32)
    np.testing.assert_allclose(
        F.cosine_embedding_loss(paddle.to_tensor(v1), paddle.to_tensor(v2),
                                paddle.to_tensor(y), margin=0.2).numpy(),
        TF.cosine_embedding_loss(torch.tensor(v1), torch.tensor(v2),
                                 torch.tensor(y), margin=0.2).numpy(),
        rtol=1e-5)
    logits = rng.standard_normal((6, 10)).astype(np.float32)
    labels = rng.integers(0, 10, 6).astype(np.int64)
    np.testing.assert_allclose(
        F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels),
                        label_smoothing=0.1).numpy(),
        TF.cross_entropy(torch.tensor(logits), torch.tensor(labels),
                         label_smoothing=0.1).numpy(), rtol=1e-5)
