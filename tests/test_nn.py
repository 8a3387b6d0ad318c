"""nn layer tests: shapes, reference values, train/eval behavior."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

rng = np.random.RandomState(0)


def t(a, sg=True):
    return paddle.to_tensor(a, stop_gradient=sg)


@pytest.mark.fast
def test_linear():
    # the layer's draw is seeded here: left to the global generator it
    # followed whatever files the xdist worker ran before, and 17 of 400
    # draws put an output so near zero (1e-3 .. 1e-4) that float32's
    # 1e-8 .. 8e-8 of summation order broke rtol alone; hence the atol too
    paddle.seed(0)
    layer = nn.Linear(4, 8)
    x = t(rng.rand(2, 4).astype(np.float32))
    out = layer(x)
    assert out.shape == [2, 8]
    ref = x.numpy() @ layer.weight.numpy() + layer.bias.numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)


def test_conv2d_shape_and_value():
    conv = nn.Conv2D(3, 8, 3, padding=1)
    x = t(rng.rand(2, 3, 16, 16).astype(np.float32))
    out = conv(x)
    assert out.shape == [2, 8, 16, 16]
    # stride/padding variants
    assert nn.Conv2D(3, 4, 3, stride=2, padding=1)(x).shape == [2, 4, 8, 8]
    assert nn.Conv2D(3, 4, 3, padding="SAME")(x).shape == [2, 4, 16, 16]
    assert nn.Conv2D(3, 6, 3, groups=3)(x).shape == [2, 6, 14, 14]


def test_conv2d_vs_manual():
    conv = nn.Conv2D(1, 1, 2, bias_attr=False)
    x = rng.rand(1, 1, 4, 4).astype(np.float32)
    out = conv(t(x)).numpy()
    w = conv.weight.numpy()[0, 0]
    expected = np.zeros((3, 3), np.float32)
    for i in range(3):
        for j in range(3):
            expected[i, j] = (x[0, 0, i : i + 2, j : j + 2] * w).sum()
    np.testing.assert_allclose(out[0, 0], expected, rtol=1e-4)


def test_conv_transpose():
    convt = nn.Conv2DTranspose(4, 2, 3, stride=2, padding=1)
    x = t(rng.rand(2, 4, 8, 8).astype(np.float32))
    assert convt(x).shape == [2, 2, 15, 15]


def test_pools():
    x = t(rng.rand(2, 3, 8, 8).astype(np.float32))
    assert nn.MaxPool2D(2, 2)(x).shape == [2, 3, 4, 4]
    assert nn.AvgPool2D(2, 2)(x).shape == [2, 3, 4, 4]
    assert nn.AdaptiveAvgPool2D((1, 1))(x).shape == [2, 3, 1, 1]
    np.testing.assert_allclose(
        nn.AdaptiveAvgPool2D((1, 1))(x).numpy()[..., 0, 0],
        x.numpy().mean((2, 3)),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        nn.MaxPool2D(2, 2)(x).numpy(),
        x.numpy().reshape(2, 3, 4, 2, 4, 2).max((3, 5)),
        rtol=1e-6,
    )


@pytest.mark.fast
def test_batchnorm_train_eval():
    bn = nn.BatchNorm2D(4)
    x = t(rng.rand(8, 4, 5, 5).astype(np.float32) * 3 + 1)
    bn.train()
    out = bn(x)
    m = out.numpy().mean((0, 2, 3))
    v = out.numpy().var((0, 2, 3))
    np.testing.assert_allclose(m, np.zeros(4), atol=1e-5)
    np.testing.assert_allclose(v, np.ones(4), atol=1e-3)
    # running stats moved toward batch stats
    assert not np.allclose(bn._mean.numpy(), np.zeros(4))
    bn.eval()
    out_eval = bn(x)
    assert out_eval.shape == [8, 4, 5, 5]


@pytest.mark.fast
def test_layernorm():
    ln = nn.LayerNorm(16)
    x = t(rng.rand(4, 16).astype(np.float32))
    out = ln(x).numpy()
    np.testing.assert_allclose(out.mean(-1), np.zeros(4), atol=1e-5)
    np.testing.assert_allclose(out.var(-1), np.ones(4), atol=1e-3)


def test_groupnorm_instance_rms():
    x = t(rng.rand(2, 8, 4, 4).astype(np.float32))
    assert nn.GroupNorm(2, 8)(x).shape == [2, 8, 4, 4]
    assert nn.InstanceNorm2D(8)(x).shape == [2, 8, 4, 4]
    y = t(rng.rand(2, 16).astype(np.float32))
    assert nn.RMSNorm(16)(y).shape == [2, 16]


@pytest.mark.fast
def test_embedding():
    emb = nn.Embedding(10, 6)
    idx = t(np.array([[1, 2], [3, 4]], np.int64))
    out = emb(idx)
    assert out.shape == [2, 2, 6]
    np.testing.assert_allclose(out.numpy()[0, 0], emb.weight.numpy()[1])


def test_dropout_modes():
    d = nn.Dropout(0.5)
    x = t(np.ones((100, 100), np.float32))
    d.train()
    y = d(x).numpy()
    assert (y == 0).mean() > 0.3
    np.testing.assert_allclose(y[y != 0], 2.0)  # upscale_in_train
    d.eval()
    np.testing.assert_allclose(d(x).numpy(), 1.0)


@pytest.mark.fast
def test_activations():
    x = rng.randn(4, 5).astype(np.float32)
    np.testing.assert_allclose(F.relu(t(x)).numpy(), np.maximum(x, 0))
    np.testing.assert_allclose(
        F.softmax(t(x), axis=-1).numpy().sum(-1), np.ones(4), rtol=1e-5
    )
    np.testing.assert_allclose(F.sigmoid(t(x)).numpy(), 1 / (1 + np.exp(-x)), rtol=1e-5)
    np.testing.assert_allclose(
        F.leaky_relu(t(x), 0.1).numpy(), np.where(x > 0, x, 0.1 * x), rtol=1e-5
    )
    assert F.gelu(t(x)).shape == [4, 5]


@pytest.mark.fast
def test_losses():
    logits = rng.randn(8, 5).astype(np.float32)
    labels = rng.randint(0, 5, (8,)).astype(np.int64)
    loss = nn.CrossEntropyLoss()(t(logits), t(labels))
    # numpy reference
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = -np.log(p[np.arange(8), labels]).mean()
    np.testing.assert_allclose(float(loss.numpy()), ref, rtol=1e-5)

    a, b = rng.rand(6).astype(np.float32), rng.rand(6).astype(np.float32)
    np.testing.assert_allclose(float(nn.MSELoss()(t(a), t(b)).numpy()), ((a - b) ** 2).mean(), rtol=1e-5)
    np.testing.assert_allclose(float(nn.L1Loss()(t(a), t(b)).numpy()), np.abs(a - b).mean(), rtol=1e-5)
    bce = nn.BCEWithLogitsLoss()(t(a), t((b > 0.5).astype(np.float32)))
    assert np.isfinite(float(bce.numpy()))


@pytest.mark.fast
def test_cross_entropy_ignore_index_and_smoothing():
    logits = rng.randn(6, 4).astype(np.float32)
    labels = np.array([0, 1, -100, 2, -100, 3], np.int64)
    loss = F.cross_entropy(t(logits), t(labels), ignore_index=-100)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    valid = labels != -100
    ref = -np.log(p[valid, labels[valid]]).mean()
    np.testing.assert_allclose(float(loss.numpy()), ref, rtol=1e-5)
    ls = F.cross_entropy(t(logits), t(np.abs(labels) % 4), label_smoothing=0.1)
    assert np.isfinite(float(ls.numpy()))


def test_sequential_layerlist():
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    assert len(net) == 3
    x = t(rng.rand(3, 4).astype(np.float32))
    assert net(x).shape == [3, 2]
    ll = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
    assert len(list(ll.parameters())) == 6


@pytest.mark.fast
def test_state_dict_roundtrip():
    net = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8, data_format="NC"), nn.Linear(8, 2))
    sd = net.state_dict()
    assert any("weight" in k for k in sd)
    net2 = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8, data_format="NC"), nn.Linear(8, 2))
    net2.set_state_dict({k: v.numpy() for k, v in sd.items()})
    for (k1, p1), (k2, p2) in zip(net.named_parameters(), net2.named_parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy())


@pytest.mark.fast
def test_multihead_attention():
    mha = nn.MultiHeadAttention(16, 4)
    x = t(rng.rand(2, 5, 16).astype(np.float32))
    out = mha(x)
    assert out.shape == [2, 5, 16]


def test_transformer_encoder():
    enc = nn.TransformerEncoder(nn.TransformerEncoderLayer(16, 4, 32), 2)
    x = t(rng.rand(2, 5, 16).astype(np.float32))
    assert enc(x).shape == [2, 5, 16]


def test_lstm_gru():
    lstm = nn.LSTM(8, 16, num_layers=2)
    x = t(rng.rand(3, 7, 8).astype(np.float32))
    out, (h, c) = lstm(x)
    assert out.shape == [3, 7, 16]
    assert h.shape == [2, 3, 16] and c.shape == [2, 3, 16]
    gru = nn.GRU(8, 16, direction="bidirect")
    out, h = gru(x)
    assert out.shape == [3, 7, 32]
    assert h.shape == [2, 3, 16]


@pytest.mark.fast
def test_layer_grad_flow():
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    x = t(rng.rand(5, 4).astype(np.float32))
    loss = net(x).sum()
    loss.backward()
    for p in net.parameters():
        assert p.grad is not None, "all parameters must receive gradients"


def test_pad_and_interpolate():
    x = t(rng.rand(1, 2, 4, 4).astype(np.float32))
    assert F.pad(x, [1, 1, 2, 2]).shape == [1, 2, 8, 6]
    assert F.interpolate(x, size=[8, 8], mode="nearest").shape == [1, 2, 8, 8]
    assert F.interpolate(x, scale_factor=2, mode="bilinear").shape == [1, 2, 8, 8]


@pytest.mark.fast
def test_clip_grad_norm():
    p = nn.Linear(4, 4).weight
    p.grad = paddle.to_tensor(np.full((4, 4), 10.0, np.float32))
    total = nn.utils.clip_grad_norm_([p], 1.0) if hasattr(nn, "utils") else None
    from paddle_tpu.nn.utils import clip_grad_norm_

    p.grad = paddle.to_tensor(np.full((4, 4), 10.0, np.float32))
    clip_grad_norm_([p], 1.0)
    assert np.linalg.norm(p.grad.numpy()) <= 1.0 + 1e-4
