"""Public API surface parity sweep.

One test per namespace asserting the commonly-migrated Paddle APIs exist
(SURVEY.md §2.2: a reference user must find what they need). Presence-only
for the long tail; numerics for the newly-added ops are spot-checked below.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as optim
import paddle_tpu.distributed as dist

pytestmark = pytest.mark.fast  # whole-module smoke: cheap on 1 core

TOP_LEVEL = """abs acos add addmm all allclose any arange argmax argmin argsort
as_complex as_real asin assign atan atan2 bernoulli bincount bitwise_and
bitwise_left_shift bitwise_not bitwise_or bitwise_xor bmm broadcast_shape
broadcast_tensors broadcast_to bucketize cast cat ceil chunk clip clone concat
conj cos cosh count_nonzero cross cumprod cumsum cumulative_trapezoid deg2rad
diag diagflat diagonal diff digamma disable_static dist divide dot einsum
empty empty_like enable_static equal equal_all erf erfinv exp expand expand_as
expm1 eye flatten flip floor floor_divide floor_mod full full_like gather
gather_nd gcd get_default_dtype grad greater_equal greater_than heaviside
histogram hypot imag in_dynamic_mode index_sample index_select inner inverse
is_tensor isclose isfinite isinf isnan kron lcm ldexp lerp less_equal
less_than lgamma linspace load log log10 log1p log2 logcumsumexp logical_and
logical_not logical_or logical_xor logit logsumexp masked_fill masked_select
matmul max maximum mean median meshgrid min minimum mm mod moveaxis
multinomial multiply mv nan_to_num nanmean nanmedian nansum neg nextafter
no_grad nonzero norm normal not_equal numel ones ones_like outer permute
pinverse poisson polar positive pow prod rad2deg rand randint randn randperm
real reciprocal remainder repeat_interleave reshape roll rot90 round rsqrt
save scale scatter scatter_nd searchsorted seed set_default_dtype
set_grad_enabled sgn shape sign signbit sin sinh slice sort split sqrt square
squeeze stack standard_normal std subtract sum summary t take take_along_axis
tan tanh tensordot tile to_tensor topk trace transpose tril triu trunc unbind
unique unique_consecutive unsqueeze unstack var vsplit where zeros
zeros_like Model callbacks utils onnx version regularizer DataParallel
LazyGuard""".split()

NN = """Linear Conv1D Conv2D Conv3D Conv1DTranspose Conv2DTranspose
Conv3DTranspose BatchNorm1D BatchNorm2D BatchNorm3D SyncBatchNorm LayerNorm
GroupNorm InstanceNorm1D InstanceNorm2D InstanceNorm3D SpectralNorm
LocalResponseNorm Embedding Dropout Dropout2D Dropout3D AlphaDropout ReLU
ReLU6 LeakyReLU PReLU RReLU ELU CELU SELU GELU Hardshrink Hardsigmoid
Hardswish Hardtanh Sigmoid LogSigmoid Softmax LogSoftmax Softplus Softshrink
Softsign Swish Mish Tanh Tanhshrink ThresholdedReLU SiLU GLU MaxPool1D
MaxPool2D MaxPool3D AvgPool1D AvgPool2D AvgPool3D AdaptiveAvgPool1D
AdaptiveAvgPool2D AdaptiveAvgPool3D AdaptiveMaxPool1D AdaptiveMaxPool2D
AdaptiveMaxPool3D MaxUnPool2D Pad1D Pad2D Pad3D ZeroPad2D CosineSimilarity
PairwiseDistance Upsample UpsamplingBilinear2D UpsamplingNearest2D
PixelShuffle PixelUnshuffle ChannelShuffle Flatten Unflatten Fold Unfold RNN
LSTM GRU SimpleRNN RNNCellBase LSTMCell GRUCell SimpleRNNCell
MultiHeadAttention Transformer TransformerEncoder TransformerEncoderLayer
TransformerDecoder TransformerDecoderLayer CrossEntropyLoss MSELoss L1Loss
NLLLoss BCELoss BCEWithLogitsLoss KLDivLoss SmoothL1Loss HuberLoss
MarginRankingLoss CTCLoss CosineEmbeddingLoss TripletMarginLoss
TripletMarginWithDistanceLoss MultiLabelSoftMarginLoss HingeEmbeddingLoss
PoissonNLLLoss GaussianNLLLoss SoftMarginLoss Sequential LayerList
ParameterList LayerDict Identity Bilinear""".split()

FUNCTIONAL = """linear conv1d conv2d conv3d conv1d_transpose conv2d_transpose
conv3d_transpose relu relu6 leaky_relu prelu rrelu elu celu selu gelu
hardshrink hardsigmoid hardswish hardtanh sigmoid log_sigmoid softmax
log_softmax softplus softshrink softsign swish mish tanhshrink
thresholded_relu silu glu gumbel_softmax max_pool1d max_pool2d max_pool3d
avg_pool1d avg_pool2d avg_pool3d adaptive_avg_pool1d adaptive_avg_pool2d
adaptive_avg_pool3d adaptive_max_pool1d adaptive_max_pool2d
adaptive_max_pool3d max_unpool2d pad interpolate upsample pixel_shuffle
pixel_unshuffle channel_shuffle affine_grid grid_sample cosine_similarity
pairwise_distance normalize batch_norm layer_norm group_norm instance_norm
local_response_norm dropout dropout2d dropout3d alpha_dropout embedding
one_hot cross_entropy binary_cross_entropy binary_cross_entropy_with_logits
mse_loss l1_loss nll_loss kl_div smooth_l1_loss ctc_loss margin_ranking_loss
cosine_embedding_loss triplet_margin_loss sigmoid_focal_loss dice_loss
log_loss soft_margin_loss multi_label_soft_margin_loss poisson_nll_loss
gaussian_nll_loss square_error_cost softmax_with_cross_entropy unfold fold
flash_attention scaled_dot_product_attention sequence_mask temporal_shift
class_center_sample""".split()

OPTIM = "SGD Momentum Adam AdamW Adamax Adagrad Adadelta RMSProp Lamb Lars LBFGS".split()
LR = """LRScheduler NoamDecay ExponentialDecay NaturalExpDecay
InverseTimeDecay PolynomialDecay LinearWarmup PiecewiseDecay
CosineAnnealingDecay MultiStepDecay StepDecay LambdaDecay ReduceOnPlateau
OneCycleLR CyclicLR MultiplicativeDecay""".split()
DIST = """init_parallel_env get_rank get_world_size all_reduce all_gather
broadcast reduce scatter reduce_scatter alltoall send recv barrier new_group
get_group spawn launch ParallelEnv fleet ReduceOp shard_tensor reshard Shard
Replicate ProcessMesh DataParallel split P2POp batch_isend_irecv""".split()


@pytest.mark.parametrize("ns,names", [
    (paddle, TOP_LEVEL), (nn, NN), (F, FUNCTIONAL), (optim, OPTIM),
    (optim.lr, LR), (dist, DIST),
])
def test_surface_present(ns, names):
    missing = [n for n in names if not hasattr(ns, n)]
    assert not missing, f"{getattr(ns, '__name__', ns)} missing: {missing}"


def test_new_ops_numerics():
    t = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
    np.testing.assert_allclose(
        paddle.addmm(paddle.ones([2, 2]), t, paddle.ones([3, 2]),
                     beta=2.0, alpha=0.5).numpy(),
        np.broadcast_to(
            2.0 + 0.5 * np.arange(6).reshape(2, 3).sum(1, keepdims=True),
            (2, 2),
        ),
    )
    z = paddle.as_complex(paddle.to_tensor(np.array([[1.0, 2.0]], np.float32)))
    np.testing.assert_allclose(paddle.as_real(z).numpy(), [[1.0, 2.0]])
    np.testing.assert_allclose(
        paddle.hypot(paddle.to_tensor(3.0), paddle.to_tensor(4.0)).numpy(), 5.0)
    s = paddle.slice(paddle.to_tensor(np.arange(24).reshape(2, 3, 4)),
                     [1, 2], [1, 0], [3, 2])
    np.testing.assert_array_equal(
        s.numpy(), np.arange(24).reshape(2, 3, 4)[:, 1:3, 0:2])
    c = paddle.combinations(paddle.to_tensor(np.array([1, 2, 3])))
    assert c.shape == [3, 2]
    tr = paddle.cumulative_trapezoid(paddle.to_tensor(np.array([1.0, 2.0, 3.0])))
    np.testing.assert_allclose(tr.numpy(), [1.5, 4.0])


def test_inplace_method_family():
    x = paddle.to_tensor(np.array([4.0, 9.0], np.float32))
    x.sqrt_()
    np.testing.assert_allclose(x.numpy(), [2.0, 3.0])
    x.reciprocal_()
    np.testing.assert_allclose(x.numpy(), [0.5, 1 / 3], rtol=1e-6)
    x.reshape_([2, 1])
    assert x.shape == [2, 1]
    assert x.dim() == 2 and x.element_size() == 4


def test_static_mode_toggles():
    assert paddle.in_dynamic_mode()
    paddle.enable_static()
    try:
        assert not paddle.in_dynamic_mode()
    finally:
        paddle.disable_static()
    assert paddle.in_dynamic_mode()


def test_default_dtype_honored_by_creation():
    try:
        paddle.set_default_dtype("float64")
        assert "float64" in str(paddle.ones([2])._value.dtype) or \
            "float32" in str(paddle.ones([2])._value.dtype)  # x64 may be off
        paddle.set_default_dtype("bfloat16")
        assert "bfloat16" in str(paddle.zeros([2])._value.dtype)
    finally:
        paddle.set_default_dtype("float32")
    assert "float32" in str(paddle.ones([2])._value.dtype)


def test_bitwise_right_shift_logical():
    x = paddle.to_tensor(np.array([-8], np.int32))
    one = paddle.to_tensor(np.array([1], np.int32))
    arith = paddle.bitwise_right_shift(x, one).numpy()[0]
    logic = paddle.bitwise_right_shift(x, one, is_arithmetic=False).numpy()[0]
    assert arith == -4
    assert logic == np.int32(np.uint32(0xFFFFFFF8) >> 1)


def test_poisson_nll_full_grad_finite_at_zero_label():
    import jax

    from paddle_tpu.framework.op import raw

    label = np.array([0.0, 1.0, 5.0], np.float32)
    g = jax.grad(
        lambda v: float(0) + raw(F.poisson_nll_loss(
            paddle.to_tensor(v), paddle.to_tensor(label), full=True))
    )(np.array([0.1, 0.2, 0.3], np.float32))
    assert np.isfinite(np.asarray(g)).all()


def test_inplace_reshape_keeps_autograd():
    x = paddle.to_tensor(np.ones((2, 3), np.float32), stop_gradient=False)
    y = x * 2.0
    y.reshape_([6])
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), np.full((2, 3), 2.0))


def test_lbfgs_converges_on_quadratic():
    paddle.seed(0)
    target = paddle.to_tensor(np.array([3.0, -2.0], np.float32))
    w = paddle.to_tensor(np.zeros(2, np.float32), stop_gradient=False)
    from paddle_tpu.nn.layer import Parameter

    p = Parameter(w._value)
    opt = optim.LBFGS(learning_rate=1.0, parameters=[p])

    def closure():
        opt.clear_grad()
        loss = ((p - target) ** 2).sum()
        loss.backward()
        return loss

    for _ in range(10):
        loss = opt.step(closure)
    assert float(loss.numpy()) < 1e-6
    np.testing.assert_allclose(p.numpy(), [3.0, -2.0], atol=1e-3)


def test_max_unpool2d_roundtrip():
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((1, 1, 4, 4)).astype("float32")
    )
    pooled, idx = F.max_pool2d(x, 2, stride=2, return_mask=True)
    out = F.max_unpool2d(pooled, idx, 2, stride=2)
    assert out.shape == [1, 1, 4, 4]
    # unpooled image contains exactly the pooled maxima, zeros elsewhere
    np.testing.assert_allclose(out.numpy().sum(), pooled.numpy().sum(), rtol=1e-6)


def test_fold_unfold_roundtrip():
    x = paddle.to_tensor(
        np.random.default_rng(1).standard_normal((1, 2, 6, 6)).astype("float32")
    )
    cols = F.unfold(x, 2, strides=2)
    back = F.fold(cols, (6, 6), 2, strides=2)
    # non-overlapping windows: fold(unfold(x)) == x
    np.testing.assert_allclose(back.numpy(), x.numpy(), rtol=1e-6)


def test_temporal_shift_shapes():
    x = paddle.to_tensor(
        np.random.default_rng(2).standard_normal((4, 8, 5, 5)).astype("float32")
    )
    out = F.temporal_shift(x, seg_num=2, shift_ratio=0.25)
    assert out.shape == [4, 8, 5, 5]


def test_color_transforms_and_random_erasing():
    from paddle_tpu.vision import transforms as T

    img = np.random.default_rng(0).integers(0, 255, (32, 32, 3)).astype(np.uint8)
    for cls, args in [
        (T.ContrastTransform, (0.4,)), (T.SaturationTransform, (0.4,)),
        (T.HueTransform, (0.2,)),
    ]:
        out = cls(*args)(img)
        assert out.shape == (32, 32, 3) and out.dtype == np.uint8
    out = T.RandomErasing(prob=1.0, value=0)(img)
    assert out.shape == (32, 32, 3)
    assert (out == 0).any()  # some rectangle was erased
    out = T.ColorJitter(0.2, 0.2, 0.2, 0.1)(img)
    assert out.shape == (32, 32, 3)
    # YIQ hue rotation sanity: +0.25 then -0.25 turns round-trips; and the
    # quarter-turn itself is NOT the identity
    from paddle_tpu.vision.transforms import _adjust_hue

    a = img.astype(np.float32)
    np.testing.assert_allclose(
        _adjust_hue(_adjust_hue(a, 0.25), -0.25), a, atol=1e-2)
    assert np.abs(_adjust_hue(a, 0.25) - a).max() > 1.0
    # CHW float RandomErasing (post-ToTensor layout) erases a region too
    chw = np.random.default_rng(1).random((3, 32, 32)).astype(np.float32)
    out = T.RandomErasing(prob=1.0, value=0.0)(chw)
    assert out.shape == (3, 32, 32) and (out == 0).any()


def test_incubate_fused_functionals():
    from paddle_tpu.incubate import nn as inn

    d, nh, hd = 16, 2, 8
    rng = np.random.default_rng(1)
    x = paddle.to_tensor(rng.standard_normal((2, 6, d)).astype("float32"))
    qkv_w = paddle.to_tensor(
        (rng.standard_normal((3, nh, hd, d)) * 0.1).astype("float32"))
    lin_w = paddle.to_tensor(
        (rng.standard_normal((d, d)) * 0.1).astype("float32"))
    out = inn.fused_multi_head_attention(
        x, qkv_w, lin_w, pre_layer_norm=True,
        pre_ln_scale=paddle.ones([d]), pre_ln_bias=paddle.zeros([d]),
        dropout_rate=0.0, attn_dropout_rate=0.0,
    )
    assert out.shape == [2, 6, d]
    assert np.isfinite(out.numpy()).all()
    # gradients flow to the fused weights (the functional must stay on the
    # tape — raw jnp math here silently detaches)
    qkv_w.stop_gradient = False
    lin_w.stop_gradient = False
    out_g = inn.fused_multi_head_attention(
        x, qkv_w, lin_w, pre_layer_norm=True,
        pre_ln_scale=paddle.ones([d]), pre_ln_bias=paddle.zeros([d]),
        dropout_rate=0.0, attn_dropout_rate=0.0,
    )
    (out_g ** 2).mean().backward()
    assert qkv_w.grad is not None and float(np.abs(qkv_w.grad.numpy()).max()) > 0
    assert lin_w.grad is not None
    w1 = paddle.to_tensor((rng.standard_normal((d, 32)) * 0.1).astype("float32"))
    w2 = paddle.to_tensor((rng.standard_normal((32, d)) * 0.1).astype("float32"))
    out2 = inn.fused_feedforward(
        x, w1, w2, dropout1_rate=0.0, dropout2_rate=0.0,
        ln2_scale=paddle.ones([d]), ln2_bias=paddle.zeros([d]),
    )
    assert out2.shape == [2, 6, d]


@pytest.mark.parametrize("device,error", [
    ("tpu", RuntimeError),      # no accelerator on the CPU test backend:
    ("gpu:0", RuntimeError),    # never quietly the host instead
    ("cuda", RuntimeError),
    ("cpu:99", ValueError),     # past the last device: never clamped
    ("cpu:-1", ValueError),
])
def test_set_device_raises_instead_of_falling_back(device, error):
    import jax

    before = (paddle.get_device(), jax.config.jax_default_device)
    with pytest.raises(error, match="set_device"):
        paddle.set_device(device)
    assert (paddle.get_device(), jax.config.jax_default_device) == before


def test_set_device_selects_the_indexed_cpu_device():
    import jax

    import paddle_tpu.device as device_mod

    before = (device_mod._current, jax.config.jax_default_device)
    try:
        place = paddle.set_device("cpu:1")
        assert paddle.get_device() == "cpu:1" and place.device_id == 1
        assert jax.config.jax_default_device == jax.devices("cpu")[1]
    finally:
        device_mod._current = before[0]
        jax.config.update("jax_default_device", before[1])
