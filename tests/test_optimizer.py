"""Optimizer + LR scheduler + AMP tests."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.optimizer import SGD, Adam, AdamW, ClipGradByGlobalNorm, Lamb, Momentum, RMSProp, lr

pytestmark = pytest.mark.fast  # whole-module smoke: cheap on 1 core

rng = np.random.RandomState(0)


def _quad_problem(opt_cls, steps=60, **kw):
    paddle.seed(0)
    target = np.array([1.0, -2.0, 3.0], np.float32)
    w = nn.layer.Parameter(paddle.to_tensor(np.zeros(3, np.float32))._value)
    opt = opt_cls(parameters=[w], **kw)
    for _ in range(steps):
        loss = ((w - paddle.to_tensor(target)) ** 2).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    return w.numpy(), target


def test_sgd_converges():
    w, tgt = _quad_problem(SGD, learning_rate=0.1, steps=100)
    np.testing.assert_allclose(w, tgt, atol=1e-2)


def test_momentum_converges():
    w, tgt = _quad_problem(Momentum, learning_rate=0.05, momentum=0.9, steps=120)
    np.testing.assert_allclose(w, tgt, atol=5e-2)


def test_adam_converges():
    w, tgt = _quad_problem(Adam, learning_rate=0.3, steps=150)
    np.testing.assert_allclose(w, tgt, atol=5e-2)


def test_adamw_decay():
    # with pure decay and zero grads, weights shrink
    w = nn.layer.Parameter(paddle.to_tensor(np.ones(3, np.float32))._value)
    opt = AdamW(learning_rate=0.1, weight_decay=0.5, parameters=[w])
    w.grad = paddle.to_tensor(np.zeros(3, np.float32))
    opt.step()
    assert (w.numpy() < 1.0).all()


def test_adamw_functional_step_keeps_bf16_params_bf16():
    """The compiled train step feeds lr as an f32 array; the decoupled
    decay must not promote a bf16 parameter to f32 (twice the bytes on the
    chip, and a second compile when the step's signature changes)."""
    import jax.numpy as jnp

    w = nn.layer.Parameter(jnp.ones((4, 4), jnp.bfloat16))
    opt = AdamW(learning_rate=0.1, parameters=[w], weight_decay=0.01)
    (new_w,), (st,) = opt.functional_step(
        [w._value], [jnp.full((4, 4), 0.5, jnp.bfloat16)],
        opt.functional_states(), jnp.asarray(0.1, jnp.float32))
    assert new_w.dtype == jnp.bfloat16
    assert st["moment1"].dtype == st["moment2"].dtype == jnp.bfloat16
    assert float(new_w[0, 0]) < 1.0


def test_adam_matches_manual():
    a = rng.rand(4).astype(np.float32)
    g = rng.rand(4).astype(np.float32)
    w = nn.layer.Parameter(paddle.to_tensor(a)._value)
    opt = Adam(learning_rate=0.01, parameters=[w])
    w.grad = paddle.to_tensor(g)
    opt.step()
    # manual first adam step
    m = 0.1 * g
    v = 0.001 * g * g
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    ref = a - 0.01 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(w.numpy(), ref, rtol=1e-5)


def test_global_norm_clip():
    w = nn.layer.Parameter(paddle.to_tensor(np.zeros(4, np.float32))._value)
    opt = SGD(learning_rate=1.0, parameters=[w], grad_clip=ClipGradByGlobalNorm(1.0))
    w.grad = paddle.to_tensor(np.full(4, 100.0, np.float32))
    opt.step()
    assert np.linalg.norm(w.numpy()) <= 1.0 + 1e-5


def test_lr_schedulers():
    s = lr.StepDecay(0.1, step_size=2, gamma=0.5)
    vals = []
    for _ in range(5):
        vals.append(s())
        s.step()
    np.testing.assert_allclose(vals, [0.1, 0.1, 0.05, 0.05, 0.025], rtol=1e-6)

    c = lr.CosineAnnealingDecay(1.0, T_max=10)
    assert abs(c() - 1.0) < 1e-6
    for _ in range(10):
        c.step()
    assert c() < 1e-6

    w = lr.LinearWarmup(0.1, warmup_steps=5, start_lr=0.0, end_lr=0.1)
    assert w() < 0.1
    for _ in range(6):
        w.step()
    np.testing.assert_allclose(w(), 0.1, rtol=1e-6)


def test_scheduler_with_optimizer():
    sched = lr.StepDecay(0.1, step_size=1, gamma=0.1)
    w = nn.layer.Parameter(paddle.to_tensor(np.zeros(2, np.float32))._value)
    opt = SGD(learning_rate=sched, parameters=[w])
    assert opt.get_lr() == 0.1
    sched.step()
    assert abs(opt.get_lr() - 0.01) < 1e-9


def test_optimizer_state_dict():
    w = nn.layer.Parameter(paddle.to_tensor(np.ones(3, np.float32))._value, name="w0")
    opt = Adam(learning_rate=0.01, parameters=[w])
    w.grad = paddle.to_tensor(np.ones(3, np.float32))
    opt.step()
    sd = opt.state_dict()
    assert any("moment1" in k for k in sd)
    opt2 = Adam(learning_rate=0.01, parameters=[w])
    opt2.set_state_dict(sd)
    np.testing.assert_allclose(
        opt2._accumulators[0]["moment1"], opt._accumulators[0]["moment1"]
    )


def test_amp_autocast_bf16():
    import paddle_tpu.amp as amp

    x = paddle.to_tensor(rng.rand(4, 4).astype(np.float32))
    y = paddle.to_tensor(rng.rand(4, 4).astype(np.float32))
    with amp.auto_cast(level="O1", dtype="bfloat16"):
        z = paddle.matmul(x, y)
        assert z.dtype == paddle.bfloat16
        s = paddle.exp(x)  # black list -> stays fp32
        assert s.dtype == paddle.float32
    z2 = paddle.matmul(x, y)
    assert z2.dtype == paddle.float32


def test_grad_scaler_fp16_flow():
    import paddle_tpu.amp as amp

    w = nn.layer.Parameter(paddle.to_tensor(np.ones(2, np.float32))._value)
    opt = SGD(learning_rate=0.1, parameters=[w])
    scaler = amp.GradScaler(init_loss_scaling=2.0)
    loss = (w * w).sum()
    scaled = scaler.scale(loss)
    scaled.backward()
    scaler.step(opt)
    scaler.update()
    np.testing.assert_allclose(w.numpy(), 1.0 - 0.1 * 2.0, rtol=1e-5)


@pytest.mark.fast
def test_lars_trust_ratio_and_exclusion():
    """Lars (reference LarsMomentumOptimizer): layerwise trust-ratio update
    checked against a numpy replay; excluded params zero the decay only."""
    import numpy as np

    paddle.seed(0)
    layer = nn.Linear(6, 4)
    layer.bias.name = "b_0"  # exclusion matches on the param NAME substring
    lr, mu, coeff, wd = 0.1, 0.9, 0.001, 0.0005
    opt = paddle.optimizer.Lars(
        learning_rate=lr, momentum=mu, lars_coeff=coeff,
        lars_weight_decay=wd, parameters=layer.parameters(),
        exclude_from_weight_decay=["b_"])
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((5, 6)).astype("float32"))

    ws = [p.numpy().copy() for p in layer.parameters()]
    vs = [np.zeros_like(w) for w in ws]
    excl = [any(s in (p.name or "") for s in ["b_"]) for p in layer.parameters()]

    for _ in range(4):
        loss = (layer(x) ** 2).mean()
        loss.backward()
        gs = [p.grad.numpy().copy() for p in layer.parameters()]
        opt.step()
        opt.clear_grad()
        for i, (w, v, g) in enumerate(zip(ws, vs, gs)):
            # exclusion zeroes ONLY the weight decay (upstream semantics);
            # the trust-ratio local lr applies to every param
            wd_i = 0.0 if excl[i] else wd
            p_n, g_n = np.linalg.norm(w), np.linalg.norm(g)
            denom = g_n + wd_i * p_n
            local = lr * coeff * p_n / denom if (p_n > 0 and denom > 0) else lr
            v = mu * v + local * (g + wd_i * w)
            ws[i], vs[i] = w - v, v
        for p, w in zip(layer.parameters(), ws):
            np.testing.assert_allclose(p.numpy(), w, rtol=1e-5, atol=1e-6)
    assert any(excl), "bias param should match the exclude list"


@pytest.mark.fast
def test_lars_works_under_compiled_trainstep():
    """The exclusion marker is pytree STRUCTURE, so Lars must survive the
    compiled jit.TrainStep path (a bool state leaf would become a traced
    array and crash on `if excluded`)."""
    import numpy as np

    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    layer = nn.Linear(6, 4)
    layer.bias.name = "b_0"
    opt = paddle.optimizer.Lars(
        learning_rate=0.05, parameters=layer.parameters(),
        exclude_from_weight_decay=["b_"])
    step = TrainStep(layer, lambda m, x: (m(x) ** 2).mean(), opt)
    x = paddle.to_tensor(
        np.random.default_rng(1).standard_normal((5, 6)).astype("float32"))
    losses = [float(step(x)) for _ in range(5)]
    assert losses[-1] < losses[0], losses
