"""Grouped-matmul Pallas kernel (dynamic ragged groups) + dropless MoE
(SURVEY.md §7 step 8 "MoE grouped matmul"; reference: per-expert GEMMs over
global_scatter in python/paddle/incubate/distributed/models/moe)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul


def _reference(lhs, rhs, sizes):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for g, s in enumerate(sizes):
        out[start:start + s] = lhs[start:start + s] @ rhs[g]
        start += s
    return out  # rows past sum(sizes) stay zero


def _mk(m, k, n, g, seed=0):
    rng = np.random.default_rng(seed)
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((g, k, n)).astype(np.float32)
    return lhs, rhs


@pytest.mark.parametrize(
    "sizes,m",
    [
        ([64, 64], 128),            # aligned groups
        ([50, 30, 48], 128),        # ragged, boundary-spanning tiles
        ([0, 100, 0, 28], 128),     # empty groups
        ([128, 0, 0], 128),         # trailing empties
        ([30, 40], 128),            # padding tail rows
        ([100, 156], 256),          # group spanning multiple tiles
    ],
)
def test_grouped_matmul_matches_reference(sizes, m):
    g = len(sizes)
    lhs, rhs = _mk(m, 32, 64, g)
    out = grouped_matmul(
        jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes), block_m=64
    )
    ref = _reference(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_grouped_matmul_dynamic_sizes_under_jit():
    """group_sizes is a traced value — one compile serves any routing."""
    lhs, rhs = _mk(128, 16, 32, 3, seed=1)

    @jax.jit
    def f(sizes):
        return grouped_matmul(
            jnp.asarray(lhs), jnp.asarray(rhs), sizes, block_m=64
        )

    for sizes in ([40, 60, 28], [0, 128, 0], [10, 10, 10]):
        out = f(jnp.asarray(sizes, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out), _reference(lhs, rhs, sizes), rtol=2e-5, atol=2e-5
        )


@pytest.mark.fast
@pytest.mark.parametrize("k,n", [
    (16, 32),
    (2048, 1024),  # OLMoE widths: the drhs kernel narrows its column block
])
def test_grouped_matmul_grads(k, n):
    sizes = [50, 30, 48]
    lhs, rhs = _mk(128, k, n, 3, seed=2)
    lhs, rhs = lhs / np.sqrt(k), rhs / np.sqrt(n)
    sz = jnp.asarray(sizes, jnp.int32)

    def f_pl(l, r):
        return (grouped_matmul(l, r, sz, block_m=64) ** 2).sum()

    def f_ref(l, r):
        out = jnp.zeros((l.shape[0], r.shape[2]), jnp.float32)
        start = 0
        for g, s in enumerate(sizes):
            out = out.at[start:start + s].set(l[start:start + s] @ r[g])
            start += s
        return (out ** 2).sum()

    gl, gr = jax.grad(f_pl, argnums=(0, 1))(jnp.asarray(lhs), jnp.asarray(rhs))
    rl, rr = jax.grad(f_ref, argnums=(0, 1))(jnp.asarray(lhs), jnp.asarray(rhs))
    np.testing.assert_allclose(np.asarray(gl), np.asarray(rl), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(rr), rtol=1e-4, atol=1e-4)


def test_dropless_moe_matches_dense_routing():
    """Dropless MoE == explicit per-token expert evaluation (no drops)."""
    from paddle_tpu import incubate

    paddle.seed(0)
    moe = incubate.MoELayer(
        d_model=16, d_hidden=32, num_experts=4, top_k=2, drop_tokens=False
    )
    moe.eval()
    x = paddle.to_tensor(
        np.random.default_rng(3).standard_normal((2, 8, 16)).astype("float32")
    )
    out = moe(x)
    assert out.shape == [2, 8, 16]

    # dense reference: every token through its top-k experts, gate-weighted
    import jax.numpy as jnp2

    flat = np.asarray(x._value).reshape(16, 16)
    logits = np.asarray(moe.gate(paddle.to_tensor(flat))._value)
    probs = np.asarray(jax.nn.softmax(jnp2.asarray(logits), -1))
    w_in = np.asarray(moe.w_in._value)
    b_in = np.asarray(moe.b_in._value)
    w_out = np.asarray(moe.w_out._value)
    b_out = np.asarray(moe.b_out._value)
    ref = np.zeros_like(flat)
    for t in range(16):
        top = np.argsort(-probs[t])[:2]
        gates = probs[t][top] / probs[t][top].sum()
        for gw, e in zip(gates, top):
            h1 = np.asarray(
                jax.nn.gelu(flat[t] @ w_in[e] + b_in[e, 0], approximate=True)
            )
            ref[t] += gw * (h1 @ w_out[e] + b_out[e, 0])
    np.testing.assert_allclose(
        np.asarray(out._value).reshape(16, 16), ref, rtol=2e-3, atol=2e-4
    )


@pytest.mark.slow
def test_dropless_moe_trains():
    from paddle_tpu import incubate, nn

    paddle.seed(1)
    moe = incubate.MoELayer(
        d_model=8, d_hidden=16, num_experts=4, top_k=2, drop_tokens=False
    )
    head = nn.Linear(8, 1)
    opt = paddle.optimizer.Adam(
        learning_rate=1e-2, parameters=moe.parameters() + head.parameters()
    )
    rng = np.random.default_rng(4)
    x = paddle.to_tensor(rng.standard_normal((4, 8, 8)).astype("float32"))
    y = paddle.to_tensor(rng.standard_normal((4, 8, 1)).astype("float32"))
    losses = []
    for step in range(8):
        loss = nn.MSELoss()(head(moe(x)), y) + moe.last_aux_loss
        loss.backward()
        if step == 0:
            # expert weights actually receive gradient through the kernel
            assert moe.w_in.grad is not None
            assert float(np.abs(moe.w_in.grad.numpy()).max()) > 0
            assert moe.w_out.grad is not None
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]


def test_grouped_matmul_nonmultiple_n():
    """N not a block_n multiple pads internally (e.g. d_hidden=192)."""
    sizes = [40, 60, 28]
    lhs, rhs = _mk(128, 32, 192, 3, seed=7)
    out = grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                         jnp.asarray(sizes), block_m=64)
    np.testing.assert_allclose(
        np.asarray(out), _reference(lhs, rhs, sizes), rtol=2e-5, atol=2e-5
    )
    g = jax.grad(
        lambda r: (grouped_matmul(jnp.asarray(lhs), r,
                                  jnp.asarray(sizes), block_m=64) ** 2).sum()
    )(jnp.asarray(rhs))
    assert g.shape == rhs.shape and np.isfinite(np.asarray(g)).all()
