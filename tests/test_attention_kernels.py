"""Flash-attention (Pallas, interpret on CPU) and ring-attention tests.

Mirrors the reference's op-test pattern (SURVEY.md §4): kernel vs dense
NumPy/jnp reference for forward, and analytic-grad parity for backward.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.nn.functional.attention import _sdpa_reference
from paddle_tpu.nn.functional.ring_attention import context_parallel_attention
from paddle_tpu.ops.pallas.flash_attention import flash_attention
import pytest


def _rand(b, t, h, d, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.fast
def test_flash_attention_matches_reference():
    q, k, v = _rand(2, 100, 2, 32)  # odd length exercises padding/masking
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal)
        ref = _sdpa_reference(q, k, v, None, 0.0, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.fast
def test_flash_attention_grads():
    q, k, v = _rand(1, 64, 2, 16)

    def f_pl(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).mean()

    def f_ref(q, k, v):
        return (_sdpa_reference(q, k, v, None, 0.0, True, None) ** 2).mean()

    g_pl = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_flash_attention_grads_mismatched_bwd_blocks(monkeypatch):
    """Backward blocks tuned SMALLER than the forward's (the sweep's shape):
    the forward-grid-padded lse residual must be re-sliced to the backward
    grid, incl. a sequence length that is a multiple of neither block."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "64")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", "64")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BWD_BLOCK_Q", "32")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BWD_BLOCK_K", "32")
    q, k, v = _rand(1, 100, 2, 16, seed=7)  # 100: not a multiple of 64 or 32

    def f_pl(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).mean()

    def f_ref(q, k, v):
        return (_sdpa_reference(q, k, v, None, 0.0, True, None) ** 2).mean()

    g_pl = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_attention_bias_and_mask():
    q, k, v = _rand(2, 96, 2, 16, seed=3)
    rng = np.random.default_rng(4)
    bias = jnp.asarray(rng.standard_normal((1, 2, 96, 96)), jnp.float32)
    out = flash_attention(q, k, v, bias=bias)
    ref = _sdpa_reference(q, k, v, jnp.swapaxes(bias, 0, 0), 0.0, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    keep = jnp.asarray(rng.random((2, 1, 96, 96)) > 0.3)
    out = flash_attention(q, k, v, mask=keep)
    ref = _sdpa_reference(q, k, v, keep, 0.0, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_bias_grad():
    q, k, v = _rand(1, 48, 2, 16, seed=5)
    rng = np.random.default_rng(6)
    bias = jnp.asarray(rng.standard_normal((1, 1, 48, 48)), jnp.float32)

    def f_pl(q, bias):
        return (flash_attention(q, k, v, causal=True, bias=bias) ** 2).mean()

    def f_ref(q, bias):
        return (_sdpa_reference(q, k, v, bias, 0.0, True, None) ** 2).mean()

    g_pl = jax.grad(f_pl, argnums=(0, 1))(q, bias)
    g_ref = jax.grad(f_ref, argnums=(0, 1))(q, bias)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_flash_attention_broadcast_padding_mask():
    """(B,1,1,Tk) padding mask rides the kernel without materialization."""
    q, k, v = _rand(2, 64, 2, 16, seed=10)
    rng = np.random.default_rng(11)
    keep = np.ones((2, 1, 1, 64), bool)
    keep[:, :, :, 48:] = False  # pad out the tail keys
    keep = jnp.asarray(keep)
    out = flash_attention(q, k, v, mask=keep)
    ref = _sdpa_reference(q, k, v, keep, 0.0, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    # grads through the masked kernel still match (mask itself has no grad)
    g = jax.grad(lambda q_: (flash_attention(q_, k, v, mask=keep) ** 2).mean())(q)
    gr = jax.grad(lambda q_: (_sdpa_reference(q_, k, v, keep, 0.0, False, None) ** 2).mean())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-4, atol=1e-5)


def test_flash_attention_fully_masked_rows_zero():
    """A query row with NO visible keys returns zeros with zero grads
    (the dense softmax reference would produce NaN there)."""
    q, k, v = _rand(1, 32, 2, 16, seed=20)
    keep = np.ones((1, 1, 32, 32), bool)
    keep[0, 0, 5, :] = False  # row 5 sees nothing
    keep = jnp.asarray(keep)
    out = flash_attention(q, k, v, mask=keep)
    np.testing.assert_array_equal(np.asarray(out)[0, 5], 0.0)
    assert not np.isnan(np.asarray(out)).any()

    g = jax.grad(lambda q_: (flash_attention(q_, k, v, mask=keep) ** 2).sum())(q)
    np.testing.assert_array_equal(np.asarray(g)[0, 5], 0.0)
    assert not np.isnan(np.asarray(g)).any()

    # causal with tq > tk: leading rows see no keys -> zeros, not NaN
    q2, k2, v2 = _rand(1, 20, 1, 8, seed=21)
    out2 = flash_attention(q2, k2[:, :15], v2[:, :15], causal=True)
    np.testing.assert_array_equal(np.asarray(out2)[0, :4], 0.0)
    assert not np.isnan(np.asarray(out2)).any()


def test_flash_attention_singleton_tq_bias_grad():
    q, k, v = _rand(1, 32, 2, 16, seed=12)
    rng = np.random.default_rng(13)
    bias = jnp.asarray(rng.standard_normal((1, 1, 1, 32)), jnp.float32)

    g_pl = jax.grad(
        lambda b_: (flash_attention(q, k, v, bias=b_) ** 2).mean()
    )(bias)
    g_ref = jax.grad(
        lambda b_: (_sdpa_reference(q, k, v, b_, 0.0, False, None) ** 2).mean()
    )(bias)
    np.testing.assert_allclose(np.asarray(g_pl), np.asarray(g_ref), rtol=1e-4, atol=1e-6)


def test_sdpa_float_mask_never_differentiated():
    """Float attn_mask is mask-semantics: zero grad on EVERY backend path."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional import attention as attn_mod

    q, k, v = _rand(1, 32, 2, 16, seed=14)
    mask = paddle.to_tensor(
        np.random.default_rng(15).standard_normal((1, 2, 32, 32)).astype("float32")
    )
    mask.stop_gradient = False
    out = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        attn_mask=mask,
    )
    (out ** 2).mean().backward()
    assert mask.grad is None or float(np.abs(np.asarray(mask.grad._value)).max()) == 0.0


def test_flash_attention_gqa():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)
    krep = jnp.repeat(k, 2, axis=2)
    vrep = jnp.repeat(v, 2, axis=2)

    out = flash_attention(q, k, v, causal=True)
    ref = _sdpa_reference(q, krep, vrep, None, 0.0, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    # GQA grads: dk/dv group-sum path
    g_pl = jax.grad(
        lambda k_, v_: (flash_attention(q, k_, v_, causal=True) ** 2).mean(),
        argnums=(0, 1),
    )(k, v)
    g_ref = jax.grad(
        lambda k_, v_: (
            _sdpa_reference(q, jnp.repeat(k_, 2, 2), jnp.repeat(v_, 2, 2),
                            None, 0.0, True, None) ** 2
        ).mean(),
        argnums=(0, 1),
    )(k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_flash_attention_cross_length():
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((1, 40, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 96, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 96, 2, 16)), jnp.float32)
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal)
        ref = _sdpa_reference(q, k, v, None, 0.0, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_long_seq_grads():
    """VERDICT #3 'done' criterion: grad parity vs dense at T>=4k.

    Uses one head / d=32 to keep the interpreted-kernel runtime sane; the
    block structure exercised is the same as production shapes.
    """
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.default_rng(9)
    t = 4096
    q = jnp.asarray(rng.standard_normal((1, t, 1, 32)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, t, 1, 32)) * 0.1, jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, t, 1, 32)) * 0.1, jnp.float32)

    old_bq, old_bk = fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K
    fa.DEFAULT_BLOCK_Q = fa.DEFAULT_BLOCK_K = 512
    try:
        g_pl = jax.grad(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
    finally:
        fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = old_bq, old_bk
    g_ref = jax.grad(
        lambda q_, k_, v_: _sdpa_reference(q_, k_, v_, None, 0.0, True, None).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


# one backward key block covers every key: dQ comes out of the dK/dV kernel
# (``flash_attention_bwd_fused``); a key block below Tk keeps two kernels
FUSED_BWD_CASES = {
    # tq, tk, heads, kv heads, causal, (B,1,1,Tk) keep-mask, bwd q block
    "noncausal": (100, 100, 2, 2, False, False, None),
    "causal": (100, 100, 2, 2, True, False, None),
    # 56 leading rows see no key; q block 0 (rows 0-31) has none at all
    "causal_tq_gt_tk": (96, 40, 2, 2, True, False, 32),
    "gqa": (64, 64, 4, 2, True, False, None),
    "padding_mask": (64, 64, 2, 2, False, True, None),
}


def _bwd_grads(q, k, v, cot, causal, keep):
    return jax.grad(
        lambda q_, k_, v_: (flash_attention(q_, k_, v_, causal=causal,
                                            mask=keep) * cot).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _bwd_kernels(fn, *args):
    """The backward kernels named in ``fn``'s program lowered for a TPU."""
    import re

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    return set(re.findall(r"flash_attention_bwd_(?:fused|dq|dkv)", text))


@pytest.mark.parametrize("case", list(FUSED_BWD_CASES))
def test_flash_backward_fused_matches_two_kernels(monkeypatch, case):
    tq, tk, h, hkv, causal, masked, block_q = FUSED_BWD_CASES[case]
    if block_q is not None:
        monkeypatch.setenv("PADDLE_TPU_FLASH_BWD_BLOCK_Q", str(block_q))
    rng = np.random.default_rng(30)
    q = jnp.asarray(rng.standard_normal((2, tq, h, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, tk, hkv, 16)), jnp.float32)
            for _ in range(2))
    cot = jnp.asarray(rng.standard_normal((2, tq, h, 16)), jnp.float32)
    keep = None
    if masked:
        keep = np.ones((2, 1, 1, tk), bool)
        keep[1, :, :, tk - 16:] = False  # the second row's padded tail
        keep = jnp.asarray(keep)

    fused = _bwd_grads(q, k, v, cot, causal, keep)
    monkeypatch.setenv("PADDLE_TPU_FLASH_BWD_BLOCK_K", "16")  # 16 < tk
    two = _bwd_grads(q, k, v, cot, causal, keep)
    for a, b in zip(fused, two):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # the dense reference: GQA's heads repeated, and for tq > tk only the
    # rows that see a key (bottom-right aligned, they are the last tk)
    dead = max(tq - tk, 0) if causal else 0
    rep = h // hkv
    ref = jax.grad(
        lambda q_, k_, v_: (_sdpa_reference(
            q_, jnp.repeat(k_, rep, 2), jnp.repeat(v_, rep, 2), keep, 0.0,
            causal, None) * cot[:, dead:]).sum(),
        argnums=(0, 1, 2))(q[:, dead:], k, v)
    dq, dk, dv = fused
    np.testing.assert_array_equal(np.asarray(dq)[:, :dead], 0.0)
    for a, b in zip((dq[:, dead:], dk, dv), ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_backward_trained_bias_keeps_two_kernels():
    """dbias sums the dS tensor the dQ kernel writes: a trained bias keeps
    that kernel even when one key block covers every key."""
    q, k, v = _rand(1, 32, 2, 16, seed=31)
    bias = jnp.asarray(
        np.random.default_rng(32).standard_normal((1, 2, 32, 32)), jnp.float32)

    def grads(q_, k_, bias_):
        return jax.grad(
            lambda a, b, c: (flash_attention(a, b, v, causal=True, bias=c)
                             ** 2).mean(), argnums=(0, 1, 2))(q_, k_, bias_)

    assert _bwd_kernels(grads, q, k, bias) == {
        "flash_attention_bwd_dq", "flash_attention_bwd_dkv"}
    g_pl = grads(q, k, bias)
    g_ref = jax.grad(
        lambda a, b, c: (_sdpa_reference(a, b, v, c, 0.0, True, None)
                         ** 2).mean(), argnums=(0, 1, 2))(q, k, bias)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t, kernels", [
    (64, {"flash_attention_bwd_fused"}),
    (72, {"flash_attention_bwd_dq", "flash_attention_bwd_dkv"}),
], ids=["t_le_block_k", "t_gt_block_k"])
def test_flash_backward_kernels_in_lowered_program(monkeypatch, t, kernels):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BWD_BLOCK_K", "64")
    x = jax.ShapeDtypeStruct((1, t, 2, 64), jnp.bfloat16)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(*a, causal=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    assert _bwd_kernels(fwd_bwd, x, x, x) == kernels


def test_sdpa_routes_to_flash_kernel(monkeypatch):
    """The public functional uses the Pallas kernel when mask/dropout allow.

    On non-TPU backends the route is gated off (interpret mode is too slow
    for real use); PADDLE_TPU_PALLAS_INTERPRET=1 forces it so this test
    exercises the actual kernel dispatch on the CPU mesh."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops.pallas import flash_attention as fa_mod

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    calls = []
    real = fa_mod.flash_attention
    monkeypatch.setattr(
        fa_mod, "flash_attention",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    q, k, v = _rand(1, 32, 2, 16)
    out = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v), is_causal=True
    )
    assert calls, "Pallas kernel was not invoked by the sdpa route"
    ref = _sdpa_reference(q, k, v, None, 0.0, True, None)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_route_is_off_in_programs_gspmd_partitions(monkeypatch):
    """The TPU compiler refuses a Mosaic kernel in a program GSPMD
    partitions ("cannot be automatically partitioned", found when the Fleet
    dp2 x mp2 step was compiled for four chips): the route is on for one
    device and inside a fully manual shard_map region, off under a mesh of
    several devices."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.nn.functional import attention as attn_mod

    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with _mesh.global_mesh(None):
        assert attn_mod._pallas_backend_ok()
    m = _mesh.build_mesh((2, 2), ("dp", "mp"), devices=jax.devices()[:4])
    with _mesh.global_mesh(m):
        assert not attn_mod._pallas_backend_ok()
        seen = []
        jax.shard_map(
            lambda x: (seen.append(attn_mod._pallas_backend_ok()), x)[1],
            mesh=m, in_specs=P("dp", "mp"), out_specs=P("dp", "mp"),
        )(jnp.zeros((2, 2)))
        assert seen == [True]
        seen.clear()
        jax.jit(jax.shard_map(
            lambda x: (seen.append(attn_mod._pallas_backend_ok()), x)[1],
            mesh=m, in_specs=P("dp"), out_specs=P("dp"),
            axis_names=frozenset({"dp"}), check_vma=False,
        ))(jnp.zeros((2, 2)))
        assert seen == [False]  # mp is still GSPMD's inside this region


@pytest.mark.fast
def test_ring_attention_exactness():
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(sep_degree=8)
    fleet.init(is_collective=True, strategy=s)
    q, k, v = _rand(2, 64, 2, 16)
    for causal in (False, True):
        out = context_parallel_attention(q, k, v, causal=causal)
        ref = _sdpa_reference(q, k, v, None, 0.0, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_ring_attention_grad():
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(sep_degree=8)
    fleet.init(is_collective=True, strategy=s)
    q, k, v = _rand(1, 32, 2, 8)
    g = jax.grad(lambda q: (context_parallel_attention(q, k, v, causal=True) ** 2).mean())(q)
    gr = jax.grad(lambda q: (_sdpa_reference(q, k, v, None, 0.0, True, None) ** 2).mean())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-4, atol=1e-6)


@pytest.mark.fast
def test_flash_attn_unpadded_segment_masked():
    """nn.functional.flash_attention submodule parity: the varlen entry
    point equals per-sequence dense attention on the unpacked slices."""
    from paddle_tpu.nn.functional.flash_attention import flash_attn_unpadded

    rng = np.random.default_rng(0)
    lens = [5, 9, 3]
    total, h, d = sum(lens), 2, 16
    q = jnp.asarray(rng.standard_normal((total, h, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, h, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, h, d)) * 0.3, jnp.float32)
    cu = np.cumsum([0] + lens).astype("int32")
    scale = 1.0 / np.sqrt(d)

    for causal in (False, True):
        out, _ = flash_attn_unpadded(
            paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
            paddle.to_tensor(np.asarray(v)), paddle.to_tensor(cu),
            paddle.to_tensor(cu), max(lens), max(lens), scale, causal=causal)
        got = np.asarray(out._value)
        for i in range(len(lens)):
            s, e = cu[i], cu[i + 1]
            ref = _sdpa_reference(
                q[None, s:e], k[None, s:e], v[None, s:e], None, 0.0,
                causal, scale)
            np.testing.assert_allclose(
                got[s:e], np.asarray(ref)[0], rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_flash_attn_unpadded_decode_and_padding():
    """Bottom-right causal alignment for q-len != k-len (decode-style) and
    finite grads with padding tokens beyond cu_seqlens[-1]."""
    from paddle_tpu.nn.functional.flash_attention import flash_attn_unpadded

    rng = np.random.default_rng(1)
    h, d = 2, 8
    # one sequence: 1 query vs 5 cached keys, causal -> ALL keys visible
    q = rng.standard_normal((1, h, d)).astype("float32")
    k = rng.standard_normal((5, h, d)).astype("float32")
    v = rng.standard_normal((5, h, d)).astype("float32")
    scale = 1.0 / np.sqrt(d)
    out, _ = flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(np.asarray([0, 1], "int32")),
        paddle.to_tensor(np.asarray([0, 5], "int32")), 1, 5, scale, causal=True)
    ref = _sdpa_reference(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        None, 0.0, True, scale)  # dense path is bottom-right aligned
    np.testing.assert_allclose(
        np.asarray(out._value), np.asarray(ref)[0], rtol=2e-4, atol=2e-5)

    # padding tail: rows beyond cu[-1] emit zeros and grads stay finite
    total = 8  # cu[-1] = 6, two padded slots
    qq = paddle.to_tensor(rng.standard_normal((total, h, d)).astype("float32"))
    kk = paddle.to_tensor(rng.standard_normal((total, h, d)).astype("float32"))
    vv = paddle.to_tensor(rng.standard_normal((total, h, d)).astype("float32"))
    cu = paddle.to_tensor(np.asarray([0, 4, 6], "int32"))
    qq.stop_gradient = False
    vv.stop_gradient = False
    out2, _ = flash_attn_unpadded(qq, kk, vv, cu, cu, 4, 4, scale, causal=True)
    assert np.all(np.asarray(out2._value)[6:] == 0)
    loss = (out2 ** 2).sum()
    loss.backward()
    assert np.isfinite(np.asarray(qq.grad._value)).all()
    assert np.isfinite(np.asarray(vv.grad._value)).all()


@pytest.mark.fast
def test_flash_attn_unpadded_qlen_exceeds_klen():
    """Causal rows with ZERO visible keys (per-sequence q-len > k-len under
    bottom-right alignment) emit zeros — not NaN — and grads stay finite."""
    from paddle_tpu.nn.functional.flash_attention import flash_attn_unpadded

    rng = np.random.default_rng(2)
    h, d = 2, 8
    q = paddle.to_tensor(rng.standard_normal((5, h, d)).astype("float32"))
    k = paddle.to_tensor(rng.standard_normal((3, h, d)).astype("float32"))
    v = paddle.to_tensor(rng.standard_normal((3, h, d)).astype("float32"))
    q.stop_gradient = False
    v.stop_gradient = False
    out, _ = flash_attn_unpadded(
        q, k, v, paddle.to_tensor(np.asarray([0, 5], "int32")),
        paddle.to_tensor(np.asarray([0, 3], "int32")), 5, 3, d ** -0.5,
        causal=True)
    got = np.asarray(out._value)
    assert np.isfinite(got).all()
    assert np.all(got[:2] == 0)  # first 2 rows see nothing (bottom-right)
    assert np.abs(got[2:]).max() > 0
    loss = (out ** 2).sum()
    loss.backward()
    assert np.isfinite(np.asarray(q.grad._value)).all()
    assert np.isfinite(np.asarray(v.grad._value)).all()
