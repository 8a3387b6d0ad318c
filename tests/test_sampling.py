"""The serving programs' on-device sampler (docs/SERVING.md, "On-device
sampling") against the sort it replaced.

``inference/engine.py::_filter_logits`` finds the top-k and top-p cut-offs
by bisection over the float32 bit patterns. The oracle below is the
sort-based formula the engine used before: the k-th largest of the sorted
row, then the smallest sorted probability whose preceding mass is below
top_p. On the same keys both must keep the same tokens and draw the same
token. The one difference allowed is the order of float summation at the
nucleus's edge: a row whose kept sets differ must sit at a float-rounding
boundary (the mass before its edge token within rounding of top_p), and at
most one such row a case.
"""
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.engine import (_filter_logits, _kth_largest,
                                         _nucleus_floor, _sample_tokens)

TOP_KS = (0, 1, 12, "V", "V+5")
TOP_PS = (1.0, 0.95, 0.5, 1e-6)
TEMPS = (1.0, 0.8, 1.3, 0.5)
#: float32 sums of up to 50,304 terms against a float64 sum
MASS_TOL = 1e-5


def _oracle_filter(logits, temperature, top_k, top_p):
    v = logits.shape[-1]
    x = logits / temperature[:, None]
    sorted_x = jnp.sort(x, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_x, (jnp.clip(top_k, 1, v) - 1)[:, None], axis=-1)
    x = jnp.where((top_k[:, None] > 0) & (x < kth), -jnp.inf, x)
    probs = jax.nn.softmax(x, axis=-1)
    sp = jnp.sort(probs, axis=-1)[:, ::-1]
    keep = (jnp.cumsum(sp, axis=-1) - sp) < top_p[:, None]
    thr = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1, keepdims=True)
    return jnp.where((top_p[:, None] < 1.0) & (probs < thr), -jnp.inf, x)


def _oracle_tokens(logits, keys, temperature, top_k, top_p, greedy,
                   exact_argmax=None):
    x = _oracle_filter(logits, temperature, top_k, top_p)
    sampled = jax.vmap(lambda xr, kr: jax.random.categorical(kr, xr))(x, keys)
    arg = (jnp.argmax(logits, axis=-1) if exact_argmax is None
           else exact_argmax)
    return jnp.where(greedy, arg, sampled).astype(jnp.int32)


def _logits(kind, n, v, rng):
    if kind == "normal":
        return rng.normal(0.0, 2.0, (n, v))
    if kind == "ties":  # seven values: every cut-off falls inside a tie
        return rng.integers(-3, 4, (n, v)).astype(np.float64)
    if kind == "one_hot":
        return np.eye(v)[rng.integers(0, v, n)]
    if kind == "uniform":
        return np.zeros((n, v))
    assert kind == "one_finite"
    x = np.full((n, v), -np.inf)
    x[np.arange(n), rng.integers(0, v, n)] = rng.normal(0.0, 2.0, n)
    return x


def _rows(v, top_ks=TOP_KS, top_ps=TOP_PS):
    """One row a (top_k, top_p) pair; temperatures cycle, every third row
    is greedy."""
    pairs = list(itertools.product(top_ks, top_ps))
    n = len(pairs)
    k = np.array([{"V": v, "V+5": v + 5}.get(a, a) for a, _ in pairs],
                 np.int32)
    p = np.array([b for _, b in pairs], np.float32)
    t = np.array([TEMPS[i % len(TEMPS)] for i in range(n)], np.float32)
    greedy = np.arange(n) % 3 == 0
    return n, jnp.asarray(t), jnp.asarray(k), jnp.asarray(p), \
        jnp.asarray(greedy)


def _check_nucleus(probs, kept, top_p):
    """Each filtered row's kept mass reaches top_p, and the mass above its
    smallest kept probability stays below it (float64 sums of the float32
    probabilities)."""
    for r in np.flatnonzero(top_p < 1.0):
        pr, kr = probs[r].astype(np.float64), kept[r]
        floor = pr[kr].min()
        assert pr[kr].sum() >= top_p[r] - MASS_TOL, r
        assert pr[pr > floor].sum() < top_p[r] + MASS_TOL, r


def _boundary_row(probs, kept_a, kept_b, top_p):
    """Whether two kept sets of one row differ only at a float-rounding
    boundary: the tokens in one set and not the other all carry the
    smaller set's next probability, and the mass of the smaller set lies
    within rounding of top_p."""
    small, big = ((kept_a, kept_b) if kept_a.sum() < kept_b.sum()
                  else (kept_b, kept_a))
    extra = big & ~small
    if not extra.any() or (small & ~big).any():
        return False
    pr = probs.astype(np.float64)
    edge = pr[extra]
    return (np.all(edge == edge.max())
            and abs(pr[small].sum() - top_p) <= MASS_TOL)


@pytest.mark.parametrize("v", [257, 50304])
@pytest.mark.parametrize("kind",
                         ["normal", "ties", "one_hot", "uniform", "one_finite"])
def test_bisection_filter_matches_sort_oracle(kind, v):
    rng = np.random.default_rng(v + len(kind))
    n, temp, top_k, top_p, greedy = _rows(v)
    logits = jnp.asarray(_logits(kind, n, v, rng), jnp.float32)
    keys = jax.random.split(jax.random.key(v + 17 * len(kind)), n)

    got_x = np.asarray(jax.jit(_filter_logits)(logits, temp, top_k, top_p))
    want_x = np.asarray(jax.jit(_oracle_filter)(logits, temp, top_k, top_p))
    kept, want_kept = got_x > -np.inf, want_x > -np.inf
    # the top-k-filtered probabilities both nuclei are cut from
    probs = np.asarray(jax.nn.softmax(jax.jit(_oracle_filter)(
        logits, temp, top_k, jnp.ones_like(top_p)), axis=-1))
    tp = np.asarray(top_p)
    _check_nucleus(probs, kept, tp)
    _check_nucleus(probs, want_kept, tp)

    differ = [r for r in range(n) if not np.array_equal(kept[r],
                                                         want_kept[r])]
    assert len(differ) <= 1, differ
    for r in differ:
        assert _boundary_row(probs[r], kept[r], want_kept[r], tp[r]), r
    same = np.setdiff1d(np.arange(n), differ)
    # kept logits are the scaled logits themselves, bit for bit
    np.testing.assert_array_equal(got_x[same], want_x[same])

    exact = (jnp.argmax(logits, axis=-1) + 1) % v
    for exact_argmax in (None, exact):
        got = np.asarray(jax.jit(_sample_tokens)(
            logits, keys, temp, top_k, top_p, greedy, exact_argmax))
        want = np.asarray(jax.jit(_oracle_tokens)(
            logits, keys, temp, top_k, top_p, greedy, exact_argmax))
        np.testing.assert_array_equal(got[same], want[same])
        g = np.asarray(greedy)
        arg = np.asarray(jnp.argmax(logits, axis=-1) if exact_argmax is None
                         else exact_argmax)
        np.testing.assert_array_equal(got[g], arg[g])
        assert np.all(kept[np.arange(n), got] | g)


@pytest.mark.parametrize("filters",
                         ["top_k_only", "top_k_1", "top_p_only", "off"])
def test_filter_skipped_where_no_row_asks(filters):
    """Each cut-off sits behind a test of whether any row asks for it; the
    rows that do not are left as the oracle leaves them."""
    v = 257
    top_ks = {"top_k_only": TOP_KS, "top_k_1": (0, 1)}.get(filters, (0,))
    top_ps = TOP_PS if filters == "top_p_only" else (1.0,)
    n, temp, top_k, top_p, greedy = _rows(v, top_ks, top_ps)
    logits = jnp.asarray(_logits("normal", n, v, np.random.default_rng(5)),
                         jnp.float32)
    got_x = np.asarray(jax.jit(_filter_logits)(logits, temp, top_k, top_p))
    want_x = np.asarray(jax.jit(_oracle_filter)(logits, temp, top_k, top_p))
    np.testing.assert_array_equal(got_x, want_x)
    if filters == "off":
        np.testing.assert_array_equal(
            got_x, np.asarray(logits / temp[:, None]))


def _primitives(fn, *args):
    return set(re.findall(r"\b([a-z_]+)\[", str(jax.make_jaxpr(fn)(*args))))


@pytest.mark.parametrize("n,v", [(64, 151936), (8, 50304), (8, 49152),
                                 (1, 50304)])
def test_sampler_traces_no_sort(n, v):
    """The sampler at the serving programs' shapes (the block pass's 16
    slots x 4 positions over SDAR's vocabulary, the GPT and Ouro decode
    steps, a prefill) holds no sort: traced only, nothing computed."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), n))
    args = (f32(n, v), keys, f32(n), jax.ShapeDtypeStruct((n,), jnp.int32),
            f32(n), jax.ShapeDtypeStruct((n,), jnp.bool_))
    assert "sort" in _primitives(_oracle_tokens, *args)  # the probe sees one
    assert "sort" not in _primitives(_sample_tokens, *args)


@pytest.mark.parametrize("top_p,floors", [(0.5, [0.5, 0.5]),
                                          (0.75, [0.25, 0.25]),
                                          (0.875, [0.125, 0.125]),
                                          (0.95, [0.125, 0.0])])
def test_nucleus_floor_at_exact_masses(top_p, floors):
    """Masses that reach top_p exactly keep the edge token out, as the
    sort's rule does (a token is kept while the mass BEFORE it is below
    top_p); a row whose sum falls short of top_p (the second holds 0.875)
    keeps every token."""
    probs = jnp.asarray([[0.125, 0.5, 0.125, 0.25],
                         [0.25, 0.0, 0.125, 0.5]], jnp.float32)
    top = jnp.full((2,), top_p, jnp.float32)
    got = np.asarray(jax.jit(_nucleus_floor)(probs, top))
    sp = jnp.sort(probs, axis=-1)[:, ::-1]
    keep = (jnp.cumsum(sp, axis=-1) - sp) < top[:, None]
    want = np.asarray(jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1,
                              keepdims=True))
    np.testing.assert_array_equal(got[:, 0], floors)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [1, 2, 3, 4, 5, 6, 7, 8])
def test_kth_largest_orders_as_sort(k):
    """Infinities, signed zeros, ties and a subnormal: the k-th is the
    sort's up to the device's float equality (which holds -0.0 and 0.0
    equal, and may flush the subnormal), and so masks the same tokens."""
    row = np.array([-0.0, 0.0, -np.inf, np.inf, 1.5, -1.5, 1.5, 1e-45],
                   np.float32)
    x = jnp.asarray(np.stack([row, row[::-1], -row]))
    got = jax.jit(_kth_largest)(x, jnp.full((3,), k, jnp.int32))
    want = jnp.sort(x, axis=-1)[:, ::-1][:, k - 1:k]
    assert bool(jnp.all(got == want))
    np.testing.assert_array_equal(np.asarray(x < got), np.asarray(x < want))
