import json
import os

import numpy as np
import pytest

import harness
import traffic
import work
from conftest import BENCH, ROOT


@pytest.fixture(params=["short_knee80", "short_saturated"])
def mix(request):
    with open(os.path.join(BENCH, "traffic", request.param + ".json")) as f:
        return json.load(f)


def _phase(mix, seed, seconds=51.0):
    sysp = traffic.system_prompts(mix, seed, 50304)
    return traffic.serve_window(mix, seed, seconds, 50304, sysp), sysp


def test_same_seed_same_schedule(mix):
    a, _ = _phase(mix, 2**31 + 5)
    b, _ = _phase(mix, 2**31 + 5)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 51)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert x.greedy == y.greedy and x.seed == y.seed
        assert np.array_equal(x.prompt, y.prompt)


def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    a, _ = _phase(mix, 1)
    b, _ = _phase(mix, 2)
    size = lambda r: (len(r.prompt), r.max_new_tokens, r.greedy,  # noqa: E731
                      r.shared >= 0)
    assert sorted(map(size, a)) == sorted(map(size, b))
    assert [size(r) for r in a] != [size(r) for r in b]
    # the gaps between arrivals are the exponential's quantiles, scaled to
    # the window, whatever the seed (the first gap lies before the window)
    quantiles = traffic._exp_gap_quantiles(len(a), 51.0)
    assert abs(quantiles.sum() - 51.0) < 1e-9
    for reqs in (a, b):
        for g in np.diff([r.due for r in reqs]):
            assert np.min(np.abs(quantiles - g)) < 1e-9


def test_mix_is_what_the_file_says(mix):
    reqs, sysp = _phase(mix, 3)
    assert all(0 <= r.due < 51 for r in reqs)
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)
    n = len(reqs)
    assert abs(sum(r.greedy for r in reqs) - n / 2) <= 1
    shared = [r for r in reqs if r.shared >= 0]
    assert abs(len(shared) - n / 2) <= 1
    for r in shared:
        assert np.array_equal(r.prompt[:128], sysp[r.shared])
        assert len(r.prompt) >= 128 + mix["prompt_len"]["min"]
    for r in reqs:
        assert 16 <= len(r.prompt) <= 1024 and 4 <= r.max_new_tokens <= 128
    assert 200 <= np.median([len(r.prompt) for r in reqs]) <= 320
    assert {r.shared for r in shared} == {0, 1, 2, 3}


def test_train_batches_differ_row_by_row_and_repeat_by_seed():
    mix = {"task": "causal_lm", "batch": 4, "seq": 16}
    a = traffic.TrainBatches(mix, 9, 1000)
    b = traffic.TrainBatches(mix, 9, 1000)
    x, y = a.next()
    assert np.array_equal(x, b.next()[0])
    assert np.array_equal(x[:, 1:], y[:, :-1])
    assert len({tuple(r) for r in x}) == 4
    assert not np.array_equal(x, a.next()[0])


def test_every_batch_holds_the_same_count_of_each_label():
    mix = {"task": "classify", "batch": 32, "seq": 8,
           "label_shares": [0.125, 0.875]}
    feed = traffic.TrainBatches(mix, 3, 1000)
    orders = set()
    for _ in range(5):
        ids, labels = feed.next()
        assert ids.shape == (32, 8) and np.bincount(labels).tolist() == [4, 28]
        orders.add(tuple(labels))
    assert len(orders) > 1


GPT = {"hidden_size": 2048, "intermediate_size": 8192, "vocab_size": 50304,
       "num_hidden_layers": 24, "num_attention_heads": 16}
ERNIE = {"hidden_size": 768, "intermediate_size": 3072, "vocab_size": 40064,
         "num_hidden_layers": 12, "num_attention_heads": 12}


def _arch(name):
    return harness.load_module(ROOT, ["benchmark"], "archs", name + ".py")


def test_model_flops_against_hand_worked_shapes():
    gpt, ernie = _arch("gpt"), _arch("ernie")
    # GPT-3 XL: 24 x (4 x 2048^2 + 2 x 2048 x 8192) + 50304 x 2048
    n_gpt = gpt.matmul_params(GPT)
    assert n_gpt == 24 * 50331648 + 103022592 and gpt.CAUSAL
    # 6N + 12 L h T / 2 at T = 2048: 7.866e9 + 0.604e9
    assert abs(work.train_flops_per_token(n_gpt, 24, 2048, 2048, True)
               / 8.470e9 - 1) < 1e-3
    # ERNIE base: 12 x 7077888 = 84934656; 6N + 12 x 12 x 768 x 1024
    n_ernie = ernie.matmul_params(ERNIE)
    assert n_ernie == 84934656 and not ernie.CAUSAL
    assert work.train_flops_per_token(n_ernie, 12, 768, 1024, False) == (
        6 * 84934656 + 12 * 12 * 768 * 1024)
    # one decoded token at position 9 attends to 10 keys
    assert work.forward_flops(n_gpt, 24, 2048, 1, 10) == (
        2 * n_gpt + 4 * 24 * 2048 * 10)


def test_attention_counts_against_hand_worked_shapes():
    # b1, 1 head, 4 x 4, d 2: forward 2 matmuls of 2*4*4*2 = 64 each
    assert work.attention_flops(1, 1, 4, 4, 2, False, False) == 128
    assert work.attention_flops(1, 1, 4, 4, 2, True, False) == 64
    assert work.attention_flops(1, 1, 4, 4, 2, False, True) == 64 * 7
    # q, k, v, o of 4 x 2 bf16 values each
    assert work.attention_bytes(1, 1, 4, 4, 2, 2, False) == 4 * 16
    assert work.attention_bytes(1, 1, 4, 4, 2, 2, True) == 8 * 16
    # 100 live tokens, 24 layers, K and V, 16 heads x 128, bf16
    assert work.paged_decode_bytes(100, 24, 16, 128, 2) == 100 * 24 * 2 * 4096
    peaks = {"bf16_flops_per_s": 2.0, "hbm_bytes_per_s": 4.0}
    assert work.least_seconds(8.0, 4.0, peaks) == 4.0
    assert work.least_seconds(2.0, 40.0, peaks) == 10.0
