"""The generator is deterministic per seed, every seed gets the same
sizes in another order, and the sizes follow the mix's distributions."""

import itertools
import math
import statistics

import numpy as np

from bench import spec, traffic


def _take(mix, seed, n, rate=None, vocab=1000):
    return list(itertools.islice(
        traffic.stream(mix, seed, vocab, rate=rate), n))


def test_deterministic_per_seed():
    mix = spec.cell("stablelm-2-1.6b.chat").mix
    a, b = _take(mix, 2**31 + 5, 300, 4.0), _take(mix, 2**31 + 5, 300, 4.0)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens
               and x.offset_s == y.offset_s for x, y in zip(a, b))
    c = _take(mix, 2**31 + 6, 300, 4.0)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_same_sizes_other_order():
    mix = spec.cell("stablelm-2-1.6b.chat").mix
    n = mix["set_size"]
    a, b = _take(mix, 1, n, 4.0), _take(mix, -7, n, 4.0)
    la = sorted(len(x.prompt) for x in a)
    assert la == sorted(len(x.prompt) for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    # the same arrival gaps too: the last offset is their sum
    assert math.isclose(a[-1].offset_s, b[-1].offset_s, rel_tol=1e-9)


def test_chat_distributions():
    mix = spec.cell("stablelm-2-1.6b.chat").mix
    # the quantile sets follow the stated distributions
    p = traffic.quantiles(mix["prompt_tokens"], 2000)
    o = traffic.quantiles(mix["output_tokens"], 2000)
    assert abs(statistics.median(p) - 256) <= 1
    assert abs(statistics.median(o) - 128) <= 1
    # lognormal sigma 0.8: the quartiles sit at median x exp(+-0.674 sigma)
    q1, _, q3 = statistics.quantiles(p, n=4)
    assert abs(math.log(q3 / q1) / (2 * 0.6745) - 0.8) < 0.01
    assert abs(np.mean(traffic.exp_gaps(5.0, 2000)) - 1 / 5.0) < 0.002
    # and a stream draws its sizes from the set, within the clip bounds
    items = _take(mix, 3, 4 * mix["set_size"], 5.0)
    p = [len(x.prompt) for x in items]
    o = [x.max_new_tokens for x in items]
    assert min(p) >= 32 and max(p) <= 1024
    assert min(o) >= 16 and max(o) <= 512
    assert sorted(p[:mix["set_size"]]) == sorted(
        traffic.quantiles(mix["prompt_tokens"], mix["set_size"]))
    gaps = np.diff([0.0] + [x.offset_s for x in items])
    assert abs(np.mean(gaps) - 1 / 5.0) < 0.02
    assert all(1 <= t < 1000 for x in items[:20] for t in x.prompt)


def test_docs_distributions():
    mix = spec.cell("qwen3-14b-pp4.docs").mix
    items = _take(mix, 4, 2 * mix["set_size"])
    p = [len(x.prompt) for x in items]
    o = [x.max_new_tokens for x in items]
    assert min(p) >= 1024 and max(p) <= 7680
    assert abs(statistics.median(traffic.quantiles(
        mix["prompt_tokens"], 2000)) - 3072) <= 2
    assert abs(statistics.median(p) - 3072) <= 200
    assert min(o) >= 16 and max(o) <= 64
    assert abs(statistics.mean(o) - 40) <= 1
    assert all(x.offset_s == 0.0 for x in items)   # closed loop
