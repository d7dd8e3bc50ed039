"""Operation and byte counts against hand-computed values."""

import pytest

from bench import spec, work


def _cfg(name):
    return spec.cell(name).config


def test_stablelm_counts():
    c = _cfg("stablelm-2-1.6b.chat")
    # per layer: q,k,v,o 4 x 2048^2, up+gate 2 x 2048 x 5632, down 5632 x 2048
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert work.stack_flops_per_token(c) == 2 * 24 * per_layer
    assert work.head_flops_per_token(c) == 2 * 2048 * 100352
    # one decode call at m = 16 on mlp.down: 2 m k n, and (mk + kn + mn) x 2
    fl, by = work.site_work(c, "mlp.down", 16)
    assert fl == 2 * 16 * 5632 * 2048 * 24
    assert by == (16 * 5632 + 5632 * 2048 + 16 * 2048) * 2 * 24
    fl, by = work.site_work(c, "mlp.up", 512)      # up and gate
    assert fl == 2 * 2 * 512 * 2048 * 5632 * 24


def test_qwen_stage_counts():
    c = _cfg("qwen3-14b-pp4.docs")
    per_layer = (5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120
                 + 3 * 5120 * 17408)
    assert work.stack_flops_per_token(c) == 2 * 10 * per_layer
    assert work.head_flops_per_token(c) == 2 * 5120 * 151936
    fl, by = work.site_work(c, "lm_head", 6)
    assert fl == 2 * 6 * 5120 * 151936
    assert by == (6 * 5120 + 5120 * 151936 + 6 * 151936) * 2


def test_window_work_takes_the_larger_bound():
    c = _cfg("qwen3-14b-pp4.docs")
    peak, bw = 197e12, 819e9
    least = work.window_work(c, [("decode", 6), ("prefill", 512)], peak, bw)
    fl, by = work.site_work(c, "attn.q", 6)
    fl2, by2 = work.site_work(c, "attn.q", 512)
    assert least["attn.q"] == max(fl / peak, by / bw) + max(fl2 / peak,
                                                            by2 / bw)
    # the head runs on one row for a prefill chunk
    fl3, by3 = work.site_work(c, "lm_head", 1)
    fl4, by4 = work.site_work(c, "lm_head", 6)
    assert least["lm_head"] == max(fl4 / peak, by4 / bw) + max(fl3 / peak,
                                                               by3 / bw)


# recorded from the weights and sites before they moved into
# bench/arch/dense_decoder.py: small widths of each configuration, every
# key and the table order as they were
DIGESTS = {
    "stablelm-2-1.6b.chat":
        "c0164464b72a994286bc442ecf43841da82d6104bb36acb7bb76f2e0b1ff5dfd",
    "qwen3-14b-pp4.docs":
        "80c356082080ce7595e3666d3e31c14a1ac38e73aa8053a56072c7a22bebc160",
}
SITES = {
    "stablelm-2-1.6b.chat": {
        "attn.q": (2048, 2048, 1, 24), "attn.k": (2048, 2048, 1, 24),
        "attn.v": (2048, 2048, 1, 24), "attn.o": (2048, 2048, 1, 24),
        "mlp.up": (2048, 5632, 2, 24), "mlp.down": (5632, 2048, 1, 24),
        "lm_head": (2048, 100352, 1, 1)},
    "qwen3-14b-pp4.docs": {
        "attn.q": (5120, 5120, 1, 10), "attn.k": (5120, 1024, 1, 10),
        "attn.v": (5120, 1024, 1, 10), "attn.o": (5120, 5120, 1, 10),
        "mlp.up": (5120, 17408, 2, 10), "mlp.down": (17408, 5120, 1, 10),
        "lm_head": (5120, 151936, 1, 1)},
}


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_weights_and_sites_as_recorded(name):
    import hashlib

    import numpy as np

    from bench import weights
    from bench.tests.small import small_cell

    cfg = small_cell(name).config
    w = weights.make(cfg, 2**31 + 17)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == DIGESTS[name]
    assert work.sites(_cfg(name)) == SITES[name]
    assert work.device_sites(_cfg(name), 1) == SITES[name]


def test_device_sites_split_by_columns_rows_and_vocabulary():
    c = _cfg("qwen3-14b-pp4.docs")
    assert work.device_sites(c, 4) == {
        "attn.q": (5120, 1280, 1, 10), "attn.k": (5120, 256, 1, 10),
        "attn.v": (5120, 256, 1, 10), "attn.o": (1280, 5120, 1, 10),
        "mlp.up": (5120, 4352, 2, 10), "mlp.down": (4352, 5120, 1, 10),
        "lm_head": (5120, 37984, 1, 1)}
    # the whole model's operations are split four ways
    whole = sum(work.site_work(c, s, 8)[0] for s in work.sites(c))
    shard = sum(work.site_work(c, s, 8, 4)[0] for s in work.sites(c))
    assert 4 * shard == whole
    # a width four does not divide stays whole on every device
    odd = dict(c, num_key_value_heads=1, head_dim=127)
    assert work.device_sites(odd, 4)["attn.k"] == (5120, 127, 1, 10)
