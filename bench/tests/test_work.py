"""Operation and byte counts against hand-computed values."""

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
