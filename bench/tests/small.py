"""A cell of the benchmark cut to a size the CPU runs in seconds: the
same mix shapes, configuration keys and harness, small widths; with
``chips`` > 1, served over a mesh of that many (virtual) devices."""

from __future__ import annotations

import dataclasses

from bench import spec


def small_cell(name: str, limit: float = 0.05, chips: int = 1):
    base = spec.cell(name)
    if chips > 1:
        base = dataclasses.replace(base, name=f"{name}.tp{chips}",
                                   chips=chips)
    qwen = base.config["qk_norm"]
    # on a mesh, two query heads a device, the KV heads split as well
    heads = 4 if chips == 1 else 2 * chips
    kv = heads // 2 if qwen else heads
    cfg = dict(base.config, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=heads,
               num_key_value_heads=kv, head_dim=16, vocab_size=512)
    mix = dict(base.mix)
    if mix["loop"] == "open":
        mix.update(prompt_tokens=dict(mix["prompt_tokens"], median=24,
                                      min=8, max=64),
                   output_tokens=dict(mix["output_tokens"], median=8, min=4,
                                      max=16), set_size=32)
        settings = dict(base.settings, slots=4, max_len=96, chunk_tokens=32,
                        rate_per_s=10.0, check_tokens=40,
                        max_logit_gap=limit)
    else:
        mix.update(prompt_tokens=dict(mix["prompt_tokens"], median=48,
                                      min=16, max=80),
                   output_tokens=dict(mix["output_tokens"], min=16, max=32),
                   set_size=16)
        settings = dict(base.settings, slots=3, max_len=128, chunk_tokens=32,
                        clients=3, check_tokens=30, max_logit_gap=limit)
    return dataclasses.replace(base, config=cfg, mix=mix, settings=settings)
