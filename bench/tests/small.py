"""A cell of the benchmark cut to a size the CPU runs in seconds: the
same mix shapes, configuration keys and harness, small widths."""

from __future__ import annotations

import dataclasses

from bench import spec


def small_cell(name: str, limit: float = 0.05):
    base = spec.cell(name)
    qwen = base.config["qk_norm"]
    cfg = dict(base.config, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2 if qwen else 4, head_dim=16,
               vocab_size=512)
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
