"""The system under test, as ``launch/serve.py`` builds it.

This is the one module of the benchmark that imports the program
(``src/repro``): the engine with the intensity-guided policy, bfloat16
on a TPU, the compiled Pallas kernels, a dense cache and a fixed
chunked-prefill budget, on one device or over the cell's mesh.  The
benchmark drives it only through ``ServeEngine.admit`` / ``step`` and
reads ``Request`` stamps and ``EngineStats`` counters.  What differs
between architectures is in ``bench/arch/<architecture>.py``."""

from __future__ import annotations

import sys

from bench import spec
from bench.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def params(model, cfg: dict, w: dict) -> dict:
    """The engine's parameter tree over the benchmark's weights."""
    return spec.arch(cfg["architecture"]).params(model, w)


def build(cfg: dict, name: str, settings: dict, w: dict, seed: int,
          mesh=None):
    """(engine, Request class, EngineStats class) for one cell; ``mesh``
    (a ``jax.sharding.Mesh`` with a ``model`` axis) serves it sharded."""
    from repro.launch.serve import abft_config, serving_dtype
    from repro.models import build_model
    from repro.serve.engine import (EngineStats, RecoveryPolicy, Request,
                                    ServeEngine)

    model = build_model(spec.arch(cfg["architecture"]).model_config(cfg,
                                                                    name))
    engine = ServeEngine(
        model, params(model, cfg, w), slots=settings["slots"],
        max_len=settings["max_len"], abft=abft_config("auto"),
        dtype=serving_dtype(), policy=RecoveryPolicy(max_retries=1),
        cache_kind="dense", chunk_tokens=settings["chunk_tokens"],
        seed=seed % (1 << 31), mesh=mesh)
    return engine, Request, EngineStats
