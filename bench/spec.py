"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout root
names each workload's configuration and traffic mix; their files live in
``bench/configs/``, ``bench/mixes/`` and ``bench/cells/``, and each
per-layer metric's reader in ``bench/metrics/``.  Adding a cell, a
configuration, a mix or a metric means adding files only."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # bench/configs/<config>.json
    traffic_name: str
    mix: dict             # bench/mixes/<traffic>.json
    settings: dict        # bench/cells/<workload>.json
    end_to_end: list      # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list       # BENCHMARK.json per_layer entries this cell reports


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    # a per-layer metric without a workloads list goes with every cell that
    # reports the end-to-end metric it moves
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=_load(BENCH / "configs" / f"{w['config']}.json"),
        traffic_name=w["traffic"],
        mix=_load(BENCH / "mixes" / f"{w['traffic']}.json"),
        settings=_load(BENCH / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def reference(architecture: str):
    """The plain reference module ``bench/reference/<architecture>.py``."""
    return importlib.import_module(f"bench.reference.{architecture}")


def peaks(device_kind: str) -> dict:
    table = _load(BENCH / "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"bench/peaks.json; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return table[device_kind]
