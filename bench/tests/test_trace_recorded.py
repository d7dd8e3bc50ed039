"""The reduction on a recorded TPU v5e trace (one engine step of the
docs cell, cut to its ops' names and scopes): pinned values, and the
invariants any trace must keep."""

import json
from pathlib import Path

import pytest

from bench import trace

DATA = json.loads((Path(__file__).parent / "data" /
                   "v5e_docs_one_step.json").read_text())
OPS = [tuple(o) for o in DATA["ops"]]
HOST = [tuple(h) for h in DATA["host"]]
W = DATA["window_ns"]


def test_busy_and_idle():
    busy = trace.busy_ns(OPS, W)
    assert busy == pytest.approx(117395369, abs=1)
    gaps = trace.idle_gaps(OPS, W)
    assert sum(e - s for s, e in gaps) == pytest.approx(W - busy)
    # the longest gap sits inside the step, between its two device calls
    assert trace.label_gaps(gaps, HOST, 1)[0] == [
        "bench.step", pytest.approx(0.006891975)]


def test_sites_and_checks():
    sites = trace.site_seconds(OPS)
    assert set(sites) == {"attn.q", "attn.k", "attn.v", "attn.o", "mlp.up",
                          "mlp.down", "lm_head"}
    assert sites["lm_head"] == pytest.approx(0.017312184)
    glob = trace.site_seconds(OPS, "global")
    assert set(glob) == {"mlp.up", "mlp.down"}     # the 512-token chunk
    check = trace.check_seconds(OPS)
    assert check == pytest.approx(0.007313721)
    assert 0 < check < sum(glob.values())
    assert sum(sites.values()) < trace.busy_ns(OPS, W) / 1e9


def test_breakdown_leaves_out_containers():
    containers = [o for o in OPS if trace.is_container(o[2])]
    assert containers                 # the scanned layer loops
    top = trace.top_ops(OPS, 10)
    assert len(top) == 10
    assert sum(v for _, v in top) <= trace.busy_ns(OPS, W) / 1e9
    assert top[0][0] == "body/squeeze"
