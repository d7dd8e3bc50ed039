"""The engine's ``serve.*`` spans beside the device ops: the readers on
plain tuples, on a recorded TPU v5e trace, and ``program_spans`` on a
profile of a small engine taken here on the CPU."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import engine_spans, program, trace, weights
from bench.tests.small import small_cell

DATA = Path(__file__).parent / "data"
DOCS = json.loads((DATA / "v5e_docs_one_step.json").read_text())
CHAT = json.loads((DATA / "v5e_chat_step_spans.json").read_text())


def _ops(*spans):
    return [(s, e, "op", "") for s, e in spans]


def test_no_program_spans():
    """A trace of a program without the spans: every reader gives None
    and the gaps keep the benchmark's names."""
    ops = [tuple(o) for o in DOCS["ops"]]
    host = [tuple(h) for h in DOCS["host"]]
    w = DOCS["window_ns"]
    gaps = trace.idle_gaps(ops, w)
    assert engine_spans.engine_self_ms([], w) is None
    assert engine_spans.engine_idle_share(ops, [], w) is None
    assert engine_spans.per_step_ms([], w) == {}
    assert engine_spans.label_gaps(gaps, host, []) == \
        trace.label_gaps(gaps, host)


def test_recorded_chat_step():
    """One decode-only chat step recorded on the chip: the engine's host
    time and its share of the idle time, pinned, and the gaps named by
    engine phase where the benchmark's spans say only ``bench.step``."""
    ops = [tuple(o) for o in CHAT["ops"]]
    host = [tuple(h) for h in CHAT["host"]]
    prog = [tuple(p) for p in CHAT["program"]]
    w = CHAT["window_ns"]
    idle = 100.0 * (1 - trace.busy_ns(ops, w) / w)
    assert idle == pytest.approx(8.40361, abs=1e-5)
    assert engine_spans.engine_self_ms(prog, w) == pytest.approx(6.140139)
    share = engine_spans.engine_idle_share(ops, prog, w)
    assert share == pytest.approx(8.37795, abs=1e-5)
    assert share <= idle
    gaps = trace.idle_gaps(ops, w)
    assert engine_spans.label_gaps(gaps, host, prog, 2) == [
        ["serve.decode", pytest.approx(0.00474346425)],
        ["serve.decode.wait", pytest.approx(0.002897309)]]
    assert [n for n, _ in trace.label_gaps(gaps, host, 2)] == \
        ["bench.step", "bench.step"]
    split = engine_spans.idle_by_span(gaps, host, prog)
    assert list(split)[:3] == ["serve.decode", "serve.decode.wait[flag]",
                               "serve.decode.prepare"]
    assert sum(split.values()) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)
    out = engine_spans.summary(
        {"window_ns": w, "devices": {"/device:TPU:0": ops}, "host": host},
        prog)
    assert out["engine_idle_share"] == share
    assert out["idle_gaps"][0][0] == "serve.decode"
    assert out["per_step_ms"]["serve.decode.wait[flag]"] == \
        pytest.approx(84.300488)


def test_window_edge():
    """A step cut by the window's edge is left out of the mean; root
    spans count only inside the window."""
    w = 100.0
    ops = _ops((10, 40), (60, 90))
    prog = [(-5, 50, "serve.step", {}),
            (20, 45, "serve.decode.wait", {"what": "flag"}),
            (55, 95, "serve.step", {}),
            (60, 90, "serve.decode.wait", {"what": "flag"}),
            (62, 70, "serve.decode.wait", {"what": "tokens"}),
            (97, 120, "serve.admit", {})]
    assert engine_spans.engine_self_ms(prog, w) == pytest.approx(10 / 1e6)
    # idle [0,10] [40,60] [90,100]; inside roots [0,50] [55,95] [97,100]
    assert engine_spans.engine_idle_share(ops, prog, w) == \
        pytest.approx(33.0)
    assert engine_spans.per_step_ms(prog, w) == {
        "serve.step": pytest.approx(40 / 1e6),
        "serve.decode.wait[flag]": pytest.approx(55 / 1e6),
        "serve.decode.wait[tokens]": pytest.approx(8 / 1e6)}


@pytest.mark.parametrize("seed", range(5))
def test_engine_idle_share_is_part_of_idle_share(seed):
    r = np.random.default_rng(seed)
    w = 1e6
    starts = np.sort(r.uniform(-1e4, w, 40))
    ops = _ops(*[(s, s + d) for s, d in zip(starts, r.uniform(0, 4e4, 40))])
    roots = np.sort(r.uniform(-1e4, w, 12))
    prog = [(s, s + d, "serve.step" if i % 2 else "serve.admit", {})
            for i, (s, d) in enumerate(zip(roots, r.uniform(0, 9e4, 12)))]
    idle = 100.0 * (1 - trace.busy_ns(ops, w) / w)
    share = engine_spans.engine_idle_share(ops, prog, w)
    assert 0.0 <= share <= idle + 1e-9
    # every idle ns goes to exactly one name
    gaps = trace.idle_gaps(ops, w)
    split = engine_spans.idle_by_span(gaps, [], prog)
    assert sum(split.values()) == pytest.approx(sum(e - s for s, e in gaps)
                                                / 1e9)


def test_program_spans_from_a_cpu_profile(tmp_path):
    """The engine's spans read back from the profiler's own file with
    the benchmark's reader: names without metadata, args, and the same
    origin as the benchmark's spans."""
    cell = small_cell("stablelm-2-1.6b.chat")
    engine, Request, _ = program.build(
        cell.config, cell.config_name, cell.settings,
        weights.make(cell.config, 1), 1)
    req = Request(uid=3, prompt=np.arange(1, 41, dtype=np.int32),
                  max_new_tokens=3)
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.admit"):
            engine.admit([req])
        while not req.done:
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
            steps += 1
    spans = engine_spans.program_spans(str(tmp_path))
    names = [n for _, _, n, _ in spans]
    assert names[0] == "serve.admit" and 0 <= spans[0][0] < 1e6
    assert names.count("serve.step") == steps
    assert all(n.startswith("serve.") and "#" not in n for n in names)
    chunks = [a for _, _, n, a in spans if n == "serve.chunk"]
    assert [a["uid"] for a in chunks] == [3, 3]          # 32 + 8 tokens
    assert chunks[0]["shape"] == "1x32" and chunks[1]["shape"] == "1x8"
    whats = {a["what"] for _, _, n, a in spans if n.endswith(".wait")}
    assert whats == {"flag", "tokens"}
    last = max(e for _, e, _, _ in spans)
    assert engine_spans.engine_self_ms(spans, last) > 0
