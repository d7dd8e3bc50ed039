"""The reduction from trace events to busy time, idle gaps and time per
protected site, on small traces whose answers are worked out by hand."""

import pytest

from bench import readers, trace

MS = 1_000_000  # ns

# two overlapping ops, a gap, a protected site with its check, another gap
OPS = [
    (0 * MS, 4 * MS, "fusion.1", "jit(step)/abft[block_1s][attn.q]/x"),
    (2 * MS, 5 * MS, "fusion.2", "jit(step)/abft[block_1s][attn.q]/y"),
    (7 * MS, 9 * MS, "convolution.3",
     "jit(step)/while/body/abft[global][mlp.up]/dot_general"),
    (9 * MS, 10 * MS, "fusion.4",
     "jit(step)/while/body/abft[global][mlp.up]/jit(_einsum)/dot_general"),
    (10 * MS, 11 * MS, "reduce.5",
     "jit(step)/while/body/abft[global][mlp.up]/reduce_sum"),
    (12 * MS, 13 * MS, "fusion.6", ""),
]
WINDOW = 16 * MS
HOST = [(0, 16 * MS, "bench.step"), (5 * MS, 7 * MS, "bench.admit"),
        (13 * MS, 16 * MS, "bench.wait")]


def test_busy_is_the_union():
    assert trace.merge(OPS) == [[0, 5 * MS], [7 * MS, 11 * MS],
                                [12 * MS, 13 * MS]]
    assert trace.busy_ns(OPS, WINDOW) == 10 * MS
    # clipped to the window
    assert trace.busy_ns(OPS, 8 * MS) == 6 * MS


def test_idle_gaps_and_their_host_spans():
    gaps = trace.idle_gaps(OPS, WINDOW)
    assert gaps == [(5 * MS, 7 * MS), (11 * MS, 12 * MS),
                    (13 * MS, 16 * MS)]
    labelled = trace.label_gaps(gaps, HOST)
    assert labelled[0] == ["bench.wait", pytest.approx(0.003)]
    assert labelled[1] == ["bench.admit", pytest.approx(0.002)]
    assert labelled[2] == ["bench.step", pytest.approx(0.001)]


def test_site_attribution():
    assert trace.site_seconds(OPS) == {
        "attn.q": pytest.approx(0.007), "mlp.up": pytest.approx(0.004)}
    assert trace.site_seconds(OPS, "global") == {
        "mlp.up": pytest.approx(0.004)}
    # the check is what sits under the global scope besides the product
    assert trace.check_seconds(OPS) == pytest.approx(0.002)
    assert trace.check_seconds(OPS[:2]) is None
    top = dict(trace.top_ops(OPS))
    assert top["abft[block_1s][attn.q]"] == pytest.approx(0.007)
    assert top["fusion"] == pytest.approx(0.001)


def test_idle_share_reader():
    class Run:
        ops, window_s = OPS, WINDOW / 1e9
    assert readers.idle_share(Run) == pytest.approx(100 * 6 / 16)
