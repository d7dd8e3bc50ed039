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
        ops, devices, window_s = OPS, {"/device:TPU:0": OPS}, WINDOW / 1e9
    assert readers.idle_share(Run) == pytest.approx(100 * 6 / 16)


# two devices of one sharded step: device 0 waits in an all-reduce (the
# name the trace gives a shard_map psum, with its opcode appended by
# ``load``) while nothing else runs, device 1's exchange overlaps its
# compute; a loop container spans both
TWO = {
    "/device:TPU:0": [
        (0 * MS, 10 * MS, "while.1 while(", ""),
        (0 * MS, 4 * MS, "fusion.1", "jit(step)/abft[block_1s][attn.o]/x"),
        (4 * MS, 6 * MS, "psum.2 all-reduce(",
         "jit(step)/abft[block_1s][attn.o]/shard_map/psum"),
        (6 * MS, 8 * MS, "all-gather-start.3", ""),
        (7 * MS, 8 * MS, "fusion.4", ""),
    ],
    "/device:TPU:1": [
        (0 * MS, 4 * MS, "fusion.1", "jit(step)/abft[block_1s][attn.o]/x"),
        (3 * MS, 5 * MS, "all-reduce.5", ""),
    ],
}


class TwoDevices:
    devices, window_s = TWO, WINDOW / 1e9
    ops = TWO["/device:TPU:1"]


def test_collectives_by_opcode():
    names = [o[2] for ops in TWO.values() for o in ops]
    assert [n for n in names if trace.is_collective(n)] == [
        "psum.2 all-reduce(", "all-gather-start.3", "all-reduce.5"]
    hlo = ('  %psum.2 = bf16[8,64]{1,0} all-reduce(%fusion.1), '
           'channel_id=1, to_apply=%add\n'
           '  ROOT %all-gather-start.3 = (f32[4]) all-gather-start(%x)\n'
           '  %fusion.4 = f32[4] fusion(%psum.2), kind=kLoop, '
           'calls=%fused_computation.4\n'
           '  %fusion.5 = f32[8,16]{1,0} fusion(%fusion.4), kind=kCustom, '
           'calls=%all-reduce-scatter.1.clone, metadata={op_name="x"}\n')
    assert trace.collective_ops(hlo) == {
        "psum.2": "all-reduce", "all-gather-start.3": "all-gather-start",
        "fusion.5": "all-reduce-scatter"}
    assert trace.is_collective("fusion.5 all-reduce-scatter(")


def test_collective_time_alone_on_each_device():
    # device 0: 4-6 ms alone, 6-7 ms alone, 7-8 ms beside fusion.4; the
    # loop container does not count as compute
    assert trace.collective_ns(TWO["/device:TPU:0"], WINDOW) == 3 * MS
    # device 1: 4-5 ms alone
    assert trace.collective_ns(TWO["/device:TPU:1"], WINDOW) == 1 * MS
    assert trace.collective_ns(OPS, WINDOW) is None
    # the exchange is not the site's time
    assert trace.site_seconds(TWO["/device:TPU:0"]) == {
        "attn.o": pytest.approx(0.004)}


def test_many_device_readers():
    # busy: device 0 10 ms (the container), device 1 5 ms, of 16
    assert readers.idle_share(TwoDevices) == pytest.approx(100 * 11 / 16)
    # device 0: 3 of 10 ms; device 1: 1 of 5 ms
    assert readers.collective_share(TwoDevices) == pytest.approx(30.0)


def test_one_device_has_no_collective_share():
    class Run:
        devices, window_s = {"/device:TPU:0": OPS}, WINDOW / 1e9
    assert readers.collective_share(Run) is None


def test_device_record_reads_every_device():
    from bench import harness

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, i, peak):
            self.id, self.peak = i, peak

        def memory_stats(self):
            return {"peak_bytes_in_use": self.peak}

    class Ctx:
        busy_s, window_s = 0.005, 0.016
        busy_by_device = [0.010, 0.005]

    rec = harness.device_record([Dev(0, 7), Dev(1, 9)], Ctx)
    assert rec["count"] == 2 and rec["memory_peak_bytes"] == 9
    assert rec["busy_s"] == 0.005 and rec["window_s"] == 0.016
    assert [d["memory_peak_bytes"] for d in rec["per_device"]] == [7, 9]
    assert [d["idle_share"] for d in rec["per_device"]] == [
        pytest.approx(37.5), pytest.approx(68.75)]
