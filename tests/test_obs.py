"""Serving telemetry subsystem (repro/obs): metrics registry export
invariants, trace-JSON validity, fault-rate monitor math, engine
integration (mirrored counters exact, byte-identical streams, fault
spans), stride-decimation alignment, heartbeat gauges, and the launch
driver's --metrics-out/--trace-out artifacts.
"""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.core import ABFTConfig, FaultSpec, Scheme
from repro.core.hardware import HardwareSpec
from repro.models import ModelFault, build_model
from repro.obs import (
    ENGINE_COUNTERS,
    CardinalityError,
    EngineTelemetry,
    FaultRateMonitor,
    MetricsRegistry,
    RegistrationError,
    Tracer,
    check_events,
)
from repro.runtime.heartbeat import HeartbeatMonitor
from repro.serve.engine import EngineStats, Request, ServeEngine

ABFT = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False)

# same spec as tests/test_chunked_prefill.py: selection flips between
# block_1s (decode-only, m <= 16) and global (mixed, m >= 32) on the
# scaled test model
FLIP_HW = HardwareSpec(
    name="flip", peak_flops=1e10, vpu_flops=2.6e8, hbm_bw=1e9,
    ici_bw=1e9, hbm_bytes=1 << 30, vmem_bytes=1 << 20,
    fixed_op_overhead_s=1e-6)


@pytest.fixture(scope="module")
def small_model():
    cfg = scaled_down(get_config("llama3.2-1b"), n_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, model, params


def _reqs(spec):
    return [Request(uid=i, prompt=np.arange(1, 1 + L, dtype=np.int32),
                    max_new_tokens=n)
            for i, (L, n) in enumerate(spec)]


def _profiled_spans(log_dir) -> list:
    """[(name, start_ns, end_ns, args)] of the ``serve.*`` host events in
    the one ``.xplane.pb`` under ``log_dir``, by start time."""
    paths = list(Path(log_dir).rglob("*.xplane.pb"))
    assert len(paths) == 1, paths
    data = jax.profiler.ProfileData.from_file(str(paths[0]))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _children(parent, spans) -> list:
    """Names of the spans whose innermost enclosing span is ``parent``,
    in start order."""
    def inside(a, b):
        return b is not a and b[1] <= a[1] and a[2] <= b[2]

    out = []
    for sp in spans:
        if not inside(sp, parent):
            continue
        if not any(inside(sp, mid) and inside(mid, parent)
                   for mid in spans):
            out.append(sp[0])
    return out


# ==================================================== metrics registry

class TestMetrics:
    def test_counter_inc_and_negative_raises(self):
        c = MetricsRegistry().counter("c_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_counter_inc_to_monotonic(self):
        c = MetricsRegistry().counter("c_total")
        c.inc_to(7)
        c.inc_to(7)                      # equal is fine
        assert c.value == 7
        with pytest.raises(ValueError):
            c.inc_to(6)

    def test_gauge_set_inc_dec(self):
        g = MetricsRegistry().gauge("g")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value == 3.0

    def test_label_validation(self):
        r = MetricsRegistry()
        c = r.counter("lc_total", labels=("scheme",))
        with pytest.raises(ValueError):
            c.labels(wrong="x")
        with pytest.raises(ValueError):
            c.labels()                   # missing declared label
        with pytest.raises(ValueError):
            c.inc()                      # label-less access on a family
        with pytest.raises(ValueError):
            r.counter("bad name")
        with pytest.raises(ValueError):
            r.counter("h_total", labels=("le",))

    def test_cardinality_cap(self):
        c = MetricsRegistry().counter(
            "uid_total", labels=("uid",), max_series=4)
        for i in range(4):
            c.labels(uid=i).inc()
        c.labels(uid=0).inc()            # existing series: still fine
        with pytest.raises(CardinalityError):
            c.labels(uid=99)

    def test_registry_idempotent_and_conflict(self):
        r = MetricsRegistry()
        a = r.counter("x_total", labels=("k",))
        assert r.counter("x_total", labels=("k",)) is a
        with pytest.raises(RegistrationError):
            r.gauge("x_total")
        with pytest.raises(RegistrationError):
            r.counter("x_total", labels=("other",))
        h = r.histogram("lat", buckets=(1.0, 2.0))
        assert r.histogram("lat", buckets=(1.0, 2.0)) is h
        with pytest.raises(RegistrationError):
            r.histogram("lat", buckets=(1.0, 2.0, 3.0))

    def test_histogram_invariants(self):
        h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 2.0, 99.0):
            h.observe(v)
        cum = h._default().cumulative()
        assert [c for _, c in cum] == [2, 3, 4, 5]
        assert cum[-1][0] == math.inf
        assert cum[-1][1] == h.count == 5   # +Inf count == count
        assert h.sum == pytest.approx(101.65)
        counts = [c for _, c in cum]
        assert counts == sorted(counts)     # cumulative never decreases
        with pytest.raises(ValueError):
            MetricsRegistry().histogram("bad", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            MetricsRegistry().histogram("bad", buckets=(math.inf,))

    def test_snapshot_is_json_ready(self):
        r = MetricsRegistry()
        r.counter("c_total", "help c").inc(3)
        r.histogram("lat", buckets=(1.0,)).observe(0.5)
        g = r.gauge("g", labels=("w",))
        g.labels(w="a").set(1)
        snap = json.loads(r.to_json())
        assert snap["c_total"]["type"] == "counter"
        assert snap["c_total"]["series"][0]["value"] == 3
        assert snap["g"]["series"][0]["labels"] == {"w": "a"}
        buckets = snap["lat"]["series"][0]["buckets"]
        assert buckets[-1][0] == "+Inf"
        assert buckets[-1][1] == snap["lat"]["series"][0]["count"] == 1

    def test_remove_series(self):
        g = MetricsRegistry().gauge("g", labels=("w",))
        g.labels(w="a").set(1)
        g.remove(w="a")
        assert list(g.series()) == []


# ============================================================= tracing

class TestTrace:
    def test_spans_nest_and_validate(self):
        t = [0]

        def clock():
            t[0] += 1000
            return t[0]

        tr = Tracer(clock=clock)
        with tr.span("outer", {"a": 1}):
            with tr.span("inner") as sp:
                sp.set_args(b=2)
        tr.instant("blip", {"k": "v"})
        evs = tr.events
        assert [e["name"] for e in evs] == ["inner", "outer", "blip"]
        assert evs[0]["ph"] == "X" and evs[0]["args"] == {"b": 2}
        assert evs[2]["ph"] == "i" and evs[2]["s"] == "t"
        assert check_events(evs) == []
        doc = tr.to_dict()
        assert doc["traceEvents"] == evs
        assert doc["otherData"]["dropped_events"] == 0

    def test_disabled_tracer_records_nothing(self):
        """A disabled tracer still opens profiler annotations but keeps
        no in-memory record and calls no sink."""
        seen = []
        tr = Tracer(enabled=False, sink=seen.append)
        with tr.span("a", {"k": 1}) as sp:
            with tr.span("b"):
                sp.set_args(x=1)
        tr.instant("i")
        assert tr.events == [] and tr.dropped == 0 and seen == []
        assert sp.args == {"k": 1, "x": 1}

    def test_span_is_a_profiler_annotation(self, tmp_path):
        """Spans land in a jax.profiler trace with their args (set late
        ones too) as metadata, nested as opened, on the same clock as
        any other TraceMe event — enabled or not."""
        for enabled in (False, True):
            tr = Tracer(enabled=enabled)
            d = tmp_path / str(enabled)
            with jax.profiler.trace(str(d)):
                with tr.span("serve.outer", {"rows": 2}) as sp:
                    with tr.span("serve.inner", {"what": "flag"}):
                        pass
                    sp.set_args(prefill=7)
            evs = _profiled_spans(d)
            assert [e[0] for e in evs] == ["serve.outer", "serve.inner"]
            (_, o0, o1, oargs), (_, i0, i1, iargs) = evs
            assert o0 <= i0 <= i1 <= o1
            assert oargs == {"rows": 2, "prefill": 7}
            assert iargs == {"what": "flag"}
            assert len(tr.events) == (2 if enabled else 0)

    def test_max_events_and_dropped(self):
        tr = Tracer(max_events=2)
        for i in range(5):
            tr.instant(f"e{i}")
        assert len(tr.events) == 2 and tr.dropped == 3
        assert tr.to_dict()["otherData"]["dropped_events"] == 3

    def test_sink_sees_dropped_events_too(self):
        seen = []
        tr = Tracer(max_events=1, sink=seen.append)
        tr.instant("a")
        tr.instant("b")
        assert [e["name"] for e in seen] == ["a", "b"]

    def test_check_events_catches_problems(self):
        bad_phase = [{"name": "x", "ph": "Q", "ts": 0}]
        assert check_events(bad_phase)
        neg = [{"name": "x", "ph": "X", "ts": 1.0, "dur": -2.0}]
        assert check_events(neg)
        overlap = [
            {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0},
            {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0},
        ]
        assert any("overlap" in p for p in check_events(overlap))
        # same intervals on distinct tids: fine
        overlap[1]["tid"] = 1
        assert check_events(overlap) == []


# ==================================================== fault-rate monitor

class TestFaultRate:
    def test_windowed_rates(self):
        m = FaultRateMonitor(window=4)
        for _ in range(3):
            m.observe(steps=1, tokens=2)
        m.observe(steps=1, tokens=2, detections=1, retries=1)
        assert m.window_detection_rate == pytest.approx(0.25)
        assert m.window_detection_rate_per_token == pytest.approx(0.125)
        assert m.window_retry_rate == pytest.approx(0.25)
        assert m.window_hard_fault_rate == 0.0
        # window slides: the faulty observation ages out after 4 more
        for _ in range(4):
            m.observe(steps=1, tokens=2)
        assert m.window_detection_rate == 0.0
        assert m.detections == 1         # lifetime total survives

    def test_ewma(self):
        m = FaultRateMonitor(window=8, alpha=0.5)
        m.observe(steps=1, detections=1)
        assert m.ewma_detections == pytest.approx(0.5)
        m.observe(steps=1)
        assert m.ewma_detections == pytest.approx(0.25)

    def test_snapshot_keys(self):
        m = FaultRateMonitor(window=2)
        m.observe(steps=1, tokens=3, hard_faults=1)
        snap = m.snapshot()
        for k in ("window", "window_detection_rate",
                  "window_detection_rate_per_token", "window_retry_rate",
                  "window_hard_fault_rate", "ewma_detections_per_step",
                  "total_steps", "total_detections"):
            assert k in snap
        assert snap["window_hard_fault_rate"] == 1.0
        assert snap["total_tokens"] == 3
        json.dumps(snap)

    def test_validation(self):
        with pytest.raises(ValueError):
            FaultRateMonitor(window=0)
        with pytest.raises(ValueError):
            FaultRateMonitor(alpha=0.0)

    def test_empty_window_snapshot(self):
        """No observations yet: every rate is 0.0 (not NaN / division
        error) and the snapshot is still JSON-complete."""
        m = FaultRateMonitor(window=4)
        assert m.window_detection_rate == 0.0
        assert m.window_detection_rate_per_token == 0.0
        assert m.window_retry_rate == 0.0
        assert m.window_hard_fault_rate == 0.0
        snap = m.snapshot()
        assert snap["window_filled"] == 0
        assert snap["window_steps"] == 0
        assert snap["total_steps"] == 0
        json.dumps(snap)

    def test_window_of_one_tracks_last_observation_only(self):
        m = FaultRateMonitor(window=1)
        m.observe(steps=1, tokens=2, detections=1)
        assert m.window_detection_rate == 1.0
        m.observe(steps=1, tokens=2)
        # the faulty observation fell out of the 1-deep window …
        assert m.window_detection_rate == 0.0
        assert m.snapshot()["window_filled"] == 1
        # … but the lifetime total keeps it
        assert m.detections == 1

    def test_reset_rebaselines_keeping_lifetime_totals(self):
        m = FaultRateMonitor(window=4, alpha=0.5)
        for _ in range(3):
            m.observe(steps=1, tokens=2, detections=1, retries=1,
                      hard_faults=1)
        assert m.window_detection_rate == 1.0
        assert m.ewma_detections > 0
        m.reset()
        # responsive signals cleared …
        assert m.window_detection_rate == 0.0
        assert m.window_retry_rate == 0.0
        assert m.window_hard_fault_rate == 0.0
        assert m.ewma_detections == 0.0
        assert m.ewma_retries == 0.0
        assert m.ewma_hard_faults == 0.0
        assert m.observations == 0
        assert m.snapshot()["window_filled"] == 0
        # … lifetime audit trail survives
        assert m.steps == 3
        assert m.detections == 3
        assert m.retries == 3
        assert m.hard_faults == 3
        # and the monitor keeps working after the re-baseline
        m.observe(steps=1, detections=1)
        assert m.window_detection_rate == 1.0
        assert m.detections == 4


# ============================================ stride-decimation alignment

def test_selection_trace_decimation_keeps_step_alignment():
    """Regression for the [::2] decimation bug: after ANY number of
    halving rounds, entry k of the trace must be the observation
    numbered (k+1)*stride — i.e. the recorded step ids are exactly the
    multiples of the current stride.  [::2] kept the odd multiples of
    the old stride, which the doubled stride can never produce, so
    alignment broke on the second round."""
    stats = EngineStats()
    stats.MAX_OCCUPANCY_SAMPLES = 8
    n = 70                               # > 3 halving rounds (stride 8)
    for step in range(1, n + 1):
        stats.steps = step
        stats.observe_selection(1, 0, 0.5, "block_1s")
    assert stats.selection_stride == 8
    assert stats.selection_count == n
    for k, entry in enumerate(stats.selection_trace):
        assert entry["step"] == (k + 1) * stats.selection_stride


def test_blocks_used_decimation_keeps_alignment():
    stats = EngineStats()
    stats.MAX_OCCUPANCY_SAMPLES = 8
    n = 70
    for i in range(1, n + 1):
        stats.observe_blocks_used(i)     # observation i records value i
    assert stats.blocks_used_stride == 8
    for k, v in enumerate(stats.blocks_used_samples):
        assert v == (k + 1) * stats.blocks_used_stride
    assert stats.blocks_used_peak == n
    assert stats.blocks_used_count == n


# ==================================================== engine integration

class TestEngineTelemetry:
    def test_counters_match_and_streams_identical(self, small_model):
        """Mirrored counters equal EngineStats exactly after a run, and
        the greedy token streams are byte-identical with no telemetry
        and with the in-memory tracer on and off."""
        _, model, params = small_model
        spec = [(5, 6), (9, 4), (3, 5), (7, 3)]

        def run(telemetry):
            eng = ServeEngine(model, params, slots=2, max_len=64,
                              abft=ABFT, dtype=jnp.float32,
                              telemetry=telemetry)
            reqs = _reqs(spec)
            eng.run(reqs)
            return eng, reqs

        eng0, reqs0 = run(None)
        tel = EngineTelemetry(trace=True)
        eng1, reqs1 = run(tel)
        assert [r.generated for r in reqs1] == \
            [r.generated for r in reqs0]
        assert tel.counters_match(eng1.stats)
        snap = tel.registry.snapshot()
        for name, attr in ENGINE_COUNTERS.items():
            assert snap[name]["series"][0]["value"] == \
                getattr(eng1.stats, attr)
        assert check_events(tel.tracer.events) == []
        names = {e["name"] for e in tel.tracer.events}
        assert {"serve.admit", "serve.prefill", "serve.prefill.wait",
                "serve.step", "serve.decode", "serve.decode.dispatch",
                "serve.decode.wait", "serve.decode.commit"} <= names
        # the same spans with the in-memory record off
        eng2, reqs2 = run(EngineTelemetry(trace=False))
        assert [r.generated for r in reqs2] == \
            [r.generated for r in reqs0]
        assert eng2.telemetry.tracer.events == []

    def test_fault_injection_telemetry(self, small_model):
        """An injected transient fault shows up on every surface: the
        FaultRateMonitor's windowed detection rate, an abft_retry span,
        and a fault_detected instant — and the recovered stream still
        matches the clean run."""
        _, model, params = small_model
        spec = [(5, 8), (7, 8)]

        def run(telemetry, fault_at):
            eng = ServeEngine(model, params, slots=2, max_len=64,
                              abft=ABFT, dtype=jnp.float32,
                              telemetry=telemetry)
            reqs = _reqs(spec)
            eng.run(reqs, fault_at=fault_at)
            return eng, reqs

        _, clean = run(None, None)
        tel = EngineTelemetry(trace=True, fault_window=16)
        fault = (3, ModelFault.at(0, "mlp_down",
                                  FaultSpec.value(0, 1, 1e5)))
        eng, reqs = run(tel, fault)
        assert [r.generated for r in reqs] == \
            [r.generated for r in clean]
        assert eng.stats.faults_detected >= 1
        assert tel.counters_match(eng.stats)
        assert tel.faults.detections == eng.stats.faults_detected
        assert tel.faults.window_detection_rate > 0.0
        assert tel.faults.ewma_detections > 0.0
        names = [e["name"] for e in tel.tracer.events]
        assert "serve.retry" in names
        assert "fault_detected" in names
        assert check_events(tel.tracer.events) == []
        # the windowed-rate gauges were published at sync time
        g = tel.registry.get("abft_detection_rate_window")
        assert g.value == pytest.approx(tel.faults.window_detection_rate)

    def test_scheme_flip_instants(self, small_model):
        """Chunked serving on FLIP_HW crosses the intensity regime
        between mixed and decode-only steps; every crossing emits a
        scheme_flip instant carrying the selection context and bumps
        the mirrored serve_scheme_flips_total counter."""
        _, model, params = small_model
        abft = ABFTConfig(scheme=Scheme.AUTO, use_pallas=False,
                          hardware=FLIP_HW)
        tel = EngineTelemetry(trace=True)
        eng = ServeEngine(model, params, slots=2, max_len=64, abft=abft,
                          dtype=jnp.float32, chunk_tokens=48,
                          telemetry=tel)
        resident = _reqs([(4, 12)])[0]
        eng.admit([resident])
        while eng._prefill_cursors:
            eng.step()
        pending = [Request(uid=10 + i,
                           prompt=np.arange(1, 48, dtype=np.int32),
                           max_new_tokens=2) for i in range(2)]
        while pending or eng.active or eng._prefill_cursors:
            if pending and eng.free_slots():
                eng.admit(pending)
            eng.step()

        flips = [e for e in tel.tracer.events
                 if e["name"] == "scheme_flip"]
        assert eng.stats.scheme_flips >= 2      # enters AND leaves global
        assert len(flips) == eng.stats.scheme_flips
        for f in flips:
            assert f["ph"] == "i"
            assert set(f["args"]) == {"intensity", "scheme", "decode",
                                      "prefill", "model_parallel"}
            assert f["args"]["model_parallel"] == 1
            assert f["args"]["scheme"] in (Scheme.GLOBAL.value,
                                           Scheme.BLOCK_1S.value)
        assert {f["args"]["scheme"] for f in flips} == \
            {Scheme.GLOBAL.value, Scheme.BLOCK_1S.value}
        assert tel.counters_match(eng.stats)
        names = {e["name"] for e in tel.tracer.events}
        assert "serve.chunk" in names
        assert check_events(tel.tracer.events) == []

    def test_step_latency_histogram_fills(self, small_model):
        _, model, params = small_model
        tel = EngineTelemetry()
        eng = ServeEngine(model, params, slots=2, max_len=64, abft=ABFT,
                          dtype=jnp.float32, telemetry=tel)
        eng.run(_reqs([(4, 4), (6, 3)]))
        assert tel.step_latency.count == eng.stats.steps
        cum = tel.step_latency._default().cumulative()
        assert cum[-1][1] == tel.step_latency.count


# ============================================ engine spans in a profile

@pytest.fixture(scope="module")
def profiled_mixed_step(small_model, tmp_path_factory):
    """A chunked engine with the default (disabled) tracer — the
    benchmark's engine — profiled over one admission and one mixed step:
    one resident decode stream beside a prompt's first chunk."""
    _, model, params = small_model
    eng = ServeEngine(model, params, slots=2, max_len=64, abft=ABFT,
                      dtype=jnp.float32, chunk_tokens=16)
    eng.admit(_reqs([(4, 12)]))
    while eng._prefill_cursors:
        eng.step()
    prompt = Request(uid=7, prompt=np.arange(1, 41, dtype=np.int32),
                     max_new_tokens=2)
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        eng.admit([prompt])
        eng.step()
    return eng, _profiled_spans(log_dir)


class TestEngineProfile:
    CALL = ["serve.{c}.prepare", "serve.{c}.dispatch", "serve.{c}.wait",
            "serve.{c}.wait", "serve.{c}.commit"]

    def test_mixed_step_span_tree(self, profiled_mixed_step):
        eng, spans = profiled_mixed_step
        assert eng.telemetry is None and not eng._tr.enabled
        steps = [sp for sp in spans if sp[0] == "serve.step"]
        assert len(steps) == 1
        assert _children(steps[0], spans) == [
            "serve.schedule", "serve.decode", "serve.chunk",
            "serve.account"]
        for c in ("decode", "chunk"):
            call = next(sp for sp in spans if sp[0] == f"serve.{c}")
            assert _children(call, spans) == [
                n.format(c=c) for n in self.CALL]

    def test_admit_span_tree(self, profiled_mixed_step):
        _, spans = profiled_mixed_step
        admits = [sp for sp in spans if sp[0] == "serve.admit"]
        assert len(admits) == 1
        # chunked admission parks the prompt: no model call
        assert _children(admits[0], spans) == ["serve.schedule",
                                               "serve.account"]
        assert admits[0][3] == {"consumed": 1, "admitted": 1}
        assert admits[0][2] <= min(sp[1] for sp in spans
                                   if sp[0] == "serve.step")

    def test_span_args(self, profiled_mixed_step):
        _, spans = profiled_mixed_step
        by = {}
        for sp in spans:
            by.setdefault(sp[0], []).append(sp[3])
        # 1 resident decode token; 16 - 1 = 15 prompt tokens in the chunk
        assert by["serve.step"] == [{"decode": 1, "prefill": 15}]
        assert by["serve.decode"] == [{"rows": 1, "shape": "2x1"}]
        assert by["serve.chunk"] == [{"rows": 1, "shape": "1x16",
                                      "uid": 7}]
        for c in ("decode", "chunk"):
            assert [a["what"] for a in by[f"serve.{c}.wait"]] == \
                ["flag", "tokens"]

    def test_spans_add_no_device_sync(self, small_model, monkeypatch):
        """Recording spans never blocks on the device: the engine's only
        syncs are the readbacks it makes anyway."""
        _, model, params = small_model
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: calls.append(1) or real(x))
        eng = ServeEngine(model, params, slots=2, max_len=64, abft=ABFT,
                          dtype=jnp.float32, chunk_tokens=16,
                          telemetry=EngineTelemetry(trace=True))
        eng.run(_reqs([(20, 3), (5, 4)]))
        assert calls == []
        names = {e["name"] for e in eng.telemetry.tracer.events}
        assert {"serve.chunk.wait", "serve.decode.wait"} <= names


# ======================================================= heartbeat gauges

class TestHeartbeatGauges:
    def test_liveness_and_staleness(self):
        now = [0.0]
        reg = MetricsRegistry()
        mon = HeartbeatMonitor(["w0", "w1"], timeout_s=10.0,
                               clock=lambda: now[0], registry=reg)
        alive = reg.get("worker_alive")
        stale = reg.get("worker_heartbeat_staleness_seconds")
        assert alive.labels(worker="w0").value == 1
        now[0] = 6.0
        mon.beat("w0")
        now[0] = 11.0
        assert mon.check() == ["w1"]
        assert alive.labels(worker="w0").value == 1
        assert alive.labels(worker="w1").value == 0
        assert stale.labels(worker="w0").value == pytest.approx(5.0)
        assert stale.labels(worker="w1").value == pytest.approx(11.0)
        # late beat revives the worker and the gauge follows
        mon.beat("w1")
        assert alive.labels(worker="w1").value == 1
        mon.remove("w1")
        assert all(lab["worker"] != "w1" for lab, _ in alive.series())
        mon.add("w2")
        assert alive.labels(worker="w2").value == 1
        # the JSON export covers the labeled gauges
        series = reg.snapshot()["worker_alive"]["series"]
        assert {"labels": {"worker": "w0"}, "value": 1} in series

    def test_no_registry_is_fine(self):
        mon = HeartbeatMonitor(["a"], timeout_s=1.0, clock=lambda: 0.0)
        mon.beat("a")
        assert mon.check() == []


# ===================================================== launch driver e2e

def test_launch_serve_writes_valid_artifacts(tmp_path):
    """--metrics-out / --trace-out produce artifacts that pass the CI
    telemetry schema gate (mirrored counters equal the final engine
    stats; the trace is Perfetto-valid)."""
    import sys

    from repro.launch.serve import main

    sys.path.insert(0, "benchmarks")
    try:
        from check_telemetry_schema import check
    finally:
        sys.path.pop(0)

    m = tmp_path / "m.json"
    t = tmp_path / "t.json"
    rc = main(["--scale", "smoke", "--requests", "3", "--new-tokens",
               "4", "--slots", "2", "--max-len", "64",
               "--inject-faults",
               "--metrics-out", str(m), "--trace-out", str(t)])
    assert rc == 0
    metrics = json.loads(m.read_text())
    trace = json.loads(t.read_text())
    assert check(metrics, trace) == []
    assert metrics["counters_match_stats"] is True
    assert metrics["engine_stats"]["abft_faults_detected_total"] >= 1
    assert metrics["faultrate"]["total_detections"] >= 1
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"serve.admit", "serve.step", "serve.decode", "serve.retry",
            "fault_detected"} <= names
