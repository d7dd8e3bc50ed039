"""Serving observability: metrics registry (JSON export), host-phase
spans (jax.profiler TraceMe events, optionally recorded as
Chrome-trace/Perfetto JSON), and the fault-rate monitor feeding
adaptive protection (ROADMAP item 5b)."""

from repro.obs.faultrate import FaultRateMonitor
from repro.obs.metrics import (
    ITL_BUCKETS_S,
    STEP_LATENCY_BUCKETS_S,
    TTFT_BUCKETS_S,
    CardinalityError,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RegistrationError,
)
from repro.obs.telemetry import ENGINE_COUNTERS, EngineTelemetry
from repro.obs.trace import Tracer, check_events

__all__ = [
    "CardinalityError",
    "Counter",
    "ENGINE_COUNTERS",
    "EngineTelemetry",
    "FaultRateMonitor",
    "Gauge",
    "Histogram",
    "ITL_BUCKETS_S",
    "MetricsRegistry",
    "RegistrationError",
    "STEP_LATENCY_BUCKETS_S",
    "TTFT_BUCKETS_S",
    "Tracer",
    "check_events",
]
