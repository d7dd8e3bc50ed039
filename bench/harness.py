"""One run of one cell: set-up, warm-up, the measured window, the trace
reduction and the comparison that decides ``correct``.

Time is ``time.perf_counter``, the clock the engine stamps its tokens
with.  The window drives ``ServeEngine.admit`` and ``ServeEngine.step``
from a front end that holds due requests in a queue and admits one
whenever a slot is free and no admitted prompt is still prefilling (one
partial prefill at a time).  That keeps every prefill call at one row,
so the warm-up can compile every shape the window can reach: one chunk
program per length bucket (multiples of 8 up to the chunk budget) and
the decode program.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types

import numpy as np

from bench import correct as correctness
from bench import program, spec, stats, trace, traffic, weights

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0
DRAIN_LIMIT_S = 60.0


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts backend compilations (including loads from the persistent
    cache) while ``on`` is set."""

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == COMPILE_EVENT:
            self.count += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info(cell, require_tpu: bool) -> tuple:
    """(device, peaks).  No TPU, too few chips or an unknown chip kind is
    an error: no number is ever reported for another device."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(f"bench: needs a TPU, JAX found "
                             f"{dev.platform!r}")
        if len(devs) < cell.chips:
            raise SystemExit(f"bench: {cell.name} needs {cell.chips} chips, "
                             f"JAX found {len(devs)}")
        return dev, spec.peaks(dev.device_kind)
    return dev, spec.peaks("TPU v5 lite")


@dataclasses.dataclass
class Window:
    """What the measured window saw, in host-clock seconds."""

    t0: float
    t1: float
    requests: list            # every request that entered the front end
    steps: list               # [(decode tokens, prefill tokens)] per step
    counters: dict            # EngineStats counters summed over the window
    compiles: int
    trace_dir: str | None = None
    trace_t0: float | None = None
    trace_t1: float | None = None
    trace_steps: list | None = None


class FrontEnd:
    """Front end + load generator around one engine."""

    COUNTERS = ("faults_detected", "retries", "hard_faults", "evictions",
                "rejections", "tokens", "steps")

    def __init__(self, engine, request_cls, stats_cls, compiles):
        self.engine = engine
        self.Request = request_cls
        self.Stats = stats_cls
        self.compiles = compiles
        self.uid = 0
        self.front: collections.deque = collections.deque()
        self.inflight: list = []

    def request(self, item, due: float):
        r = self.Request(uid=self.uid, prompt=item.prompt,
                         max_new_tokens=item.max_new_tokens)
        self.uid += 1
        r.due = due
        r.admitted_at = None
        return r

    def _admit(self, now) -> list:
        """Admit the head of the queue when a slot is free and no admitted
        prompt is still prefilling.  Returns requests it finished (a
        rejected request is done at once)."""
        import jax

        if not self.front or not self.engine.free_slots():
            return []
        if any(not r.generated and not r.done for r in self.inflight):
            return []
        r = self.front.popleft()
        r.admitted_at = now
        with jax.profiler.TraceAnnotation("bench.admit"):
            self.engine.admit([r])
        if r.done:
            return [r]
        self.inflight.append(r)
        return []

    def _step(self) -> list:
        """One engine step; returns the requests it finished."""
        import jax

        with jax.profiler.TraceAnnotation("bench.step"):
            self.engine.step()
        done = [r for r in self.inflight if r.done]
        if done:
            self.inflight = [r for r in self.inflight if not r.done]
        return done

    def take_counters(self, acc: dict, steps: list) -> None:
        """Move the engine's per-step record and counters into ``steps`` and
        ``acc`` and start a fresh ``EngineStats``."""
        st = self.engine.stats
        if st.selection_stride != 1:
            raise RuntimeError("more steps than the engine's selection "
                               "trace keeps; shorten the window")
        steps.extend((e["decode"], e["prefill"])
                     for e in st.selection_trace)
        for k in self.COUNTERS:
            acc[k] = acc.get(k, 0) + getattr(st, k)
        self.engine.stats = self.Stats()

    def serve_all(self, items, limit_s: float = 900.0) -> None:
        """Serve ``items`` through the front end until all are done (the
        warm-up)."""
        now = time.perf_counter()
        self.front.extend(self.request(it, now) for it in items)
        t_stop = now + limit_s
        while self.front or self.inflight:
            if time.perf_counter() > t_stop:
                raise RuntimeError("warm-up did not finish in time")
            self._admit(time.perf_counter())
            if self.inflight:
                self._step()
        self.engine.stats = self.Stats()

    def window(self, items, seconds: float, *, loop: str, clients: int = 0,
               trace_at: float | None = None) -> Window:
        """Serve ``items`` for ``seconds``.  Open loop: item i is due at
        t0 + its offset.  Closed loop: ``clients`` requests are due at t0
        and each finished request makes its client's next one due.  With
        ``trace_at``, the profiler records TRACE_SECONDS from t0 +
        trace_at."""
        import jax

        self.engine.stats = self.Stats()
        seen: list = []
        steps: list = []
        acc: dict = {}
        items = iter(items)
        nxt = next(items)

        def send(due):
            nonlocal nxt
            r = self.request(nxt, due)
            nxt = next(items)
            self.front.append(r)
            seen.append(r)

        t0 = time.perf_counter()
        t_end = t0 + seconds
        w = Window(t0=t0, t1=t_end, requests=seen, steps=steps,
                   counters=acc, compiles=0)
        tracing = False
        self.compiles.count = 0
        self.compiles.on = True
        if loop == "closed":
            for _ in range(clients):
                send(t0)
        while True:
            now = time.perf_counter()
            if trace_at is not None and w.trace_dir is None \
                    and now >= t0 + trace_at:
                w.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                self.take_counters(acc, steps)
                jax.profiler.start_trace(w.trace_dir)
                tracing = True
                w.trace_t0 = time.perf_counter()
            if tracing and now >= w.trace_t0 + TRACE_SECONDS:
                jax.profiler.stop_trace()
                w.trace_t1 = time.perf_counter()
                tracing = False
                n0 = len(steps)
                self.take_counters(acc, steps)
                w.trace_steps = steps[n0:]
            if now >= t_end:
                break
            if loop == "open":
                with jax.profiler.TraceAnnotation("bench.generator"):
                    while t0 + nxt.offset_s <= now:
                        send(t0 + nxt.offset_s)
            done = self._admit(now)
            if self.inflight:
                done += self._step()
            elif loop == "open":
                wait = min(t0 + nxt.offset_s, t_end) - now
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(wait)
            if loop == "closed":
                for _ in done:         # the client sends its next request
                    send(time.perf_counter())
        w.t1 = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            w.trace_t1 = time.perf_counter()
            n0 = len(steps)
            self.take_counters(acc, steps)
            w.trace_steps = steps[n0:]
        self.take_counters(acc, steps)
        if loop == "open":
            # every request due in the window gets its first token; its
            # latency counts the wait
            t_stop = time.perf_counter() + DRAIN_LIMIT_S
            while any(not r.generated and not r.error for r in seen) and \
                    time.perf_counter() < t_stop:
                self._admit(time.perf_counter())
                if self.inflight:
                    self._step()
        self.compiles.on = False
        w.compiles = self.compiles.count
        return w

    def drain(self, limit_s: float = DRAIN_LIMIT_S) -> None:
        """Finish every admitted request and drop the queue."""
        self.front.clear()
        t_stop = time.perf_counter() + limit_s
        while self.inflight and time.perf_counter() < t_stop:
            self._step()
        self.engine.stats = self.Stats()


# ---------------------------------------------------------------- metrics
def coverage_items(settings: dict, vocab: int, seed: int) -> list:
    """One request per prefill length bucket (multiples of 8 up to the
    chunk budget), each prefilled in one call; the first also decodes, so
    the decode program is warm too."""
    chunk = settings["chunk_tokens"]
    r = traffic.rng(seed, "warm-up")
    lengths = [chunk] + list(range(8, chunk, 8))
    return [traffic.Item(prompt=r.integers(1, vocab, size=n, dtype=np.int32),
                         max_new_tokens=2 if i == 0 else 1, offset_s=0.0)
            for i, n in enumerate(lengths)]


def end_to_end(w: Window, loop: str) -> tuple:
    """({metric: value}, attempted, failed, sample counts)."""
    reqs = w.requests
    gaps = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])
            if w.t0 <= b <= w.t1]
    if loop == "open":
        due = [r for r in reqs if r.due < w.t1]
        ttft = [r.times[0] - r.due for r in due if r.times]
        failed = sum(1 for r in due if r.error or not r.times)
        attempted = len(due)
    else:
        entered = [r for r in reqs if r.admitted_at is not None]
        ttft = []
        failed = sum(1 for r in entered if r.error)
        attempted = len(entered)
    generated = sum(1 for r in reqs for t in r.times if w.t0 <= t <= w.t1)
    prefilled = sum(p for _, p in w.steps)
    vals = {
        "ttft_p50_ms": stats.percentile_ms(ttft, 50),
        "itl_p95_ms": stats.percentile_ms(gaps, 95),
        "itl_mean_ms": stats.mean_ms(gaps),
        "tokens_per_s": (prefilled + generated) / (w.t1 - w.t0),
    }
    counts = {"ttft_samples": len(ttft), "itl_samples": len(gaps),
              "prompt_tokens_prefilled": prefilled,
              "tokens_generated": generated,
              "window_s": w.t1 - w.t0,
              "ttft_p95_ms": stats.percentile_ms(ttft, 95),
              "itl_p50_ms": stats.percentile_ms(gaps, 50)}
    return vals, attempted, failed, counts


def traced(w: Window, cell, cfg: dict, peaks: dict, settings: dict,
           keep: str | None = None) -> tuple:
    """(run context for the per-layer readers, device busy/window,
    breakdown) from the traced sub-window.  ``keep``: a directory to copy
    the raw trace into."""
    tr = trace.load(w.trace_dir)
    if keep:
        shutil.copytree(w.trace_dir, os.path.join(keep, "raw_trace"),
                        dirs_exist_ok=True)
    shutil.rmtree(w.trace_dir, ignore_errors=True)
    window_ns = tr["window_ns"]
    planes = sorted(tr["devices"])
    if not planes:
        raise RuntimeError("the trace holds no device operations")
    busy = [trace.busy_ns(tr["devices"][p], window_ns) for p in planes]
    ops = tr["devices"][planes[0]]
    gaps = trace.idle_gaps(ops, window_ns)
    first = sum(1 for r in w.requests if r.times
                and w.trace_t0 <= r.times[0] <= w.trace_t1)
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, peaks=peaks, settings=settings, window=w,
        ops=ops, window_s=window_ns / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9, steps=w.trace_steps,
        first_tokens=first, compiles=w.compiles,
        queue_waits=[r.admitted_at - r.due for r in w.requests
                     if r.admitted_at is not None])
    breakdown = {"device_ops": trace.top_ops(ops),
                 "idle_gaps": trace.label_gaps(gaps, tr["host"])}
    return run, breakdown, tr["sample_stats"]


# ------------------------------------------------------------------ run
def setup(cell, seed: int, require_tpu: bool = True, engine_hook=None):
    """Weights, engine and warm-up: (front end, weights, device, peaks)."""
    import jax

    cfg, settings = cell.config, cell.settings
    compiles = CompileCounter()
    cache_dir = enable_compile_cache()
    dev, peaks = device_info(cell, require_tpu)
    log(f"bench: {cell.name} seed {seed} on {len(jax.devices())} x "
        f"{dev.device_kind} ({dev.platform}); compile cache {cache_dir}")
    w_dev = weights.make(cfg, seed)
    jax.block_until_ready(w_dev)
    engine, Request, EngineStats = program.build(
        cfg, cell.config_name, settings, w_dev, seed)
    if engine_hook is not None:
        engine_hook(engine)
    drv = FrontEnd(engine, Request, EngineStats, compiles)
    t_warm = time.perf_counter()
    drv.serve_all(coverage_items(settings, cfg["vocab_size"], seed))
    log(f"bench: warm-up {time.perf_counter() - t_warm:.1f} s")
    return drv, w_dev, dev, peaks


def run(workload: str, seed: int, seconds: float, trace_on: bool, *,
        t_start: float, require_tpu: bool = True, cell=None,
        engine_hook=None, out_dir: str | None = None) -> dict:
    """One run of one cell.  Returns the result object; the caller prints
    it.  ``cell`` and ``engine_hook`` (applied to the built engine) let the
    tests run the harness at a small size with a broken engine."""
    import jax

    cell = cell or spec.cell(workload)
    cfg, mix, settings = cell.config, cell.mix, cell.settings
    drv, w_dev, dev, peaks = setup(cell, seed, require_tpu, engine_hook)

    loop = mix["loop"]
    items = traffic.stream(mix, seed, cfg["vocab_size"],
                           rate=settings.get("rate_per_s"))
    setup_s = time.perf_counter() - t_start
    trace_at = max(0.0, (seconds - TRACE_SECONDS) / 2) if trace_on else None
    w = drv.window(items, seconds, loop=loop,
                   clients=settings.get("clients", 0), trace_at=trace_at)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    values, attempted, failed, counts = end_to_end(w, loop)
    values["setup_s"] = setup_s
    counts.update(compiles_in_window=w.compiles, **{
        f"engine_{k}": v for k, v in w.counters.items()})
    log("bench: samples " + json.dumps(counts))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    result: dict = {}
    if trace_on:
        run_ctx, breakdown, sample = traced(w, cell, cfg, peaks, settings,
                                            keep=out_dir)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, "trace_sample.json"), "w") as fh:
                json.dump({"stats": sample, "ops": run_ctx.ops[:4000],
                           "breakdown": breakdown}, fh)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=run_ctx.busy_s, window_s=run_ctx.window_s)
        result["breakdown"] = breakdown
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is None:
                raise RuntimeError(f"{m['name']} has no samples")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state goes before the reference runs
    finished = [r for r in w.requests if r.done and not r.error
                and r.generated]
    drv.engine = engine = None
    gc.collect()
    ref = spec.reference(cfg["architecture"])
    chosen = correctness.sample(finished, settings["check_tokens"], seed)
    t_ref = time.perf_counter()
    g = correctness.gaps(ref, w_dev, cfg, chosen)
    served = int(g.size)
    gap = float(g.max()) if served else float("inf")
    log(f"bench: reference over {len(chosen)} requests, {served} served "
        f"tokens, {time.perf_counter() - t_ref:.1f} s")
    want_tokens = min(settings["check_tokens"],
                      sum(len(r.generated) for r in finished))
    checks = {
        "max_logit_gap": {"value": gap,
                          "limit": settings["max_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "served_tokens_compared": {"value": served,
                                   "limit_at_least": max(want_tokens, 1)},
        "abft_detections": {"value": w.counters.get("faults_detected", 0),
                            "limit": 0},
    }
    ok = (gap <= settings["max_logit_gap"] and failed == 0
          and served >= max(want_tokens, 1)
          and w.counters.get("faults_detected", 0) == 0)
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **result,
              "checks": checks}
    for k, c in checks.items():
        lim = {kk: vv for kk, vv in c.items() if kk != "value"}
        log(f"check {k} {c['value']} {json.dumps(lim)}")
    return result
