#!/usr/bin/env python3
"""The engine's own host spans in a profiler trace, beside the device
ops that ``bench/trace.py`` reads.

The program opens a ``jax.profiler`` annotation around every host phase
of an engine step (``src/repro/obs/trace.py``); their names start with
``serve.`` (the tree is in the ``serve/engine.py`` docstring).  This
module reads them and splits the device's idle time by engine phase:

  python3 bench/engine_spans.py <out>/raw_trace

reads a trace that ``bench/run.py --trace 1 --out <out>`` kept and prints
one JSON object: the readers below, the ten longest idle gaps named by
the innermost ``bench.*`` or ``serve.*`` span, the idle time under each
innermost span, and each ``serve.*`` span's mean time per step.

A program span is ``(start_ns, end_ns, name, args)``, on the origin
that ``trace.load`` gives the device ops and the benchmark's spans (the
start of the first ``bench.*`` span).  A trace of a program without
these spans holds none: every reader then returns None."""

from __future__ import annotations

import collections
import glob
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

PREFIX = "serve."
ROOTS = ("serve.step", "serve.admit")


def program_spans(log_dir: str) -> list:
    """The ``serve.*`` host spans of the one xplane file under
    ``log_dir``, shifted like ``trace.focus`` shifts the device ops, by
    start.  A ``#k=v#`` metadata suffix is cut from a name."""
    from bench import xplane

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    lo, spans = None, []
    for pl in xplane.planes(paths[0]):
        if not pl["name"].startswith("/host:"):
            continue
        md = pl["event_metadata"]
        for ln in pl["lines"]:
            for s, e, mid, stats in ln["events"]:
                name = md.get(mid, {}).get("name", "").split("#", 1)[0]
                if name.startswith(trace.HOST_PREFIX):
                    lo = s if lo is None else min(lo, s)
                elif name.startswith(PREFIX):
                    spans.append((s, e, name, stats))
    lo = lo or 0
    return sorted(((s - lo, e - lo, n, a) for s, e, n, a in spans),
                  key=lambda p: (p[0], -p[1]))


def _phase(name: str, args: dict) -> str:
    """A span's name, with a ``wait`` span's ``what`` appended."""
    return f"{name}[{args['what']}]" if "what" in args else name


def _overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two sorted disjoint interval
    lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def engine_self_ms(program: list, window_ns: float) -> float | None:
    """Mean, over the ``serve.step`` spans wholly inside the window, of
    the span's duration less the union of the ``serve.*.wait`` spans in
    it: the engine's host work per step, time it is not waiting on the
    chip."""
    steps = [p for p in program
             if p[2] == "serve.step" and p[0] >= 0 and p[1] <= window_ns]
    if not steps:
        return None
    waits = [p for p in program if p[2].endswith(".wait")]
    own = []
    for s, e, _, _ in steps:
        inside = [(max(ws, s), min(we, e)) for ws, we, _, _ in waits
                  if ws < e and we > s]
        own.append((e - s) - sum(b - a for a, b in trace.merge(inside)))
    return sum(own) / len(own) / 1e6


def engine_idle_share(ops, program: list,
                      window_ns: float) -> float | None:
    """Per cent of the window in which no op runs on the device and the
    host is inside a ``serve.step`` or ``serve.admit`` span: the part of
    ``idle_share`` that the engine's own host path causes."""
    roots = [(max(s, 0), min(e, window_ns)) for s, e, n, _ in program
             if n in ROOTS and e > 0 and s < window_ns]
    if not roots:
        return None
    gaps = trace.idle_gaps(ops, window_ns)
    return 100.0 * _overlap_ns(gaps, trace.merge(roots)) / window_ns


def label_gaps(gaps, host: list, program: list, n: int = 10) -> list:
    """``trace.label_gaps`` over the benchmark's and the program's spans
    together: a gap is named by the innermost of either."""
    return trace.label_gaps(gaps, host + [p[:3] for p in program], n)


def idle_by_span(gaps, host: list, program: list) -> dict:
    """{span name: idle seconds}: every stretch of every idle gap given
    to the innermost ``bench.*`` or ``serve.*`` span covering it (a
    ``wait`` span's name carries its ``what``)."""
    spans = [(s, e, n) for s, e, n in host] + [
        (s, e, _phase(n, a)) for s, e, n, a in program]
    spans.sort()
    out: dict = collections.defaultdict(float)
    for gs, ge in gaps:
        near = [sp for sp in spans if sp[0] < ge and sp[1] > gs]
        cuts = sorted({gs, ge, *(t for sp in near for t in sp[:2]
                                 if gs < t < ge)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [sp for sp in near if sp[0] <= mid < sp[1]]
            name = min(cover, key=lambda sp: sp[1] - sp[0])[2] \
                if cover else "no span"
            out[name] += (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def per_step_ms(program: list, window_ns: float) -> dict:
    """{span name: milliseconds per ``serve.step``} of every ``serve.*``
    span inside the window (a ``wait`` span's name carries its
    ``what``)."""
    steps = sum(1 for p in program if p[2] == "serve.step"
                and p[0] >= 0 and p[1] <= window_ns)
    if not steps:
        return {}
    tot: dict = collections.defaultdict(float)
    for s, e, n, a in program:
        if s >= 0 and e <= window_ns:
            tot[_phase(n, a)] += e - s
    return {k: v / steps / 1e6 for k, v in sorted(tot.items())}


def summary(tr: dict, program: list) -> dict:
    """The readers and breakdowns of one trace: ``tr`` as
    ``trace.load`` gives it, ``program`` as ``program_spans`` does."""
    w = tr["window_ns"]
    ops = tr["devices"][sorted(tr["devices"])[0]]
    gaps = trace.idle_gaps(ops, w)
    return {
        "window_s": w / 1e9,
        "idle_share": 100.0 * (1 - trace.busy_ns(ops, w) / w),
        "engine_self_ms": engine_self_ms(program, w),
        "engine_idle_share": engine_idle_share(ops, program, w),
        "idle_gaps": label_gaps(gaps, tr["host"], program),
        "idle_by_span_s": idle_by_span(gaps, tr["host"], program),
        "per_step_ms": per_step_ms(program, w),
    }


if __name__ == "__main__":
    print(json.dumps(summary(trace.load(sys.argv[1]),
                             program_spans(sys.argv[1]))))
