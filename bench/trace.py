"""Device trace: capture with the JAX profiler, read the ``.xplane.pb``
with ``bench/xplane.py``, and reduce it to busy time, idle gaps
and time per protected GEMM site.

The reduction works on plain tuples so that it can be checked on a small
recorded trace (``bench/tests``):
  device op  (start_ns, end_ns, name, scope)  scope: the op's name
             path from its metadata when that holds an
             ``abft[<scheme>][<site>]`` or ``flops[<kind>]`` marker,
             else ""
  host span  (start_ns, end_ns, name)  the benchmark's own annotations
Times are nanoseconds from the start of the trace."""

from __future__ import annotations

import collections
import glob
import re

ABFT_RE = re.compile(r"abft\[([^\]]*)\]\[([^\]]*)\]")
MARK_RE = re.compile(r"(abft\[[^\]]*\]\[[^\]]*\]|flops\[[^\]]*\])")
DIGITS_RE = re.compile(r"[.\d]+$")
CONTAINER_RE = re.compile(r"\s(while|conditional|call)\(")
COLLECTIVES = r"(?:all-reduce-scatter|all-reduce|all-gather|" \
              r"reduce-scatter|all-to-all|collective-permute)(?:-start|-done)?"
COLLECTIVE_RE = re.compile(rf"(?:^%?|\s)({COLLECTIVES})(?:[.(\s]|$)")
MAIN_DOT_RE = re.compile(r"/(dot_general|matmul)")
HOST_PREFIX = "bench."


PROGRAM_RE = re.compile(r"\((\d+)\)\s*$")
INSTR_RE = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = .*?metadata=\{op_name="'
                      r'([^"]*)"', re.M)
NAME_RE = re.compile(r"^%?([^\s=]+)")
COLLECTIVE_INSTR_RE = re.compile(
    rf"^\s*(?:ROOT\s+)?%([^\s=]+) = [^\n]*?(?:\s({COLLECTIVES})\(|"
    rf"\sfusion\([^\n]*?calls=%({COLLECTIVES})[.\w-]*)", re.M)


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of a compiled module's text."""
    return {m.group(1): m.group(2) for m in INSTR_RE.finditer(hlo_text)}


def collective_ops(hlo_text: str) -> dict:
    """{instruction name: opcode} of the exchanges between chips in a
    compiled module's text.  The name alone does not tell: a ``psum``
    under ``shard_map`` is an ``all-reduce`` named ``psum.<n>``, and the
    TPU compiler fuses a reduce-scatter into a ``fusion.<n>`` that calls
    an ``all-reduce-scatter`` computation (opcode given as that name)."""
    return {m.group(1): m.group(2) or m.group(3)
            for m in COLLECTIVE_INSTR_RE.finditer(hlo_text)}


def load(log_dir: str) -> dict:
    """{"window_ns", "devices": {plane: [op, ...]}, "host": [span, ...],
    "sample": [...]} from the one xplane file under ``log_dir``.  An op's
    scope is the ``op_name`` that its compiled module's metadata gives
    the instruction, found through the module the op ran in; a
    collective's name gets its opcode appended (``psum.3 all-reduce(``),
    as the module's text gives it."""
    from bench import xplane

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    planes = xplane.planes(paths[0])
    window = None
    hlo = {}
    for pl in planes:
        if pl["name"] == "Task Environment":
            st = pl["stats"]
            window = int(st["profile_stop_time"]) - int(
                st["profile_start_time"])
        elif pl["name"] == "/host:metadata":
            for md in pl["event_metadata"].values():
                m = PROGRAM_RE.search(md["name"])
                if m and "Hlo Proto" in md["stats"]:
                    hlo[int(m.group(1))] = md["stats"]["Hlo Proto"]
    if window is None:
        raise RuntimeError("trace has no profile start and stop time")
    names_of: dict = {}

    def module(program):
        if program not in names_of:
            text = xplane.hlo_text(hlo[program]) if program in hlo else ""
            names_of[program] = (op_names(text), collective_ops(text))
        return names_of[program]

    devices, host, sample = {}, [], []
    for pl in planes:
        md = pl["event_metadata"]
        if pl["name"].startswith("/device:TPU:"):
            lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
            modules = sorted(
                (s, e, int(PROGRAM_RE.search(md[mid]["name"]).group(1)))
                for s, e, mid, _ in lines.get("XLA Modules", [])
                if PROGRAM_RE.search(md[mid]["name"]))
            ops = devices.setdefault(pl["name"], [])
            k = 0
            for s, e, mid, _ in sorted(lines.get("XLA Ops", [])):
                while k < len(modules) and modules[k][1] < s:
                    k += 1
                name = md[mid]["name"]
                program = modules[k][2] if k < len(modules) and \
                    modules[k][0] <= s else None
                m = NAME_RE.match(name)
                scope = ""
                if m:
                    scopes, colls = module(program)
                    scope = scopes.get(m.group(1), "")
                    if m.group(1) in colls and not is_collective(name):
                        name = f"{name} {colls[m.group(1)]}("
                if len(sample) < 60:
                    sample.append([name[:160], program, scope])
                ops.append((s, e, name, scope))
        elif pl["name"].startswith("/host:"):
            for ln in pl["lines"]:
                for s, e, mid, _ in ln["events"]:
                    n = md.get(mid, {}).get("name", "")
                    if n.startswith(HOST_PREFIX):
                        host.append((s, e, n))
    return focus({"window_ns": window, "devices": devices, "host": host,
                  "sample_stats": sample})


def focus(tr: dict) -> dict:
    """Narrow the trace to the stretch that the benchmark's host spans
    cover: the profiler's own start and stop, before the first and after
    the last step, are not the program's idle time."""
    if not tr["host"]:
        return tr
    lo = min(s for s, _, _ in tr["host"])
    hi = min(max(e for _, e, _ in tr["host"]), tr["window_ns"])
    shift = [(s - lo, e - lo, *rest) for s, e, *rest in tr["host"]]
    devices = {p: [(s - lo, e - lo, n, sc) for s, e, n, sc in ops
                   if e > lo and s < hi]
               for p, ops in tr["devices"].items()}
    return dict(tr, window_ns=hi - lo, devices=devices, host=shift)


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, window_ns: float) -> float:
    """Time in [0, window) in which some operation ran on the device."""
    return sum(min(e, window_ns) - max(s, 0)
               for s, e in merge(ops) if e > 0 and s < window_ns)


def idle_gaps(ops, window_ns: float) -> list:
    """[(start, end)] of the device's idle stretches inside the window."""
    gaps, t = [], 0.0
    for s, e in merge(ops):
        if s > t:
            gaps.append((t, min(s, window_ns)))
        t = max(t, e)
        if t >= window_ns:
            break
    if t < window_ns:
        gaps.append((t, window_ns))
    return [(s, e) for s, e in gaps if e > s]


def is_collective(name: str) -> bool:
    """An exchange between chips, by the opcode in its name (``load``
    appends it): an ``all-reduce``, ``all-gather``, ``reduce-scatter``
    (or the TPU's fused ``all-reduce-scatter``), ``all-to-all`` or
    ``collective-permute``, or the ``-start`` or ``-done`` half of an
    asynchronous one."""
    return bool(COLLECTIVE_RE.search(name))


def collective_ns(ops, window_ns: float) -> float | None:
    """Time in [0, window) in which a collective runs on the device and
    no other op does (control-flow containers, which span their bodies,
    aside), or None where the device ran no collective."""
    coll = [o for o in ops if is_collective(o[2])]
    if not coll:
        return None
    rest = [o for o in ops if not is_collective(o[2])
            and not is_container(o[2])]
    return busy_ns(coll + rest, window_ns) - busy_ns(rest, window_ns)


def site_seconds(ops, scheme: str | None = None) -> dict:
    """{site: seconds of device ops under its abft scope}, optionally of
    one scheme only; collectives are left out (``collective_ns`` counts
    them)."""
    out = collections.defaultdict(float)
    for s, e, name, scope in ops:
        if is_collective(name):
            continue
        m = ABFT_RE.search(scope) if scope else None
        if m and (scheme is None or m.group(1) == scheme):
            out[m.group(2)] += (e - s) / 1e9
    return dict(out)


def check_seconds(ops) -> float | None:
    """Seconds of device ops under an ``abft[global][site]`` scope that
    are not the site's product itself (the checksum reductions and
    compares), or None when the trace holds no global site.  The product
    is the ``dot_general`` directly under the scope; the checks' own
    products sit under their ``einsum``."""
    total, seen = 0.0, False
    for s, e, _, scope in ops:
        m = ABFT_RE.search(scope) if scope else None
        if not m or m.group(1) != "global":
            continue
        seen = True
        if not MAIN_DOT_RE.match(scope[m.end():]):
            total += (e - s) / 1e9
    return total if seen else None


def op_key(name: str, scope: str) -> str:
    """An op's group: its protection marker, else the last two parts of
    its name path, else its HLO name without the number."""
    m = MARK_RE.search(scope) if scope else None
    if m:
        return m.group(1)
    if scope:
        return "/".join(scope.split("/")[-2:])
    n = NAME_RE.match(name)
    return DIGITS_RE.sub("", n.group(1)) if n else name


def is_container(name: str) -> bool:
    """A ``while``/``conditional``/``call`` op spans the ops of its body,
    which the trace also lists."""
    return bool(CONTAINER_RE.search(name))


def top_ops(ops, n: int = 10) -> list:
    """[[name, seconds]] of the n device-op groups that took most time,
    grouped by protection scope or by op name without its number;
    control-flow containers are left out (their bodies count)."""
    tot = collections.defaultdict(float)
    for s, e, name, scope in ops:
        if not is_container(name):
            tot[op_key(name, scope)] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gaps, host, n: int = 10) -> list:
    """[[host span, seconds]] of the n longest idle gaps, each named by the
    innermost benchmark span that covers the gap's midpoint."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        covering = [h for h in host if h[0] <= mid < h[1]]
        name = min(covering, key=lambda h: h[1] - h[0])[2] if covering \
            else "no benchmark span"
        out.append([name, (e - s) / 1e9])
    return out
