"""The ``.xplane.pb`` reader and the tie from a device op to its
protection scope, on a trace file written here in the layout a TPU
profile has: a metadata plane holding the compiled module's HLO, a
device plane with its module and op lines, and a host plane with the
benchmark's spans."""

import struct

import jax
import jax.numpy as jnp

from bench import trace


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _plane(name, metadata=(), lines=(), stats=(), stat_names=()):
    parts = [(2, name)]
    for mid, mname, mstats in metadata:
        em = _msg((1, mid), (2, mname), *[(5, s) for s in mstats])
        parts.append((4, _msg((1, mid), (2, em))))
    for sid, sname in stat_names:
        parts.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    parts += [(6, s) for s in stats]
    parts += [(3, ln) for ln in lines]
    return _msg(*parts)


def _line(name, events):
    return _msg((2, name), (3, 0), *[
        (4, _msg((1, mid), (2, off_ps), (3, dur_ps)))
        for mid, off_ps, dur_ps in events])


def test_device_op_gets_its_scope(tmp_path):
    def f(x, w):
        with jax.named_scope("abft[global][mlp.up]"):
            return jnp.dot(x, w)

    x = jnp.ones((8, 8))
    exe = jax.jit(f).lower(x, x).compile().runtime_executable()
    module = exe.hlo_modules()[0]
    text = module.to_string()
    instr = next(n for n, op in trace.op_names(text).items()
                 if op.endswith("abft[global][mlp.up]/dot_general"))
    hlo_proto = _msg((1, module.as_serialized_hlo_module_proto()))
    stat = _msg((1, 1)) + struct.pack("<B", 6 << 3 | 2) + _varint(
        len(hlo_proto)) + hlo_proto
    space = b"".join(_field(1, p) for p in (
        _plane("/host:metadata", [(7, "jit_f(7)", [stat])],
               stat_names=[(1, "Hlo Proto")]),
        _plane("/device:TPU:0",
               [(1, "jit_f(7)", []), (2, f"%{instr} = f32[8,8] dot()", [])],
               [_line("XLA Modules", [(1, 0, 10_000_000)]),
                _line("XLA Ops", [(2, 2_000_000, 5_000_000)])]),
        _plane("/host:CPU", [(3, "bench.step", [])],
               [_line("python", [(3, 0, 12_000_000)])]),
        _plane("Task Environment", stats=[
            _msg((1, 1), (4, 1_000)), _msg((1, 2), (4, 21_000))],
            stat_names=[(1, "profile_start_time"),
                        (2, "profile_stop_time")]),
    ))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(space)

    tr = trace.load(str(tmp_path))
    (op,) = tr["devices"]["/device:TPU:0"]
    assert op[0] == 2_000 and op[1] == 7_000      # ns from the first span
    assert op[3].endswith("abft[global][mlp.up]/dot_general")
    assert tr["window_ns"] == 12_000
    assert trace.site_seconds([op]) == {"mlp.up": 5e-6}
    assert trace.check_seconds([op]) == 0.0
