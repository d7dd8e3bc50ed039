"""Recursive ClosedJaxpr walker: inventory every FLOP-carrying primitive.

``flop_ops`` walks a traced entry point — through ``pjit``, ``scan``,
``cond`` branches, ``remat``, ``custom_jvp/vjp`` — and returns one
``TracedOp`` per ``dot_general`` / ``conv_general_dilated`` equation, with:

* exact FLOPs from the equation's dimension numbers and operand avals
  (2*M*K*N per batched GEMM element; 2 * out_elems * K_eff per conv),
  multiplied by the enclosing scan trip counts (a scanned stack of R
  repeats traces ONE layer body — the walker restores the xR factor);
* the equation's ``name_stack`` string, which carries the auditor's
  ``abft[...]``/``flops[...]`` markers (markers.py);
* a human-readable path (``prefill/pjit:fn/scan[x4]/dot_general``) for
  pinpointing unprotected ops in reports.

``pallas_call`` equations are surfaced as ``TracedOp``s too (flops=0 —
kernel internals are opaque to tracing) so fused-kernel dispatch sites
stay visible to the classifier instead of vanishing.
"""

from __future__ import annotations

import dataclasses
import math

from jax.extend import core as jax_core

FLOP_PRIMITIVES = ("dot_general", "conv_general_dilated")


@dataclasses.dataclass(frozen=True)
class TracedOp:
    """One FLOP-carrying equation found by the walk."""

    primitive: str
    flops: float               # repeats included
    m: int                     # lhs free size (batch folded out)
    k: int                     # contraction size
    n: int                     # rhs free size / out channels
    name_stack: str
    path: str
    repeats: int = 1           # product of enclosing scan lengths


def _prod(xs) -> int:
    return int(math.prod(int(x) for x in xs)) if xs else 1


def _dot_geometry(eqn):
    """(batch, m, k, n) of a dot_general from its dimension numbers."""
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    batch = _prod([lhs[i] for i in lhs_b])
    k = _prod([lhs[i] for i in lhs_c])
    m = _prod([d for i, d in enumerate(lhs) if i not in lhs_c + lhs_b])
    n = _prod([d for i, d in enumerate(rhs)
               if i not in tuple(rhs_c) + tuple(rhs_b)])
    return batch, m, k, n


def _conv_geometry(eqn):
    """(m, k, n) of a conv: m = batch*out_spatial, k = in_per_group *
    prod(kernel_spatial), n = out_channels."""
    dn = eqn.params["dimension_numbers"]
    out_shape = eqn.outvars[0].aval.shape
    rhs_shape = eqn.invars[1].aval.shape
    n = int(rhs_shape[dn.rhs_spec[0]])          # out feature dim
    k = _prod(rhs_shape) // max(n, 1)           # in_per_group * spatial
    m = _prod(out_shape) // max(n, 1)           # batch * out positions
    return m, k, n


def _sub_jaxprs(eqn):
    """Every jaxpr-valued object hiding in an equation's params."""
    subs = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, jax_core.ClosedJaxpr):
                subs.append(item.jaxpr)
            elif isinstance(item, jax_core.Jaxpr):
                subs.append(item)
    return subs


def _eqn_label(eqn) -> str:
    prim = eqn.primitive.name
    if prim == "pjit":
        name = eqn.params.get("name")
        return f"pjit:{name}" if name else prim
    if prim == "scan":
        return f"scan[x{eqn.params.get('length', '?')}]"
    return prim


def flop_ops(traced, entry: str = "trace") -> list:
    """Walk a ClosedJaxpr (or anything with ``.jaxpr``) and return the
    ``TracedOp`` inventory.  ``entry`` labels the path root."""
    jaxpr = getattr(traced, "jaxpr", traced)
    out: list = []
    _walk(jaxpr, (entry,), 1, out, "")
    return out


def _walk(jaxpr, path: tuple, repeats: int, out: list,
          prefix: str) -> None:
    """``prefix``: accumulated name-stack string of the ENCLOSING
    equations.  A scope opened around ``lax.scan``/``pjit`` lands on the
    wrapping equation itself — body eqns carry only their local stacks —
    so markers must be read off the concatenation."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        local = str(eqn.source_info.name_stack)
        ns = "/".join(s for s in (prefix, local) if s)
        if prim == "dot_general":
            batch, m, k, n = _dot_geometry(eqn)
            out.append(TracedOp(
                primitive=prim,
                flops=2.0 * batch * m * k * n * repeats,
                m=batch * m, k=k, n=n,
                name_stack=ns,
                path="/".join(path + (prim,)),
                repeats=repeats,
            ))
        elif prim == "conv_general_dilated":
            m, k, n = _conv_geometry(eqn)
            out.append(TracedOp(
                primitive=prim,
                flops=2.0 * m * k * n * repeats,
                m=m, k=k, n=n,
                name_stack=ns,
                path="/".join(path + (prim,)),
                repeats=repeats,
            ))
        elif prim == "pallas_call":
            # fused kernel: internals opaque; visible for classification
            out.append(TracedOp(
                primitive=prim, flops=0.0, m=0, k=0, n=0,
                name_stack=ns,
                path="/".join(path + (prim,)),
                repeats=repeats,
            ))
        sub = _sub_jaxprs(eqn)
        if sub:
            mult = repeats
            if prim == "scan":
                mult *= int(eqn.params.get("length", 1))
            label = _eqn_label(eqn)
            for s in sub:
                _walk(s, path + (label,), mult, out, ns)
