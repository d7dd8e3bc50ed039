"""Random weights from the seed, made by the benchmark on the device in
one jitted call, in bfloat16 (the type they are served in).

The layout is the architecture's (``bench/arch/<architecture>.py``
``shapes``): a flat dict of arrays stacked over layers, which
``program.params`` nests (no copy) into the tree the serving engine
takes and the plain reference reads as it is.  On a mesh each device
makes only its own shard (``specs``); with JAX's partitionable threefry
the values are those of the one-device call.

Scales keep every layer's output of order one, so the logits spread
over a few units and the greedy token is not a coin toss between
near-equal logits: GEMM weights N(0, 1/fan_in), embedding N(0, 1),
norm gains 1 + N(0, 0.1^2), norm and projection biases N(0, 0.25^2)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from bench import spec

BF16 = jnp.bfloat16


def _body(key, table):
    out = {}
    keys = jax.random.split(key, len(table))
    for k, (name, shape, kind) in zip(keys, table):
        z = jax.random.normal(k, shape, BF16)
        if kind == "gemm":
            z = z * BF16(1.0 / math.sqrt(shape[-2]))
        elif kind == "gain":
            z = BF16(1.0) + z * BF16(0.1)
        elif kind == "bias":
            z = z * BF16(0.25)
        out[name] = z
    return out


_make = jax.jit(_body, static_argnums=(1,))


def make(cfg: dict, seed: int, mesh=None) -> dict:
    """The weights of ``cfg`` for ``seed``: on the default device, or
    sharded over ``mesh``'s ``model`` axis."""
    arch = spec.arch(cfg["architecture"])
    table = tuple((n, s, k) for n, (s, k) in sorted(arch.shapes(cfg).items()))
    key = jax.random.key(seed % (1 << 32))
    key = jax.random.fold_in(key, (seed >> 32) % (1 << 31))
    if mesh is None:
        return _make(key, table)
    specs = arch.specs(cfg, mesh.shape["model"])
    out = {n: NamedSharding(mesh, specs[n]) for n, _, _ in table}
    return jax.jit(functools.partial(_body, table=table),
                   out_shardings=out)(key)
