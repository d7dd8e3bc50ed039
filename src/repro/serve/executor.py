"""Executor layer of the serving engine (executor-hierarchy refactor).

The executor owns the DEVICE residency of a serving engine — params,
cache, and per-slot PRNG keys — and compiles the engine's
``ProtectionPlan`` for the hardware it actually runs on:

``LocalExecutor``
    Single-device (the old monolith's implicit behavior): params/cache
    live wherever jax puts them, ``model_parallel == 1``, and the plan
    sees the model's full GEMM shapes.

``MeshExecutor``
    Tensor-parallel serving over a ``(data=1, model=k)`` device mesh.
    Params are committed with the production sharding rules
    (``distributed/sharding.py::param_specs`` — heads/ffn/vocab over
    the ``model`` axis), the KV cache with ``cache_specs`` (paged block
    pools shard their kv-head dim over ``model`` while the host block
    table stays ONE logical table — per-device KV shards behind one
    logical index), and the jitted runner entry points then run SPMD by
    GSPMD propagation from those committed inputs: no per-call
    ``in_shardings``, no runner changes, no scheduler changes.

    The executor is also where protection becomes HARDWARE-AWARE PER
    SHARD: ``protection_plan`` passes ``model_parallel=k`` down to
    ``ProtectionPlan.for_model``, which divides each GEMM's sharded dim
    (n for column-parallel, k for row-parallel) before computing
    arithmetic intensity — so TP=4 can legitimately select a DIFFERENT
    ABFT scheme than TP=1 for the same layer (smaller per-device GEMMs
    sit lower on the roofline).  That per-shard re-selection is the
    paper's intensity-guided decision re-made for the post-sharding
    shapes, and it is what the sharded equivalence tests pin down.

Stream equality: greedy token streams are byte-identical between
``LocalExecutor`` and ``MeshExecutor`` at any width for bf16 models —
per-device partial GEMMs accumulate in f32 and round to bf16 after the
reduction, so the psum reordering TP introduces is below the output
precision.  (Full-f32 models can differ in the last ulp across widths;
the equivalence suite therefore runs bf16, like production serving.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.mesh import build_mesh, make_hints
from repro.distributed.sharding import (
    cache_specs,
    make_sharding,
    param_specs,
)
from repro.models.model import Model


class LocalExecutor:
    """Single-device executor: owns params/cache/keys, no mesh."""

    mesh = None
    model_parallel = 1

    def __init__(self, model: Model, params, *, dtype, hints=None):
        self.model = model
        self.params = params
        self.dtype = dtype
        self.dtype_bytes = jnp.dtype(dtype).itemsize
        self.hints = hints
        # the one device the cache lands on, whose storage layout the
        # layer loop keeps (LayerCtx.cache_device); a mesh-sharded cache
        # leaves its layout to the partitioner
        self.cache_device = jax.devices()[0] if self.mesh is None else None
        self.cache = None
        self.keys = None

    # ------------------------------------------------------------- state
    def init_dense_cache(self, slots: int, max_len: int) -> None:
        self.cache = self.model.init_cache(slots, max_len, dtype=self.dtype)

    def init_paged_cache(self, slots: int, num_blocks: int,
                         block_size: int) -> None:
        self.cache = self.model.init_paged_cache(
            slots, num_blocks, block_size, dtype=self.dtype)

    def init_keys(self, seed: int, slots: int) -> None:
        # per-slot PRNG key vector: each slot samples from its own stream
        self.keys = jax.random.split(jax.random.PRNGKey(seed), slots)

    # -------------------------------------------------------------- plan
    def protection_plan(self, abft, *, slots: int):
        """Compile the ProtectionPlan for THIS executor's hardware view:
        per-shard GEMM shapes under ``model_parallel``-way TP."""
        return self.model.protection_plan(
            hw=abft.hw, policy=abft.effective_policy(),
            phase="serve", n_tokens=slots, dtype_bytes=self.dtype_bytes,
            model_parallel=self.model_parallel)


class MeshExecutor(LocalExecutor):
    """Mesh-sharded executor (see module docstring).

    ``mesh``: an int tensor-parallel width (builds a ``(data=1,
    model=k)`` mesh over the first k local devices via the canonical
    ``distributed/mesh.py::build_mesh``) or a prebuilt ``jax.sharding
    .Mesh`` carrying a ``model`` axis."""

    def __init__(self, model: Model, params, *, mesh, dtype, hints=None):
        if isinstance(mesh, int):
            mesh = build_mesh(model=mesh, data=1)
        if "model" not in mesh.axis_names:
            raise ValueError(
                f"MeshExecutor needs a 'model' axis, mesh has "
                f"{mesh.axis_names}")
        self.mesh = mesh
        self.model_parallel = int(mesh.shape["model"])
        if hints is None:
            hints = make_hints(model.cfg, mesh)
        # commit the params with the production sharding rules; the
        # jitted runner entry points pick the layout up by propagation
        specs = param_specs(model.cfg, params, mesh)
        params = jax.device_put(params, make_sharding(mesh, specs))
        super().__init__(model, params, dtype=dtype, hints=hints)

    def _put_cache(self, cache, *, paged: bool, slots: int):
        specs = cache_specs(self.model.cfg, cache, self.mesh, slots,
                            paged=paged)
        return jax.device_put(cache, make_sharding(self.mesh, specs))

    def init_dense_cache(self, slots: int, max_len: int) -> None:
        super().init_dense_cache(slots, max_len)
        self.cache = self._put_cache(self.cache, paged=False, slots=slots)

    def init_paged_cache(self, slots: int, num_blocks: int,
                         block_size: int) -> None:
        super().init_paged_cache(slots, num_blocks, block_size)
        self.cache = self._put_cache(self.cache, paged=True, slots=slots)

    def init_keys(self, seed: int, slots: int) -> None:
        super().init_keys(seed, slots)
        # keys are host-logical state: replicate them so every device
        # samples identically (the sampler's argmax/categorical runs on
        # model-replicated logits rows)
        self.keys = jax.device_put(
            self.keys, NamedSharding(self.mesh, P()))
