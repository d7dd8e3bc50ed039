"""``protected_matmul`` — the paper's contribution as a composable JAX op.

Every linear layer in the framework calls this instead of ``x @ w``.  The
active ProtectionPolicy (core/policy.py, paper §5.3) resolves the scheme
per layer shape at trace time; the chosen scheme's registered *executor*
runs and returns (y, CheckResult).

Scheme dispatch goes through the SchemeRegistry — the executors defined
here register at import for the built-ins:
  global   — XLA dot + Hari-style global check using the offline weight
             checksum (precompute via ``precompute_weight_checksums``).
  block_*  — the fused Pallas kernel (kernels/ops.py): compiled on a
             TPU, interpret mode elsewhere; or, off a TPU only, the XLA
             emulation of its semantics when ``use_pallas=False``.
  replica  — fused kernel in replica mode (ablation baseline).
  none     — plain dot, clean CheckResult.
A newly registered scheme (cost model + executor) dispatches here with no
edit to this module.

``ABFTConfig`` below is the DEPRECATED facade: it survives for existing
callers and simply constructs a ProtectionPolicy (``effective_policy``) —
an ``IntensityGuidedPolicy`` for ``scheme=AUTO``, a ``FixedPolicy``
otherwise.  New code should build policies (and ``ProtectionPlan``s)
directly and wrap them via ``ABFTConfig.from_policy`` where a config
object is still required.

Distribution note: under GSPMD the GLOBAL path (and the jnp emulation of
the block schemes) shards exactly like the dot it protects — the check
einsums follow the same specs.  A Pallas kernel is a custom call the
partitioner cannot split, so when ``ABFTConfig.mesh`` names a
tensor-parallel mesh (the serving ``MeshExecutor`` sets it) the kernel
schemes run under ``shard_map`` on every TP-sharded site: each device
checks its local sub-GEMM — column-parallel sites own a slice of the
output columns, row-parallel sites a slice of the contraction, whose f32
partial products are summed across the ``model`` axis before the cast to
the output dtype — which is the paper's "smallest parallel subproblem"
principle lifted one level up the hierarchy.  Sites the mesh does not
shard (and any run without ``mesh``) call the kernel directly.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.markers import protection_scope
from repro.core import checksums
from repro.core.checksums import CheckResult
from repro.core.faults import FaultSpec, inject_output_fault
from repro.core.hardware import HardwareSpec, device_hardware, on_tpu
from repro.core.intensity import GemmDims
from repro.core.policy import (
    FixedPolicy,
    ProtectionPolicy,
    default_registry,
    policy_from_selector,
    scheme_name_of,
)
from repro.core.schemes import BlockShape, Scheme
from repro.core.selector import SelectorConfig


class WeightChecksums(NamedTuple):
    """Offline row checksums of a weight matrix (paper §2.5)."""

    w_sum: jnp.ndarray
    w_abs_sum: jnp.ndarray


def precompute_weight_checksums(w: jnp.ndarray) -> WeightChecksums:
    return WeightChecksums(
        w_sum=checksums.weight_row_checksum(w),
        w_abs_sum=checksums.weight_abs_checksum(w),
    )


@dataclasses.dataclass(frozen=True)
class ABFTConfig:
    """Framework-wide ABFT config, threaded through model construction.

    DEPRECATED as a policy surface: selection lives in the
    ProtectionPolicy API (core/policy.py); this object merely carries
    execution knobs (hardware, kernel choice, c_factor) plus the policy.
    ``scheme``/``selector`` survive for legacy callers and are folded
    into ``effective_policy()``; prefer ``ABFTConfig.from_policy``."""

    enabled: bool = True
    scheme: Scheme = Scheme.AUTO
    selector: SelectorConfig = SelectorConfig()
    # None: the device's own spec (core/hardware.py::device_hardware)
    hardware: HardwareSpec | None = None
    blocks: BlockShape = BlockShape()
    # off a TPU: True runs block schemes through the Pallas kernel in
    # interpret mode, False through the jnp emulation.  Ignored on a TPU,
    # where they always run the compiled kernel (``runs_kernels``)
    use_pallas: bool = True
    c_factor: float = 16.0
    protect_backward: bool = False  # optional dgrad/wgrad protection
    # tensor-parallel jax.sharding.Mesh (with a 'model' axis) the kernel
    # schemes run per shard on; None: no per-shard execution
    mesh: Any = None
    # fused-ABFT flash attention backend (kernels/flash_attention.py):
    # protects attention's own GEMMs in-kernel and keeps score chunks in
    # VMEM (the §Perf-identified lever).  XLA chunked attention otherwise.
    flash_attention: bool = False
    # the first-class selection strategy; None falls back to the legacy
    # scheme/selector fields (exact same decisions, same code path)
    policy: ProtectionPolicy | None = None

    def __post_init__(self):
        # Warn exactly when the legacy selection surface is in use: a
        # non-AUTO fixed scheme or a non-default SelectorConfig with no
        # first-class policy.  Plain ABFTConfig() / scheme=AUTO stays
        # silent — those denote the default IntensityGuidedPolicy and are
        # not steering selection through the deprecated fields.
        # stacklevel=3: warn -> __init__ (generated) -> caller.
        if self.policy is None and (
                self.scheme != Scheme.AUTO
                or self.selector != SelectorConfig()):
            warnings.warn(
                "ABFTConfig(scheme=..., selector=...) is deprecated as a "
                "selection surface; build a ProtectionPolicy "
                "(core/policy.py) and wrap it via ABFTConfig.from_policy "
                "— FixedPolicy(scheme) replaces scheme=, "
                "policy_from_selector(selector) replaces selector=",
                DeprecationWarning, stacklevel=3)

    def effective_policy(self) -> ProtectionPolicy:
        """The ProtectionPolicy this config denotes (the facade's whole
        job).  Precedence: disabled > explicit policy > fixed legacy
        scheme > legacy SelectorConfig."""
        if not self.enabled:
            return FixedPolicy(Scheme.NONE)
        if self.policy is not None:
            return self.policy
        if self.scheme != Scheme.AUTO:
            return FixedPolicy(self.scheme)
        return policy_from_selector(self.selector)

    @property
    def runs_kernels(self) -> bool:
        """Block schemes run the Pallas kernel (compiled on a TPU,
        interpret mode elsewhere) rather than the jnp emulation."""
        return self.use_pallas or on_tpu()

    @property
    def hw(self) -> HardwareSpec:
        """The spec selection runs against: ``hardware`` if given, else
        the device's (resolved at first use, never at import)."""
        return self.hardware if self.hardware is not None \
            else device_hardware()

    def resolve(self, dims: GemmDims, first_layer: bool = False):
        """Scheme for one GEMM shape (Scheme enum for built-ins, name
        string for registered plug-in schemes).  Passes itself as the
        policy's ``cfg`` so registry availability predicates see the
        active backend."""
        return self.effective_policy().select(
            dims, self.hw, first_layer=first_layer,
            cfg=self).scheme

    @staticmethod
    def off() -> "ABFTConfig":
        return ABFTConfig(enabled=False)

    @staticmethod
    def from_policy(policy: ProtectionPolicy, **kw) -> "ABFTConfig":
        """Wrap a ProtectionPolicy for call sites that still take the
        config object (models, engine, trainer)."""
        return ABFTConfig(policy=policy, **kw)


def _gemm_dims(x: jnp.ndarray, w: jnp.ndarray, out_dtype) -> GemmDims:
    *lead, m, k = x.shape
    n = w.shape[-1]
    batch = 1
    for d in lead:
        batch *= d
    return GemmDims(
        m=batch * m, k=k, n=n, batch=1,
        dtype_bytes=jnp.dtype(x.dtype).itemsize,
        out_dtype_bytes=jnp.dtype(out_dtype).itemsize,
    )


def protected_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    cfg: ABFTConfig = ABFTConfig(),
    *,
    wsums: WeightChecksums | None = None,
    out_dtype=None,
    fault: FaultSpec | None = None,
    first_layer: bool = False,
    site: str = "unlabeled",
) -> tuple[jnp.ndarray, CheckResult]:
    """ABFT-protected ``y = x @ w``.

    x: (..., m, k);  w: (k, n).  Returns (y, CheckResult).
    ``fault`` (optional) injects a single output fault for testing — on the
    block path it corrupts the kernel accumulator; on the global path the
    materialized output.

    The active policy resolves the scheme for these dims at trace time;
    the scheme's registered executor (SchemeRegistry) runs it inside an
    ``abft[<scheme>][<site>]`` named scope — the static marker the
    coverage auditor (repro.analysis) reads back off the jaxpr to prove
    every GEMM flows through a registered scheme.  ``site`` is the
    plan-facing layer tag (``attn.q``, ``mlp.down``, ...) threaded down
    from the model layers; audit cross-validation matches it against
    ``ProtectionPlan`` LayerSpec names."""
    out_dtype = out_dtype or x.dtype
    dims = _gemm_dims(x, w, out_dtype)
    scheme = cfg.resolve(dims, first_layer=first_layer)
    executor = default_registry().executor(scheme)
    with protection_scope(scheme_name_of(scheme), site):
        dim = _kernel_shard_dim(cfg, scheme, site, w)
        if dim is not None:
            return _per_shard(executor, x, w, cfg, dim,
                              out_dtype=out_dtype, fault=fault)
        return executor(x, w, cfg, wsums=wsums, out_dtype=out_dtype,
                        fault=fault)


# ------------------------------------------------ per-shard kernel schemes
KERNEL_SCHEMES = ("block_1s", "block_2s", "replica")


def _kernel_shard_dim(cfg: ABFTConfig, scheme, site: str, w) -> str | None:
    """The GEMM dim ('n' column-parallel, 'k' row-parallel) the mesh's
    'model' axis shards at this site when a Pallas kernel runs it, else
    None.  Mirrors the parameter specs: a dim the axis does not divide
    stays replicated (distributed/sharding.py::sanitize_spec)."""
    if (cfg.mesh is None or not cfg.runs_kernels
            or scheme_name_of(scheme) not in KERNEL_SCHEMES):
        return None
    from repro.models.counting import TP_SHARD_DIM

    tp = cfg.mesh.shape["model"]
    dim = TP_SHARD_DIM.get(site)
    if tp == 1 or dim is None:
        return None
    size = w.shape[1] if dim == "n" else w.shape[0]
    return dim if size % tp == 0 else None


def _per_shard(executor, x, w, cfg: ABFTConfig, dim: str, *, out_dtype,
               fault):
    """Run a kernel scheme on each device's shard under ``shard_map``
    (module docstring).  The flag is the OR over shards; residual and
    threshold fold to the worst shard's max residual and min threshold.
    A fault's global column is translated to the shard owning it
    (column-parallel) or injected on shard 0 (row-parallel)."""
    lead = (None,) * (x.ndim - 1)
    if dim == "n":
        specs = (P(), P(None, "model"), P(*lead, "model"))
        local_dtype = out_dtype
    else:
        specs = (P(*lead, "model"), P("model", None), P())
        local_dtype = jnp.float32       # partials sum in f32, then cast
    fault = fault if fault is not None else FaultSpec.none()

    def body(xs, ws, f):
        s = jax.lax.axis_index("model")
        if dim == "n":
            n_loc = ws.shape[1]
            col = f.col - s * n_loc
            on = (col >= 0) & (col < n_loc)
            f = f._replace(col=col, enabled=f.enabled * on)
        else:
            f = f._replace(enabled=f.enabled * (s == 0))
        y, chk = executor(xs, ws, cfg, wsums=None, out_dtype=local_dtype,
                          fault=f)
        if dim == "k":
            y = jax.lax.psum(y, "model").astype(out_dtype)
        flag = jax.lax.psum(chk.flag.astype(jnp.int32), "model") > 0
        res = jax.lax.pmax(jnp.max(chk.residual), "model")
        tau = jax.lax.pmin(jnp.min(chk.threshold), "model")
        return y, CheckResult(flag=flag, residual=res, threshold=tau)

    x_spec, w_spec, y_spec = specs
    f_spec = FaultSpec(*([P()] * len(FaultSpec._fields)))
    # check_vma=False: a pallas_call's outputs carry no varying-axes type
    return jax.shard_map(
        body, mesh=cfg.mesh, in_specs=(x_spec, w_spec, f_spec),
        out_specs=(y_spec, CheckResult(P(), P(), P())), check_vma=False,
    )(x, w, fault)


# ------------------------------------------------------------- executors
# The built-in schemes' execution paths, registered below.  Signature:
# (x, w, cfg, *, wsums, out_dtype, fault) -> (y, CheckResult).

def _plain_dot(x, w, out_dtype, fault):
    y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    y = y.astype(out_dtype)
    if fault is not None:
        y = inject_output_fault(y, fault)
    return y


def _exec_none(x, w, cfg, *, wsums, out_dtype, fault):
    return _plain_dot(x, w, out_dtype, fault), CheckResult.clean()


def _exec_global(x, w, cfg, *, wsums, out_dtype, fault):
    y = _plain_dot(x, w, out_dtype, fault)
    if wsums is None:
        wsums = precompute_weight_checksums(w)
    x2 = x.reshape((-1, x.shape[-1]))
    y2 = y.reshape((-1, y.shape[-1]))
    check = checksums.global_row_check(
        x2, wsums.w_sum, wsums.w_abs_sum, y2, c_factor=cfg.c_factor
    )
    return y, check


def _block_executor(mode: str):
    """Block-level schemes — the fused Pallas kernel, or the XLA
    emulation of the fused kernel's *semantics* when ``use_pallas=False``
    (used on CPU, e.g. inside the 512-device dry-run, where
    interpret-mode pallas_call cannot lower): the one-sided check with
    the weight checksum recomputed inline, as the kernel does.
    Sharding-friendly: pure einsums, no reshapes of sharded dims.  On a
    TPU the compiled kernel always runs (``ABFTConfig.runs_kernels``);
    its internal costs are modeled analytically for the roofline since a
    custom-call's internals are opaque to cost_analysis either way."""

    def _exec(x, w, cfg, *, wsums, out_dtype, fault):
        if cfg.runs_kernels:
            from repro.kernels import ops

            return ops.abft_matmul(
                x, w, mode=mode, blocks=cfg.blocks, out_dtype=out_dtype,
                fault=fault, c_factor=cfg.c_factor,
            )
        f32 = jnp.float32
        y = jnp.matmul(x, w, preferred_element_type=f32).astype(out_dtype)
        if fault is not None:
            y = inject_output_fault(y, fault)
        # reductions accumulate in f32 via dtype= — materializing
        # .astype(f32) copies of the weights would add 3x weight traffic
        # per layer to the emulation (measured; the fused kernel pays
        # none of this)
        w_sum = jnp.sum(w, axis=-1, dtype=f32)
        w_abs = jnp.sum(jnp.abs(w), axis=-1, dtype=f32)
        check = jnp.einsum("...mk,k->...m", x, w_sum.astype(x.dtype),
                           preferred_element_type=f32,
                           precision=checksums.CHECK_PRECISION)
        bound = jnp.einsum("...mk,k->...m", jnp.abs(x),
                           w_abs.astype(x.dtype),
                           preferred_element_type=f32,
                           precision=checksums.CHECK_PRECISION)
        yf = y.astype(f32)
        rowsum = jnp.sum(y, axis=-1, dtype=f32)
        res = jnp.abs(check - rowsum)
        rtol = checksums.tolerance_scale(x.shape[-1], c=cfg.c_factor)
        if x.dtype != f32:
            # w_sum was quantized to the activation dtype for the check
            # einsum: absorb its quantization into the threshold
            rtol = rtol + 0.5 * checksums.eps_of(x.dtype)
        tau = checksums.ATOL + rtol * bound
        if y.dtype != f32:
            tau = tau + 0.5 * checksums.eps_of(y.dtype) * jnp.sum(
                jnp.abs(yf), axis=-1)
        flag = checksums.flag_from(res, tau)
        return y, CheckResult(flag=flag, residual=res, threshold=tau)

    return _exec


for _name, _exec in (
    ("none", _exec_none),
    ("global", _exec_global),
    ("block_1s", _block_executor("1s")),
    ("block_2s", _block_executor("2s")),
    ("replica", _block_executor("replica")),
):
    default_registry().set_executor(_name, _exec)
