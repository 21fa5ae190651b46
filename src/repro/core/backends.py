"""Execution backends for the MVDRAM engine — the ONE place backend names
live.

The engine's three interchangeable executors used to be picked by string
`mode` kwargs ("jnp" | "pallas" | "sim") scattered through `engine.py`,
`models/layers.py` and `serve/engine.py`. They are now first-class objects
behind a small protocol:

  `Backend.gemv(engine, handle, a, **opts)`   one registered GeMV
  `Backend.linear(engine, x, w, act_bits)`    one serving linear
  `Backend.linear_group(engine, x, ws, b)`    k linears sharing one input
                                              (q/k/v, up/gate) — Pallas
                                              fuses them into one launch
  `Backend.run_program(engine, prog, xs)`     a compiled GemvProgram decode
                                              block — Pallas: one fused
                                              launch; sim: the fused wave
                                              schedule; default: per-leaf
  `Backend.kernel_impl`                       the kernel-registry impl
                                              string this backend lowers to

Call sites hold `Backend` instances (`JNP`, `PALLAS`, `SIM`, or
`get_backend(...)`); the string names exist only in this registry, where
`get_backend` also serves the deprecation shims — old `mode="sim"`-style
call sites keep working through it (with a `DeprecationWarning`) until they
migrate. Registering a custom backend is `register_backend(MyBackend())`.
"""
from __future__ import annotations

import abc
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp


class Backend(abc.ABC):
    """One way to execute a registered GeMV / serving linear."""

    #: registry name (unique)
    name: str = ""

    @property
    def kernel_impl(self) -> Optional[str]:
        """The `kernels/*` impl string this backend lowers dense/bit-plane
        kernel calls to; None for backends with no kernel lowering (sim)."""
        return None

    @abc.abstractmethod
    def gemv(self, engine, handle, a: jax.Array, **opts):
        """Execute handle's GeMV on a (N,) vector or (B, N) lane batch."""

    def linear(self, engine, x: jax.Array, w, act_bits: Optional[int]):
        """One lane-batched serving linear on a packed weight leaf."""
        from ..kernels.bitplane_gemv import ops as bp_ops
        from .quant import QuantSpec
        if act_bits:
            return bp_ops.bitplane_gemv_bitserial(
                x, w, QuantSpec(bits=act_bits), impl=self.kernel_impl)
        return bp_ops.bitplane_gemv(x, w, impl=self.kernel_impl)

    def linear_group(self, engine, x: jax.Array, ws: tuple,
                     act_bits: Optional[int]) -> tuple:
        """k serving linears sharing one input (q/k/v, up/gate). Default:
        per-leaf `linear` calls — backends that can fuse them override."""
        return tuple(self.linear(engine, x, w, act_bits) for w in ws)

    def run_program(self, engine, program, activations, *,
                    lane_mask=None, fidelity: str = "code"):
        """Execute a compiled `GemvProgram` decode block; returns per-layer
        outputs. Default: per-leaf linears — identical results, no fusion."""
        import jax.numpy as jnp
        outs = []
        for h, x in zip(program.handles, activations):
            program._check_layer(h)
            out = self.linear(engine, jnp.asarray(x), h.weights,
                              h.a_spec.bits)
            if lane_mask is not None:
                out = jnp.where(jnp.asarray(lane_mask)[:, None], out, 0)
            outs.append(out)
        return outs

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


class JnpBackend(Backend):
    """Pure-jnp bit-plane oracle (any shape; the kernel's reference)."""

    name = "jnp"

    @property
    def kernel_impl(self) -> str:
        return "jnp"

    def gemv(self, engine, handle, a, **opts):
        from .bitplane import bitplane_gemv_bitserial, bitplane_gemv_f32
        from .quant import quantize_activations
        if handle.a_spec is None:
            return bitplane_gemv_f32(a, handle.weights)
        aq = quantize_activations(a, handle.a_spec)
        return bitplane_gemv_bitserial(aq, handle.weights)


def require_tpu(what: str) -> None:
    """Raise unless JAX runs on a TPU: a compiled Pallas kernel never
    quietly turns into its interpreter, so a CPU run cannot pass for a
    kernel run."""
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"{what} compiles the Pallas kernels for a TPU, but JAX runs on "
            f"{jax.default_backend()!r}; name PALLAS_INTERPRET (the kernel "
            f"body in interpret mode) to run them here")


class PallasBackend(Backend):
    """The TPU kernel (kernels/bitplane_gemv) — a single source of truth
    for gemv() and serving linear(). Raises off-TPU: CPU callers name
    `PALLAS_INTERPRET`."""

    name = "pallas"

    @property
    def kernel_impl(self) -> str:
        require_tpu("the PALLAS backend")
        return "pallas"

    def gemv(self, engine, handle, a, *, fidelity: str = "code", **opts):
        from ..kernels.bitplane_gemv import ops as bp_ops
        if handle.a_spec is None:
            return bp_ops.bitplane_gemv(a, handle.weights,
                                        impl=self.kernel_impl)
        return bp_ops.bitplane_gemv_bitserial(
            a, handle.weights, handle.a_spec, impl=self.kernel_impl,
            fidelity=fidelity)

    def linear_group(self, engine, x, ws, act_bits):
        """Fuse the group into ONE Pallas launch (program.py) — bit-exact
        with the per-leaf path (padding-invariance algebra, tested)."""
        if not act_bits or len(ws) < 2:
            return super().linear_group(engine, x, ws, act_bits)
        from ..kernels.bitplane_gemv import program as bp_program
        return bp_program.fused_group_linears(
            x, ws, act_bits,
            interpret=(self.kernel_impl == "pallas_interpret"))

    def run_program(self, engine, program, activations, *,
                    lane_mask=None, fidelity: str = "code"):
        """The program-aware path: one fused launch per decode block."""
        return program.run_kernel(
            activations, fidelity=fidelity, lane_mask=lane_mask,
            interpret=(self.kernel_impl == "pallas_interpret"))


class PallasInterpretBackend(PallasBackend):
    """The Pallas kernel bodies in interpret mode, on any jax backend —
    how CPU callers (tests, examples) run the kernels."""

    name = "pallas_interpret"

    @property
    def kernel_impl(self) -> str:
        return "pallas_interpret"


class SimBackend(Backend):
    """Bit-exact PUD command-stream simulation (numpy; the ground truth).

    Residency-aware: a 2-D lane batch against a handle whose placement is
    live in the engine's `DramPool` executes against its staged rows
    (`StagedWaves`) with zero re-staging; 1-D vectors, the naive micro-op
    oracle and `wave=False` run the per-call staging paths — and never
    touch (or lazily build) the resident staging.
    """

    name = "sim"

    def gemv(self, engine, handle, a, *, naive: bool = False,
             wave=None, **opts):
        from .quant import quantize_activations
        from .pud.gemv import mvdram_gemv
        if handle.a_spec is None:
            raise ValueError("PUD simulation needs quantized activations")
        if a.ndim not in (1, 2):
            raise ValueError(
                f"sim backend takes a (N,) vector or a (B, N) lane "
                f"batch, got shape {tuple(a.shape)}")
        if engine.is_degraded(handle):
            # the fault-recovery ladder demoted this linear to the host
            # oracle (persistent bank faults past the retry/quarantine
            # budget) — serve it from jnp, no simulated command stream
            return jnp.asarray(JNP.gemv(engine, handle, a)), None
        resident_eligible = (a.ndim == 2 and not naive
                             and wave is not False)
        staged = engine.staged_for(handle) if resident_eligible else None
        if staged is not None:
            out, report = engine.run_resident(handle, a, staged)
        else:
            aq = quantize_activations(a, handle.a_spec)
            out, report = mvdram_gemv(aq, handle.wq,
                                      sparsity=engine.sparsity,
                                      geom=engine.geom, naive=naive,
                                      templates=handle.templates, wave=wave)
        return jnp.asarray(out), report

    def linear(self, engine, x, w, act_bits):
        if not act_bits:
            raise ValueError(
                "the sim audit route executes bit-serial command "
                "streams — float-activation linears need act_bits")
        return engine.sim_linear(x, w, act_bits)

    def run_program(self, engine, program, activations, *,
                    lane_mask=None, fidelity: str = "code"):
        """The simulator executes its own fused wave schedule."""
        outs, _report = program.run(activations, lane_mask=lane_mask)
        return outs


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend, overwrite: bool = False) -> Backend:
    if not backend.name:
        raise ValueError("backend needs a non-empty name")
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} is already registered")
    _REGISTRY[backend.name] = backend
    return backend


def backend_names() -> tuple:
    return tuple(sorted(_REGISTRY))


JNP = register_backend(JnpBackend())
PALLAS = register_backend(PallasBackend())
PALLAS_INTERPRET = register_backend(PallasInterpretBackend())
SIM = register_backend(SimBackend())
DEFAULT = JNP


def get_backend(spec: Union[str, Backend, None],
                warn_string: bool = False,
                what: str = "mode") -> Backend:
    """Resolve a backend spec: None → the default, `Backend` → itself,
    registry name → the instance. `warn_string=True` marks a legacy
    string-mode call site (the deprecation shims route through here)."""
    if spec is None:
        return DEFAULT
    if isinstance(spec, Backend):
        return spec
    if isinstance(spec, str):
        if spec not in _REGISTRY:
            raise ValueError(
                f"unknown {what} {spec!r}; registered backends: "
                f"{backend_names()}")
        if warn_string:
            warnings.warn(
                f"string {what}={spec!r} is deprecated; pass a Backend "
                f"(repro.core.backends.{spec.upper()}) or use the "
                f"`backend=` kwarg", DeprecationWarning, stacklevel=3)
        return _REGISTRY[spec]
    raise TypeError(f"cannot resolve a backend from {spec!r}")


def resolve(backend: Union[str, Backend, None],
            mode: Optional[str] = None, what: str = "mode") -> Backend:
    """The one shim entry for `backend=`/legacy `mode=` kwarg pairs: a
    non-None `mode` string resolves with the deprecation warning, else
    `backend` resolves silently (None → default)."""
    if mode is not None:
        return get_backend(mode, warn_string=True, what=what)
    return get_backend(backend, what=what)


def resolve_impl(impl) -> Union[str, object]:
    """Resolve a layer-level `impl` to what the kernel registry consumes:
    None → the default backend's kernel impl string; a `Backend` → its
    kernel impl; a callable (e.g. `EngineLinear`) or an explicit kernel
    impl string (e.g. "pallas_interpret") passes through unchanged."""
    if impl is None:
        return DEFAULT.kernel_impl
    if isinstance(impl, Backend):
        return impl.kernel_impl
    return impl
