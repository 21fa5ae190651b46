"""Backend protocol + registry, and the deprecation shims that keep the old
string `mode=` / `impl=` call sites working.

Acceptance (ISSUE 4): old `register`/`gemv(mode=...)` call sites still pass
via deprecation shims; no backend-name string literals remain outside the
registry — every call site resolves through `core.backends`.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends
from repro.core.backends import (JNP, PALLAS, PALLAS_INTERPRET, SIM, Backend,
                                 get_backend, register_backend, resolve_impl)
from repro.core.bitplane import make_bitplane_weights
from repro.core.engine import EngineLinear, MVDRAMEngine
from repro.core.pud.gemv import PudGeometry
from repro.core.quant import QuantSpec

GEOM = PudGeometry(subarray_cols=32, n_sub_max=16,
                   channels=2, banks_per_channel=2)


def _engine(rng, n=48, m=12):
    eng = MVDRAMEngine(geom=GEOM)
    w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    h = eng.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    return eng, h


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_resolves_names_and_instances():
    assert get_backend("jnp") is JNP
    assert get_backend("pallas") is PALLAS
    assert get_backend("sim") is SIM
    assert get_backend(None) is backends.DEFAULT
    assert get_backend(SIM) is SIM
    assert set(backends.backend_names()) >= {"jnp", "pallas", "sim"}


def test_registry_rejects_unknown():
    with pytest.raises(ValueError, match="unknown mode 'tpu-v9'"):
        get_backend("tpu-v9")
    with pytest.raises(TypeError):
        get_backend(42)
    with pytest.raises(ValueError, match="already registered"):
        register_backend(backends.JnpBackend())


def test_kernel_impl_strings_live_in_backends():
    assert JNP.kernel_impl == "jnp"
    if jax.default_backend() == "tpu":
        assert PALLAS.kernel_impl == "pallas"
    else:   # never a quiet switch to interpret mode off-TPU
        with pytest.raises(RuntimeError, match="PALLAS_INTERPRET"):
            PALLAS.kernel_impl
    assert SIM.kernel_impl is None
    # the pre-registry impl string still resolves (forced interpret mode)
    assert get_backend("pallas_interpret").kernel_impl == "pallas_interpret"


def test_pallas_interpret_string_still_serves(rng):
    """`impl="pallas_interpret"` worked before the registry — it must keep
    resolving end to end (ServeEngine/EngineLinear-style call sites)."""
    eng, h = _engine(rng)
    a = jnp.asarray(rng.normal(size=(2, 48)), jnp.float32)
    out_i = eng.gemv(h, a, backend="pallas_interpret")
    out_j = eng.gemv(h, a, backend=JNP)
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_j),
                               rtol=1e-4, atol=1e-4)
    lin = EngineLinear(eng, backend="pallas_interpret")
    assert lin.mode == "pallas_interpret"


def test_sim_oracle_paths_do_not_stage_resident_rows(rng):
    """1-D / naive / wave=False sim launches run the per-call oracle and
    must NOT lazily build (and pin) the resident staging."""
    eng, h = _engine(rng)
    a1 = jnp.asarray(rng.normal(size=(48,)), jnp.float32)
    eng.gemv(h, a1, backend=SIM)
    eng.gemv(h, a1, backend=SIM, naive=True)
    eng.gemv(h, a1, backend=SIM, wave=False)
    assert eng.residency_stats()["staged_layers"] == 0
    eng.gemv(h, a1[None, :], backend=SIM)     # 2-D: resident path stages
    assert eng.residency_stats()["staged_layers"] == 1


def test_resolve_impl():
    assert resolve_impl(None) == backends.DEFAULT.kernel_impl
    assert resolve_impl(PALLAS_INTERPRET) == "pallas_interpret"
    assert resolve_impl("pallas_interpret") == "pallas_interpret"
    fn = lambda x, w, ab: x                     # noqa: E731
    assert resolve_impl(fn) is fn


def test_custom_backend_registration(rng):
    class EchoBackend(Backend):
        name = "echo-test"

        def gemv(self, engine, handle, a, **opts):
            return ("echo", handle.name)

    be = register_backend(EchoBackend())
    try:
        eng, h = _engine(rng)
        assert eng.gemv(h, jnp.zeros((48,)), backend="echo-test") \
            == ("echo", "w")
    finally:
        backends._REGISTRY.pop("echo-test")


# ---------------------------------------------------------------------------
# Deprecation shims — old string-mode call sites
# ---------------------------------------------------------------------------

def test_gemv_mode_string_shim_warns_and_matches(rng):
    eng, h = _engine(rng)
    a = jnp.asarray(np.random.default_rng(0).normal(size=(2, 48)),
                    jnp.float32)
    with pytest.warns(DeprecationWarning, match="mode='jnp' is deprecated"):
        out_shim = eng.gemv(h, a, mode="jnp")
    out_new = eng.gemv(h, a, backend=JNP)
    np.testing.assert_array_equal(np.asarray(out_shim), np.asarray(out_new))
    with pytest.warns(DeprecationWarning):
        out_sim, rep = eng.gemv(h, a, mode="sim")
    out_sim2, rep2 = eng.gemv(h, a, backend=SIM)
    np.testing.assert_array_equal(np.asarray(out_sim), np.asarray(out_sim2))
    assert rep.runtime.asdict() == rep2.runtime.asdict()


def test_linear_mode_string_shim(rng):
    eng, _h = _engine(rng)
    w = make_bitplane_weights(
        jnp.asarray(np.random.default_rng(1).normal(size=(32, 8)),
                    jnp.float32), QuantSpec(bits=4))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(3, 32)),
                    jnp.float32)
    with pytest.warns(DeprecationWarning):
        out_shim = eng.linear(x, w, act_bits=4, mode="jnp")
    out_new = eng.linear(x, w, act_bits=4, backend=JNP)
    np.testing.assert_array_equal(np.asarray(out_shim), np.asarray(out_new))
    # sim audit route places the leaf as a resident handle
    out_sim = eng.linear(x, w, act_bits=4, backend=SIM)
    np.testing.assert_allclose(np.asarray(out_sim), np.asarray(out_new),
                               rtol=1e-4, atol=1e-4)
    # same leaf again: resolved to the SAME resident registration
    before = eng.pool.stats()["placements"]
    eng.linear(x, w, act_bits=4, backend=SIM)
    assert eng.pool.stats()["placements"] == before


def test_engine_linear_shim_and_mode_property(rng):
    eng, _h = _engine(rng)
    with pytest.warns(DeprecationWarning):
        lin_shim = EngineLinear(eng, mode="jnp")
    lin_new = EngineLinear(eng, backend=JNP)
    assert lin_shim.backend is lin_new.backend is JNP
    # string-only call sites (MoE vmap) still read a kernel impl string
    assert lin_shim.mode == "jnp"
    assert EngineLinear(eng).backend is backends.DEFAULT
    w = make_bitplane_weights(
        jnp.asarray(np.random.default_rng(1).normal(size=(32, 8)),
                    jnp.float32), QuantSpec(bits=4))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 32)),
                    jnp.float32)
    np.testing.assert_array_equal(np.asarray(lin_shim(x, w, 4)),
                                  np.asarray(lin_new(x, w, 4)))


def test_dense_default_impl_resolves_through_registry(rng):
    from repro.models.layers import dense
    w = make_bitplane_weights(
        jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
        QuantSpec(bits=4))
    x = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(dense(x, w)),                       # None → default
        np.asarray(dense(x, w, impl=backends.DEFAULT)))
    np.testing.assert_allclose(
        np.asarray(dense(x, w)),
        np.asarray(dense(x, w, impl="pallas_interpret")),
        rtol=1e-4, atol=1e-4)
