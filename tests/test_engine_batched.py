"""Engine-level batch axis: `MVDRAMEngine.gemv` takes (B, N) lane batches in
all three backends (jnp / pallas / sim), the sim backend rejects bad ranks
with a clear ValueError, packed leaves round-trip exactly into the
simulator's codes, and `EngineLinear` routes serving linears through the
engine."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitplane import (from_quantized, make_bitplane_weights,
                                 to_quantized)
from repro.core.engine import EngineLinear, MVDRAMEngine
from repro.core.pud.gemv import BatchReport, PudGeometry, TileReport
from repro.core.quant import QuantSpec, quantize_weights

GEOM = PudGeometry(subarray_cols=32, n_sub_max=16,
                   channels=2, banks_per_channel=2)


def _engine_with_matrix(rng, n=48, m=12, q=4, p=4):
    eng = MVDRAMEngine(geom=GEOM)
    w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    h = eng.register("w", w, QuantSpec(bits=q), a_spec=QuantSpec(bits=p))
    return eng, h


def test_gemv_batched_all_modes_agree(rng):
    eng, h = _engine_with_matrix(rng)
    A = jnp.asarray(rng.normal(size=(3, 48)), jnp.float32)
    out_j = eng.gemv(h, A, mode="jnp")
    out_p = eng.gemv(h, A, mode="pallas_interpret")
    out_s, rep = eng.gemv(h, A, mode="sim")
    assert out_j.shape == out_p.shape == out_s.shape == (3, 12)
    assert isinstance(rep, BatchReport) and rep.batch == 3
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_p),
                               rtol=1e-4, atol=1e-4)
    # batched sim rows == the per-vector sim runs
    for b in range(3):
        o1, r1 = eng.gemv(h, A[b], mode="sim")
        assert isinstance(r1, TileReport)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(out_s[b]))


def test_gemv_sim_rejects_bad_rank(rng):
    eng, h = _engine_with_matrix(rng)
    with pytest.raises(ValueError, match="lane batch"):
        eng.gemv(h, jnp.zeros((2, 2, 48)), mode="sim")
    with pytest.raises(ValueError, match="lane batch"):
        eng.gemv(h, jnp.zeros(()), mode="sim")


def test_to_quantized_roundtrip_exact(rng):
    for q in (1, 2, 3, 4, 8):
        w = jnp.asarray(rng.normal(size=(40, 6)), jnp.float32)
        wq = quantize_weights(w, QuantSpec(bits=q))
        back = to_quantized(from_quantized(wq))
        np.testing.assert_array_equal(np.asarray(back.values),
                                      np.asarray(wq.values))
        assert back.zero == wq.zero and back.spec == wq.spec


def test_register_packed_serves_all_backends(rng):
    eng = MVDRAMEngine(geom=GEOM)
    w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=3))
    h = eng.register_packed("packed", bw, a_spec=QuantSpec(bits=3))
    assert h.templates is not None
    A = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    out_j = eng.gemv(h, A, mode="jnp")
    out_s, _ = eng.gemv(h, A, mode="sim")
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_s),
                               rtol=1e-4, atol=1e-4)
    # stacked (MoE) leaves are rejected with guidance
    stacked = make_bitplane_weights(w, QuantSpec(bits=3))
    stacked = type(stacked)(planes=stacked.planes[None], scale=stacked.scale,
                            zero=stacked.zero, col_sum=stacked.col_sum,
                            n=stacked.n, spec=stacked.spec)
    with pytest.raises(ValueError, match="2-D weight leaf"):
        eng.register_packed("bad", stacked)


def test_engine_linear_routes_and_matches_kernel_path(rng):
    """EngineLinear == the dense() bitplane branch, for float and
    bit-serial activations, and the sim audit path agrees."""
    eng = MVDRAMEngine(geom=GEOM)
    w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=4))
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    from repro.kernels.bitplane_gemv import ops as bp
    lin = EngineLinear(eng, mode="jnp")
    np.testing.assert_array_equal(
        np.asarray(lin(x, bw, None)),
        np.asarray(bp.bitplane_gemv(x, bw, impl="jnp")))
    np.testing.assert_array_equal(
        np.asarray(lin(x, bw, 4)),
        np.asarray(bp.bitplane_gemv_bitserial(x, bw, QuantSpec(bits=4),
                                              impl="jnp")))
    assert eng.routed_linears == 2
    assert lin.mode == "jnp"   # what string-only call sites read
    out_sim = eng.linear(x, bw, act_bits=4, mode="sim")
    np.testing.assert_allclose(np.asarray(out_sim), np.asarray(lin(x, bw, 4)),
                               rtol=1e-4, atol=1e-4)
