"""Pallas kernel sweeps: shapes × bits × batch × dtypes, interpret-mode
kernel body vs the pure-jnp oracle and vs exact dequantized matmul."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitplane import make_bitplane_weights
from repro.core.quant import (QuantSpec, dequantize_weights,
                              quantize_activations, quantize_weights,
                              quantized_gemv_reference)
from repro.kernels.bitplane_gemv import ops as bp
from repro.kernels.quant_matmul import ops as qm

SHAPES = [(512, 256, 1), (384, 300, 3), (1000, 130, 2), (256, 512, 4)]


@pytest.mark.parametrize("n,m,b", SHAPES)
@pytest.mark.parametrize("q", [2, 4, 8])
def test_bitplane_f32_kernel_vs_exact(rng, n, m, b, q):
    w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=q))
    exact = a @ dequantize_weights(quantize_weights(w, QuantSpec(bits=q)))
    got = bp.bitplane_gemv(a, bw, impl="pallas_interpret")
    ref = bp.bitplane_gemv(a, bw, impl="jnp")
    scale = float(jnp.abs(exact).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("n,m,b", SHAPES[:3])
@pytest.mark.parametrize("q,p", [(2, 4), (4, 4), (3, 2)])
def test_bitplane_bitserial_kernel_vs_integer_ref(rng, n, m, b, q, p):
    w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=q))
    wq = quantize_weights(w, QuantSpec(bits=q))
    ref = np.stack([np.asarray(quantized_gemv_reference(
        quantize_activations(a[i], QuantSpec(bits=p)), wq))
        for i in range(b)])
    got = bp.bitplane_gemv_bitserial(a, bw, QuantSpec(bits=p),
                                     impl="pallas_interpret")
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4,
                               atol=1e-4 * scale)


@pytest.mark.parametrize("n,m,b", SHAPES[:2])
@pytest.mark.parametrize("q,p", [(2, 4), (4, 4), (3, 2)])
def test_code_dot_fast_path_equals_bitserial(rng, n, m, b, q, p):
    """Σ_k 2^k a^(k) = a_codes ⇒ the q-dot fast path and the decomposed
    q·p-dot schedule produce identical integers; both match the jnp oracle."""
    from repro.kernels.bitplane_gemv.kernel import dots_per_tile
    w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=q))
    spec = QuantSpec(bits=p)
    ref = bp.bitplane_gemv_bitserial(a, bw, spec, impl="jnp")
    code = bp.bitplane_gemv_bitserial(a, bw, spec, impl="pallas_interpret",
                                      fidelity="code")
    bits = bp.bitplane_gemv_bitserial(a, bw, spec, impl="pallas_interpret",
                                      fidelity="bitserial")
    scale = float(jnp.abs(ref).max()) + 1e-9
    assert float(jnp.abs(code - bits).max()) / scale <= 1e-4
    np.testing.assert_allclose(np.asarray(code), np.asarray(ref),
                               rtol=1e-4, atol=1e-4 * scale)
    assert dots_per_tile(q, p, "code") == q
    assert dots_per_tile(q, p, "bitserial") == q * p


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bitplane_kernel_dtypes(rng, dtype):
    w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(2, 256)), jnp.dtype(dtype))
    bw = make_bitplane_weights(w, QuantSpec(bits=4))
    got = bp.bitplane_gemv(a, bw, impl="pallas_interpret")
    ref = bp.bitplane_gemv(a.astype(jnp.float32), bw, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2 * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("block", [(64, 128), (128, 256), (256, 128)])
def test_bitplane_kernel_block_shape_sweep(rng, block):
    bn, bm = block
    w = jnp.asarray(rng.normal(size=(512, 384)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(1, 512)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=3))
    ref = bp.bitplane_gemv(a, bw, impl="jnp")
    got = bp.bitplane_gemv(a, bw, impl="pallas_interpret", bn=bn, bm=bm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n,m,b", SHAPES[:3])
@pytest.mark.parametrize("q,gs", [(4, -1), (8, 256), (2, -1)])
def test_quant_matmul_kernel(rng, n, m, b, q, gs):
    if gs > 0 and n % gs:
        pytest.skip("group must divide n")
    w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    wq = quantize_weights(w, QuantSpec(bits=q, group_size=gs))
    exact = a @ dequantize_weights(wq)
    got = qm.quant_matmul(a, wq, impl="pallas_interpret")
    scale = float(jnp.abs(exact).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               rtol=1e-4, atol=1e-4 * scale)


def test_kernels_agree_with_engine_modes(rng):
    """pallas_interpret == jnp == PUD sim through the engine."""
    from repro.core.engine import MVDRAMEngine
    from repro.core.pud.gemv import PudGeometry
    eng = MVDRAMEngine(geom=PudGeometry(subarray_cols=128, n_sub_max=64))
    w = jnp.asarray(rng.normal(size=(128, 24)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    h = eng.register("m", w, QuantSpec(bits=3), a_spec=QuantSpec(bits=4))
    o_sim, _ = eng.gemv(h, a, mode="sim")
    o_jnp = eng.gemv(h, a, mode="jnp")
    o_pl = eng.gemv(h, a[None], mode="pallas_interpret")[0]
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_sim),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_pl),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fidelity", ["code", "bitserial"])
def test_row_tiled_kernels_match_single_block(rng, fidelity):
    """Past ROW_BLOCK rows (a prefill chunk) the activation rows tile over
    a grid axis; every row still matches its own single-block launch —
    bitwise on the integer paths (code kernel, fused group), to f32
    rounding on the float kernel."""
    from repro.kernels.bitplane_gemv import program as bp_prog
    from repro.kernels.bitplane_gemv.kernel import ROW_BLOCK
    n, m, rows = 300, 130, ROW_BLOCK + 44
    spec = QuantSpec(bits=4)
    ws = [make_bitplane_weights(
        jnp.asarray(rng.normal(size=(n, m)), jnp.float32), QuantSpec(bits=q))
        for q in (2, 3)]
    a = jnp.asarray(rng.normal(size=(rows, n)), jnp.float32)
    aq = quantize_activations(a, spec)
    tiled = bp.bitplane_gemv_codes(aq.values, ws[0], 4, aq.zero,
                                   impl="pallas_interpret", fidelity=fidelity)
    blocks = [bp.bitplane_gemv_codes(aq.values[s:s + 4], ws[0], 4, aq.zero,
                                     impl="pallas_interpret",
                                     fidelity=fidelity)
              for s in (0, ROW_BLOCK, rows - 4)]
    np.testing.assert_array_equal(
        np.asarray(tiled)[[0, 1, 2, 3, ROW_BLOCK, ROW_BLOCK + 1,
                           ROW_BLOCK + 2, ROW_BLOCK + 3, -4, -3, -2, -1]],
        np.concatenate([np.asarray(b) for b in blocks]))
    # the float kernel's f32 dot may sum in a shape-dependent order
    f_tiled = bp.bitplane_gemv(a, ws[1], impl="pallas_interpret")
    f_last = bp.bitplane_gemv(a[-4:], ws[1], impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(f_tiled)[-4:], np.asarray(f_last),
                               rtol=1e-5, atol=1e-5)
    fused = bp_prog.fused_group_linears(a, ws, 4, fidelity=fidelity,
                                        interpret=True)
    for w, out in zip(ws, fused):
        ref = bp.bitplane_gemv_bitserial(a, w, spec, impl="pallas_interpret",
                                         fidelity=fidelity)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
