"""Pallas kernel sweeps: shapes × bits × batch × dtypes, interpret-mode
kernel body vs the pure-jnp oracle and vs exact dequantized matmul."""
import dataclasses

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


def _pow2_scales(bw):
    """`bw` with each scale rounded to a power of two: every product and
    sum of the kernels' f32 epilogue is then exact, whatever order or
    fused multiply-add the compiler picks, so outputs compare the integer
    cores bit for bit."""
    return dataclasses.replace(
        bw, scale=jnp.exp2(jnp.round(jnp.log2(bw.scale))))


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
    """Σ_k 2^k a^(k) = a_codes and Σ_i 2^i W^(i) = w_codes ⇒ the one-dot
    code path and the decomposed q·p-dot schedule produce identical
    integers; both match the jnp oracle."""
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
    np.testing.assert_array_equal(np.asarray(code), np.asarray(bits))
    np.testing.assert_allclose(np.asarray(code), np.asarray(ref),
                               rtol=1e-4, atol=1e-4 * scale)
    tn = bp._pick_blocks(n, m, q=q, rows=b)[0]
    z_a = spec.zero_point
    assert dots_per_tile(q, p, "code", tn=tn, z_a=z_a) == 1
    assert dots_per_tile(q, p, "bitserial", tn=tn, z_a=z_a) == q * p


@pytest.mark.parametrize("n,m,b", SHAPES[1:3])
# (1, 4) fills 2-bit fields with one plane; (5, 3) and (8, 4) 8-bit fields
@pytest.mark.parametrize("q,p", [(2, 4), (4, 4), (3, 2), (1, 4), (5, 3),
                                 (8, 4)])
@pytest.mark.parametrize("path", ["leaf", "fused"])
def test_one_dot_body_is_bit_identical(rng, n, m, b, q, p, path):
    """The code body folds the q weight planes into one code tile and
    issues one dot per cell; at ragged shapes its outputs EQUAL the
    bit-serial oracle's and the jnp reference's (not to a tolerance), in
    the per-leaf kernel and in the fused program kernel."""
    from repro.kernels.bitplane_gemv import program as bp_prog
    spec = QuantSpec(bits=p)
    ws = [_pow2_scales(make_bitplane_weights(
        jnp.asarray(rng.normal(size=(n, mm)), jnp.float32), QuantSpec(bits=q)))
        for mm in (m, m + 70)]
    a = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    refs = [bp.bitplane_gemv_bitserial(a, w, spec, impl="jnp") for w in ws]
    for fidelity in ("code", "bitserial"):
        if path == "leaf":
            outs = [bp.bitplane_gemv_bitserial(a, w, spec,
                                               impl="pallas_interpret",
                                               fidelity=fidelity)
                    for w in ws]
        else:
            plan = bp_prog.plan_from_weights(tuple(ws), spec)
            assert plan.one_dot
            outs = bp_prog.fused_group_linears(a, ws, p, fidelity=fidelity,
                                               interpret=True)
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_past_the_exactness_bound_takes_plane_dots(rng):
    """8-bit weights against 8-bit codes at zero point 0: tn·255·255 is
    past 2^24, so one f32-accumulated dot could round. Both kernels build
    the per-plane body there (the one-dot counters stay put) and still
    equal the jnp reference exactly, with the cell walking both of the
    reduction's compute tiles."""
    from repro.kernels.bitplane_gemv import kernel as bk
    from repro.kernels.bitplane_gemv import program as bp_prog
    n, m, b, q, p, z_a = 1000, 130, 2, 8, 8, 0
    bw = _pow2_scales(make_bitplane_weights(
        jnp.asarray(rng.normal(size=(n, m)), jnp.float32), QuantSpec(bits=q)))
    codes = jnp.asarray(rng.integers(0, 256, size=(b, n)), jnp.uint8)
    tn, bn, bm = bp._pick_blocks(n, m, q=q, rows=b)
    assert (tn, bn, bm) == (512, 1024, 256)
    assert not bk.one_dot_exact(q, p, z_a, tn)
    assert bk.dots_per_tile(q, p, "code", tn=tn, z_a=z_a) == q
    ref = np.asarray(bp.bitplane_gemv_codes(codes, bw, p, z_a, impl="jnp"))
    for fidelity in ("code", "bitserial"):
        got = bp.bitplane_gemv_codes(codes, bw, p, z_a,
                                     impl="pallas_interpret",
                                     fidelity=fidelity)
        np.testing.assert_array_equal(np.asarray(got), ref)
    # the per-leaf launch itself, built outside the jit cache
    a2 = bk._pad_axis(codes, bn, 1, value=z_a)
    planes = bk._pad_axis(bk._pad_axis(bw.planes, bn // 32, 1), bm, 2)
    scale_t = bk._pad_axis(bp._expand_scales(bw, tn, a2.shape[1]), bm, 1)
    l0, o0, c0 = bk.LAUNCHES, bk.ONE_DOT_LAUNCHES, bk.GRID_CELLS
    got = bk.gemv_bs_pallas(a2, planes, scale_t, q=q, p=p, z_a=z_a,
                            z_w=bw.zero, tn=tn, bn=bn, bm=bm, interpret=True)
    np.testing.assert_array_equal(np.asarray(got)[:, :m], ref)
    assert (bk.LAUNCHES - l0, bk.ONE_DOT_LAUNCHES - o0,
            bk.GRID_CELLS - c0) == (1, 0, 1)
    # the fused program kernel
    plan = bp_prog.build_plan(((n, m, q, 1, bw.zero, p, z_a),))
    assert not plan.one_dot
    planes_t, scale_t = bp_prog.pack_weights(plan, (bw,))
    codes_t = bp_prog.pack_codes(plan, (codes,), bk.row_block(b))
    params_t = jnp.asarray(bp_prog.pack_params(plan))
    l0, o0 = bp_prog.LAUNCHES, bp_prog.ONE_DOT_LAUNCHES
    for fidelity in ("code", "bitserial"):
        out = bp_prog.program_gemv(plan, codes_t, planes_t, scale_t,
                                   params_t, fidelity=fidelity,
                                   interpret=True)
        got = bp_prog.gather_outputs(plan, out, b)[0]
        np.testing.assert_array_equal(np.asarray(got), ref)
    assert (bp_prog.LAUNCHES - l0, bp_prog.ONE_DOT_LAUNCHES - o0) == (2, 0)


# (n, m, q, rows, scale groups): ragged n and m, the decode and a
# multi-row launch, per-group scales at a 256-row tile and at 512
CELLS = [(1000, 300, 2, 4, 1), (1600, 130, 4, 40, 1), (2048, 384, 2, 4, 8),
         (2048, 640, 4, 40, 4)]


@pytest.mark.parametrize("n,m,q,rows,g", CELLS)
def test_cell_walk_equals_one_tile_cells(rng, n, m, q, rows, g):
    """The picker's cell walks several compute tiles; its outputs EQUAL
    (not to a tolerance, and with unrounded scales) the same launch with
    one-tile cells and the 256-column output block — the blocks every
    launch had before cells were sized from the shape — in the per-leaf
    kernel; and the fused kernel, over members of different m with the
    same walk, equals the per-leaf path. Fewer grid cells, same numbers."""
    from repro.kernels.bitplane_gemv import kernel as bk
    from repro.kernels.bitplane_gemv import program as bp_prog
    spec = QuantSpec(bits=4)
    ws = [make_bitplane_weights(
        jnp.asarray(rng.normal(size=(n, mm)), jnp.float32),
        QuantSpec(bits=q, group_size=n // g if g > 1 else -1))
        for mm in (m, m + 256)]
    a = jnp.asarray(rng.normal(size=(rows, n)), jnp.float32)
    aq = quantize_activations(a, spec)
    tn, bn, bm = bp._pick_blocks(n, m, None, None, n // g if g > 1 else None,
                                 q=q, rows=rows)
    assert bn > tn          # the cell walks more than one tile
    for fidelity in ("code", "bitserial"):
        c0 = bk.GRID_CELLS
        got = bp.bitplane_gemv_codes(aq.values, ws[0], 4, aq.zero,
                                     impl="pallas_interpret",
                                     fidelity=fidelity)
        c1 = bk.GRID_CELLS
        one = bp.bitplane_gemv_codes(aq.values, ws[0], 4, aq.zero,
                                     impl="pallas_interpret", bn=tn, bm=256,
                                     fidelity=fidelity)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
        assert 0 < c1 - c0 < bk.GRID_CELLS - c1
        fused = bp_prog.fused_group_linears(a, ws, 4, fidelity=fidelity,
                                            interpret=True)
        for w, out in zip(ws, fused):
            ref = bp.bitplane_gemv_bitserial(a, w, spec,
                                              impl="pallas_interpret",
                                              fidelity=fidelity)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


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
