"""Fused whole-block program kernel (PR 8): one Pallas launch per decode
block, integer-identical to the per-leaf path.

The fused kernel (`kernels/bitplane_gemv/program.py`) pads every layer's
tiles up to a program-wide (BN, BM) envelope with exactness-preserving
values, so its outputs must be BITWISE equal — `np.array_equal`, not
allclose — to per-leaf `bitplane_gemv_bitserial` / `EngineLinear` calls
across ragged reduction dims, sub-block output dims, mixed weight and
activation precisions, grouped scales, concurrency groups, lane masks and
capacity programs. The launch-count hooks (`program.LAUNCHES`,
`kernel.LAUNCHES` — trace-time counters) pin down the "ONE launch per
block" claim itself.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends
from repro.core.engine import EngineLinear, MVDRAMEngine
from repro.core.pud.gemv import PudGeometry
from repro.core.quant import QuantSpec
from repro.kernels.bitplane_gemv import ops as bp
from repro.kernels.bitplane_gemv import program as bp_prog
from repro.kernels.bitplane_gemv.kernel import gemv_bs_pallas

GEOM = PudGeometry(subarray_cols=64, n_sub_max=32)

# (n, m, q, p, groups-of-scales): ragged n (non-multiples of 32), m below
# the 128 output block, weight bits 2..5, activation bits 2..4, grouped
# scales — every padding axis of the envelope at once
BLOCKS = [
    # heterogeneous q/k/v-style block + down projection
    [(300, 90, 2, 2, 1), (300, 90, 3, 3, 1), (300, 90, 4, 2, 1),
     (160, 40, 5, 4, 1)],
    # grouped scales (gs % 32 == 0, n % gs == 0) and mixed tile counts
    [(320, 200, 2, 2, 2), (480, 130, 4, 3, 3), (512, 256, 4, 2, 1)],
    # single layer, sub-block m
    [(256, 40, 3, 2, 1)],
]


def _build(cfgs, B, rng, groups=None, b_max=None):
    eng = MVDRAMEngine(geom=GEOM)
    hs, X = [], []
    for i, (n, m, q, p, g) in enumerate(cfgs):
        w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        gs = n // g if g > 1 else -1
        hs.append(eng.register(f"l{i}", w,
                               QuantSpec(bits=q, group_size=gs),
                               a_spec=QuantSpec(bits=p)))
        X.append(jnp.asarray(rng.normal(size=(B, n)), jnp.float32))
    prog = eng.compile(hs, groups=groups, b_max=b_max)
    return eng, hs, prog, X


def _per_leaf(hs, X):
    return [bp.bitplane_gemv_bitserial(x, h.weights, h.a_spec,
                                       impl="pallas_interpret")
            for x, h in zip(X, hs)]


@pytest.mark.parametrize("cfgs", BLOCKS)
@pytest.mark.parametrize("B", [1, 3])
def test_fused_block_bitwise_equals_per_leaf(rng, cfgs, B):
    groups = [[0, 1, 2], [3]] if len(cfgs) == 4 else None
    eng, hs, prog, X = _build(cfgs, B, rng, groups=groups)
    fused = prog.run_kernel(X, interpret=True)
    for f, ref, h in zip(fused, _per_leaf(hs, X), hs):
        assert np.array_equal(np.asarray(f), np.asarray(ref)), \
            f"layer {h.name}: fused != per-leaf (bitwise)"


@pytest.mark.parametrize("seed", range(4))
def test_fused_block_random_property(seed):
    """Random blocks: random layer count, ragged dims, mixed q/p, random
    group partition — fused must stay bitwise equal to per-leaf."""
    r = np.random.default_rng(100 + seed)
    L = int(r.integers(2, 6))
    cfgs = []
    for _ in range(L):
        n = int(r.choice([96, 160, 224, 300, 512]))
        m = int(r.choice([40, 90, 128, 200, 256]))
        q = int(r.integers(2, 6))
        p = int(r.integers(2, 5))
        g = int(r.choice([1, 2])) if n % 64 == 0 else 1
        cfgs.append((n, m, q, p, g))
    # random contiguous partition into concurrency groups
    cuts = sorted(set([0, L]) | set(
        int(c) for c in r.integers(1, L, size=2))) if L > 1 else [0, L]
    groups = [list(range(a, b)) for a, b in zip(cuts[:-1], cuts[1:])]
    B = int(r.integers(1, 4))
    eng, hs, prog, X = _build(cfgs, B, np.random.default_rng(200 + seed),
                              groups=groups)
    fused = prog.run_kernel(X, interpret=True)
    for f, ref in zip(fused, _per_leaf(hs, X)):
        assert np.array_equal(np.asarray(f), np.asarray(ref))


def test_one_launch_per_block(rng):
    """The tentpole claim, asserted via the trace-time hooks: a whole
    block costs ONE fused pallas_call; the per-leaf contrast costs one
    per weight leaf."""
    eng, hs, prog, X = _build(BLOCKS[0], 2, rng, groups=[[0, 1, 2], [3]])
    p0 = bp_prog.LAUNCHES
    prog.run_kernel(X, interpret=True)
    assert bp_prog.LAUNCHES - p0 == 1
    # repeat steps hit the jit cache: still no new launches
    prog.run_kernel(X, interpret=True)
    assert bp_prog.LAUNCHES - p0 == 1
    import repro.kernels.bitplane_gemv.kernel as leaf_kernel
    k0 = leaf_kernel.LAUNCHES
    _per_leaf(hs, X)
    assert leaf_kernel.LAUNCHES - k0 == len(hs)


def test_code_equals_bitserial_inside_fused_kernel(rng):
    """§V-D linearity collapse holds inside the fused kernel: the q-dot
    code path and the decomposed q·p-dot bit-serial path are identical."""
    eng, hs, prog, X = _build(BLOCKS[1], 2, rng)
    code = prog.run_kernel(X, fidelity="code", interpret=True)
    bits = prog.run_kernel(X, fidelity="bitserial", interpret=True)
    for c, b in zip(code, bits):
        assert np.array_equal(np.asarray(c), np.asarray(b))


def test_lane_mask_and_capacity(rng):
    """Capacity program: launches exactly b_max lanes; masked lanes come
    back as zero rows, active lanes bitwise-match the per-leaf path."""
    B = 4
    eng, hs, prog, X = _build(BLOCKS[0], B, rng,
                              groups=[[0, 1, 2], [3]], b_max=B)
    mask = np.array([True, False, True, False])
    outs = prog.run_kernel(X, lane_mask=mask, interpret=True)
    for o, ref in zip(outs, _per_leaf(hs, X)):
        o, ref = np.asarray(o), np.asarray(ref)
        assert np.array_equal(o[mask], ref[mask])
        assert not o[~mask].any()
    with pytest.raises(ValueError, match="b_max"):
        prog.run_kernel([x[:2] for x in X], interpret=True)
    with pytest.raises(ValueError, match="active lanes"):
        prog.run_kernel(X, lane_mask=np.zeros(B, bool), interpret=True)


def test_run_kernel_matches_engine_linear_and_backend_route(rng):
    """`Backend.run_program` on the Pallas-interpret backend routes to the
    fused kernel; per-leaf `EngineLinear` calls are the oracle."""
    eng, hs, prog, X = _build(BLOCKS[1], 2, rng)
    lin = EngineLinear(eng, backend=backends.PALLAS_INTERPRET)
    refs = [lin(x, h.weights, act_bits=h.a_spec.bits)
            for x, h in zip(X, hs)]
    via_backend = backends.PALLAS_INTERPRET.run_program(eng, prog, X)
    for got, ref in zip(via_backend, refs):
        assert np.array_equal(np.asarray(got), np.asarray(ref))
    # the default (JNP) backend's per-leaf fallback agrees numerically
    jnp_outs = backends.JNP.run_program(eng, prog, X)
    for got, ref in zip(jnp_outs, refs):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_fused_group_linears_and_dense_group(rng):
    """The serve-side group hook: q/k/v sharing one input fuse into one
    launch, bitwise equal to per-leaf dense() calls."""
    from repro.models.layers import dense, dense_group
    eng = MVDRAMEngine(geom=GEOM)
    n, B = 256, 2
    ws, hs = [], []
    for i, m in enumerate([90, 128, 200]):
        w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        hs.append(eng.register(f"g{i}", w, QuantSpec(bits=3),
                               a_spec=QuantSpec(bits=3)))
        ws.append(hs[-1].weights)
    x = jnp.asarray(rng.normal(size=(B, n)), jnp.float32)
    lin = EngineLinear(eng, backend=backends.PALLAS_INTERPRET)
    p0 = bp_prog.LAUNCHES
    fused = dense_group(x, tuple(ws), act_bits=3, impl=lin)
    assert bp_prog.LAUNCHES - p0 == 1
    for f, w in zip(fused, ws):
        ref = dense(x, w, act_bits=3, impl=lin)
        assert np.array_equal(np.asarray(f), np.asarray(ref))
    # non-engine impl falls back to per-leaf dense with the same numbers
    fb = dense_group(x, tuple(ws), act_bits=3, impl="pallas_interpret")
    for f, g in zip(fused, fb):
        np.testing.assert_allclose(np.asarray(f), np.asarray(g),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q", [None, 2])
def test_pick_blocks_pads_small_m_instead_of_shrinking(q):
    """m < 128 must keep bm at the 128 output block (callers slice
    out[:, :m]); shrinking bm to m used to hand Pallas a misaligned
    grid. The same holds for one-tile cells (no `q`), for cells sized from
    the shape and for a fused group's common block; a ragged m pads only
    to its 128-column tile there."""
    tn, bn, bm = bp._pick_blocks(256, 40, None, None, None, q=q, rows=4)
    assert (tn, bn, bm) == (256, 256, 128)
    tn, bn, bm = bp._pick_blocks(256, 300, None, None, None, q=q, rows=4)
    assert bm % 128 == 0
    if q:
        assert bm == 384 and bp.output_block((40, 300)) == 128


def test_value_errors_carry_shapes(rng):
    """Satellite: the former bare asserts across kernels/ now raise
    ValueErrors naming the offending shapes and values."""
    with pytest.raises(ValueError, match="group_size=48"):
        bp._pick_blocks(512, 256, None, None, 48)
    with pytest.raises(ValueError, match=r"fidelity.*nope.*\(2, 64\)"):
        gemv_bs_pallas(jnp.zeros((2, 64), jnp.uint8),
                       jnp.zeros((3, 2, 128), jnp.uint32),
                       jnp.zeros((1, 128), jnp.float32),
                       q=3, p=2, z_a=0, z_w=0, tn=64, bn=64, bm=128,
                       fidelity="nope")
    with pytest.raises(ValueError, match="fidelity"):
        bp_prog.program_gemv(None, jnp.zeros((1, 1, 1, 32), jnp.uint8),
                             None, None, None, fidelity="nope")
    from repro.core.quant import quantize_weights
    from repro.kernels.quant_matmul import ops as qm
    w = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    wq = quantize_weights(w, QuantSpec(bits=3))
    a = jnp.asarray(rng.normal(size=(1, 128)), jnp.float32)
    with pytest.raises(ValueError, match="packing.*density"):
        qm.quant_matmul(a, wq, impl="pallas_interpret", bn=64)
    from repro.kernels.decode_attention.kernel import decode_attention_pallas
    s, d = 100, 32   # 100 % 64 != 0
    with pytest.raises(ValueError, match="multiple of block=64"):
        decode_attention_pallas(
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 1, d), jnp.float32),
            jnp.zeros((1, s, 1, d), jnp.float32),
            jnp.zeros((1, s, 1, d), jnp.float32),
            jnp.zeros((1, s), jnp.int32), None, None,
            scale=1.0, window=None, block=64)


def test_run_kernel_input_validation(rng):
    eng, hs, prog, X = _build(BLOCKS[2], 2, rng)
    with pytest.raises(ValueError, match="activations"):
        prog.run_kernel(X + [X[0]], interpret=True)
    with pytest.raises(ValueError, match="expects"):
        prog.run_kernel([x[:, :-1] for x in X], interpret=True)
    # 1-D activations promote to B=1 and squeeze back
    one = prog.run_kernel([x[0] for x in X], interpret=True)
    ref = _per_leaf(hs, [x[:1] for x in X])
    for o, r in zip(one, ref):
        assert o.ndim == 1
        assert np.array_equal(np.asarray(o), np.asarray(r)[0])


def test_run_kernel_needs_a_tpu_or_interpret(rng):
    """The compiled kernel never quietly becomes its interpreter: off-TPU,
    `run_kernel` without `interpret=True` raises."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernel runs here")
    _eng, _hs, prog, X = _build(BLOCKS[2], 2, rng)
    with pytest.raises(RuntimeError, match="PALLAS_INTERPRET"):
        prog.run_kernel(X)


def _bench_config(config):
    """bench/run.py as a module, and a benchmark configuration's `as_run`
    block."""
    import json
    import os
    import sys
    bench_tests = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "tests")
    if bench_tests not in sys.path:
        sys.path.insert(0, bench_tests)
    import _paths                 # the benchmark tests' loader
    with open(os.path.join(_paths.BENCH, "configs", f"{config}.json")) as f:
        return _paths.bench_run(), json.load(f)["as_run"]


def _step_launches(bench_run, a, rows):
    """[(members' (n, m), grid cells, scoped VMEM, padded output columns)]
    of every bit-plane launch of one served step of `as_run` block `a` at
    `rows` activation rows, from the block picker: a per-leaf launch's
    `_pick_blocks`, a fused group's plan and `cell_tiles`. The shapes are
    the benchmark's (`families/dense.py:linears`)."""
    from repro.kernels.bitplane_gemv import kernel as bk
    q, p = a["weight_bits"], a["act_bits"]
    z_a = bp_prog.static_zero(QuantSpec(bits=p))
    br = bk.row_block(rows)
    groups = {}
    for name, n, m, grp in bench_run.spec.family("dense").linears(a):
        groups.setdefault(grp or name, []).append((n, m))
    out = []
    for members in groups.values():
        if len(members) == 1:
            (n, m), = members
            tn, bn, bm = bp._pick_blocks(n, m, q=q, rows=rows)
            k, nt, m_pad = bn // tn, -(-n // bn) * bn // tn, -(-m // bm) * bm
            cells = (m_pad // bm) * (nt // k)
        else:
            plan = bp_prog.build_plan(
                tuple((n, m, q, 1, 0, p, z_a) for n, m in members),
                (tuple(range(len(members))),))
            tn, bm, nt = plan.tn, plan.bm, plan.nt
            k = bp.cell_tiles(nt, tn, q, bm, br)
            cells = plan.slots * (nt // k)
            m_pad = sum(L.m_tiles for L in plan.layers) * bm
        out.append((members, cells, bk.vmem_bytes(q, tn, k, bm, br), m_pad))
    return out


@pytest.mark.parametrize("config,cells", [("qwen2-7b-w2a4", 3013),
                                          ("starcoder2-3b-w4a4", 1596)])
@pytest.mark.parametrize("rows", [4, 8], ids=["decode", "prefill"])
def test_served_step_grid_cells(config, cells, rows):
    """The block picker at every launch of one served step of each
    benchmark configuration (4 decode lanes; 8 in the prefill cell): a
    step takes 3,013 grid cells (qwen2-7b, 53,942 with one-tile cells of
    512 × 256) and 1,596 (starcoder2-3b, 23,112), every launch's blocks
    fit the default scoped VMEM, and no output is padded."""
    from repro.kernels.bitplane_gemv import kernel as bk
    launches = _step_launches(*_bench_config(config), rows)
    assert sum(c for _, c, _, _ in launches) == cells
    assert max(v for _, _, v, _ in launches) <= bk.SCOPED_VMEM
    for members, _, _, m_pad in launches:
        assert m_pad == sum(m for _, m in members)


@pytest.mark.parametrize("config,leaf,fused", [
    ("qwen2-7b-w2a4", 3, 2),        # o, down, lm_head; q/k/v, up/gate
    ("starcoder2-3b-w4a4", 4, 1),   # o, up, down, lm_head; q/k/v
])
def test_served_launches_take_the_one_dot_body(config, leaf, fused):
    """Trace only, nothing executed: one decode step of each benchmark
    configuration at its full layer shapes, through the served path with
    the kernels in interpret mode. Every per-leaf (`bitplane_gemv_codes`)
    and fused (`_run_codes`) launch is built with the one-dot body, and a
    decode block still costs the same launches (the layer scan's body is
    traced once, the lm_head after it). The `GRID_CELLS` counters read
    the picker's cells of one layer and the lm_head."""
    import jax

    import repro.kernels.bitplane_gemv.kernel as leaf_kernel
    from repro.models.model import Model, param_defs
    from repro.serve.quantize import quantize_defs

    bench_run, a = _bench_config(config)
    cfg = bench_run.program_config(a)
    lin = EngineLinear(MVDRAMEngine(), backend=backends.PALLAS_INTERPRET)
    model = Model(cfg, act_bits=a["act_bits"], impl=lin)
    params = quantize_defs(param_defs(cfg), a["weight_bits"])
    lanes, max_seq = 4, 640
    cache = jax.eval_shape(lambda: model.init_cache(lanes, max_seq))
    inp = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    bp.bitplane_gemv_codes.clear_cache()    # both wrappers trace afresh
    bp_prog._run_codes_jit.clear_cache()
    counters = (leaf_kernel.LAUNCHES, leaf_kernel.ONE_DOT_LAUNCHES,
                bp_prog.LAUNCHES, bp_prog.ONE_DOT_LAUNCHES,
                leaf_kernel.GRID_CELLS + bp_prog.GRID_CELLS)
    jax.make_jaxpr(model.decode_step)(params, cache, inp, pos)
    after = (leaf_kernel.LAUNCHES, leaf_kernel.ONE_DOT_LAUNCHES,
             bp_prog.LAUNCHES, bp_prog.ONE_DOT_LAUNCHES,
             leaf_kernel.GRID_CELLS + bp_prog.GRID_CELLS)
    launches = _step_launches(bench_run, a, lanes)
    per_layer = len(launches) // a["layers"]
    cells = sum(c for _, c, _, _ in launches[:per_layer] + launches[-1:])
    assert tuple(x - y for x, y in zip(after, counters)) == (
        leaf, leaf, fused, fused, cells)
    # the benchmark's launches per step, which its kernel metrics divide by
    counts = bench_run.counts
    step = counts.kernel_calls(a, a["weight_bits"], a["act_bits"], lanes)
    assert len(step) == a["layers"] * (leaf - 1 + fused) + 1
