"""Fused whole-block Pallas decode kernel — the kernel-side twin of
`core.engine.GemvProgram`.

The simulator has executed the fused cross-layer wave schedule since PR 5,
but the jit path still dispatched every decode-time linear as its own
`bitplane_gemv_codes` launch. This module walks the SAME program structure
in ONE `pallas_call`: a 2-D grid over (m-slot, reduction cell) where the
m-slots enumerate every layer's output tiles in the program's concurrency-
group order — q/k/v (and up/gate) interleave on consecutive slots exactly
the way their tiles share boundary waves in the simulator's schedule. A
cell walks k compute tiles in ascending order (`lax.fori_loop`), as the
per-leaf kernel does; k is picked at launch from the rows
(`ops.cell_tiles`), and a slot's BM is one output block that divides
every member's m (`ops.output_block`), so no member pads past its
128-column tile.

Why one launch is legal across heterogeneous layers: each layer keeps ITS
OWN compute tile tn_l from `_pick_blocks`, and tiles are padded up to
the program-wide (TN, BM) envelope with *exactness-preserving* values —

  * weight planes pad with 0 bits,
  * activation codes pad with the layer's zero point z_a,

so the padded rows cancel algebraically. The code body centres the codes
(`ac = a − z_a`, see kernel.py): a padded row is 0 against zero weight
bits, so it adds nothing to `ac · w` or to `z_w · Σ ac`. The bit-serial
oracle keeps the uncentred epilogue with its `+ TN·z_a·z_w` term at the
padded width TN: the extra `−z_w·(TN−tn)·z_a` from `sum_a` is exactly
offset by the extra `+(TN−tn)·z_a·z_w`, and the extra plane rows leave
`acc` and `col_sum` untouched. Every operation is integer-exact, so the
fused kernel is integer-identical (not just close) to the per-leaf path.
Fully-padded tiles (a layer with fewer reduction tiles than the
envelope) carry zero scales, so whatever their finite integer correction
is, they contribute exactly 0.0. Mixed weight/activation precisions ride
the same trick: the weight codes are built from the envelope's q_max
planes, zero-padded above a layer's q, and the bitserial path's code loop
runs to p_max — codes < 2^p_l have zero high bits, so the extra dots are
exact zeros.

One dot per tile: the code body folds the q_max planes into one weight
code tile and issues one dot when `one_dot_exact` holds for the envelope
(TN, q_max and the widest centred code of any member, all static in the
plan — `ProgramKernelPlan.one_dot`); otherwise one dot per plane.

Codes are stored once per LAYER, not per slot: a scalar-prefetched table
maps each m-slot to its layer (and that layer's zero points, read from
SMEM), and the codes BlockSpec follows it. The activation-row axis is the
outermost grid dimension, tiled like the per-leaf kernels (`row_block`).

`LAUNCHES` counts `pallas_call` constructions at trace time — the parity
test asserts the whole decode block costs ONE launch on this path —
`ONE_DOT_LAUNCHES` those built with the one-dot body, and `GRID_CELLS`
their grid cells.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.quant import QuantSpec, quantize_activations
from . import ops as bp_ops
from .kernel import (_pad_axis, compiler_params, one_dot_exact, row_block,
                     tile_corr, vmem_bytes)

#: pallas_call constructions on the fused program path (trace-time; jit
#: caching means one launch per distinct block shape, asserted in tests).
LAUNCHES = 0
#: of those, the launches built with the one-dot code body (trace-time)
ONE_DOT_LAUNCHES = 0
#: the grid cells of those launches, summed over their grids (trace-time)
GRID_CELLS = 0


def static_zero(spec: QuantSpec) -> int:
    """The static zero point `quantize_activations` will bake into codes."""
    return spec.zero_point if spec.symmetric else spec.levels // 2


@dataclasses.dataclass(frozen=True)
class LayerTiles:
    """Static per-layer tiling of one program member (all ints, hashable)."""

    n: int          # reduction dim
    m: int          # output dim
    q: int          # weight bits
    g: int          # weight scale groups
    z_w: int        # weight zero point
    p: int          # activation bits
    z_a: int        # activation zero point
    tn: int         # this layer's own compute tile (reduction rows)
    n_tiles: int
    m_tiles: int    # output blocks of the plan's BM


@dataclasses.dataclass(frozen=True)
class ProgramKernelPlan:
    """The fused launch's static geometry — a pure function of layer shapes
    and the program's concurrency groups, hashable so it can be a jit
    static argument."""

    layers: tuple                # LayerTiles per program layer
    groups: tuple                # concurrency groups, indices into layers
    slot_layer: tuple            # (S,) layer index per m-slot
    slot_mtile: tuple            # (S,) that layer's m-tile index
    tn: int                      # padded compute-tile envelope TN
    bm: int                      # output block BM, dividing every m
    nt: int                      # compute tiles NT along the reduction
    q_max: int
    p_max: int

    @property
    def slots(self) -> int:
        return len(self.slot_layer)

    @property
    def one_dot(self) -> bool:
        """Whether the code body's one dot per tile is exact for every
        member at the envelope (TN, q_max)."""
        return all(one_dot_exact(self.q_max, L.p, L.z_a, self.tn)
                   for L in self.layers)


@functools.lru_cache(maxsize=512)
def build_plan(metas: tuple, groups: Optional[tuple] = None
               ) -> ProgramKernelPlan:
    """metas: tuple of (n, m, q, g, z_w, p, z_a) per layer. Slots walk the
    concurrency groups in order, round-robin across each group's members —
    the kernel-grid mirror of the schedule's shared boundary waves."""
    bm = bp_ops.output_block(tuple(meta[1] for meta in metas))
    layers = []
    for n, m, q, g, z_w, p, z_a in metas:
        tn = bp_ops._pick_blocks(n, m, None, None,
                                 n // g if g > 1 else None)[0]
        layers.append(LayerTiles(
            n=n, m=m, q=q, g=g, z_w=z_w, p=p, z_a=z_a, tn=tn,
            n_tiles=-(-n // tn), m_tiles=-(-m // bm)))
    if groups is None:
        groups = tuple((i,) for i in range(len(layers)))
    slot_layer, slot_mtile = [], []
    for grp in groups:
        for r in range(max(layers[l].m_tiles for l in grp)):
            for l in grp:
                if r < layers[l].m_tiles:
                    slot_layer.append(l)
                    slot_mtile.append(r)
    return ProgramKernelPlan(
        layers=tuple(layers), groups=tuple(tuple(g) for g in groups),
        slot_layer=tuple(slot_layer), slot_mtile=tuple(slot_mtile),
        tn=max(L.tn for L in layers), bm=bm,
        nt=max(L.n_tiles for L in layers),
        q_max=max(L.q for L in layers), p_max=max(L.p for L in layers))


def plan_from_weights(ws: Sequence, a_spec: QuantSpec,
                      groups: Optional[tuple] = None) -> ProgramKernelPlan:
    """Plan for a group of `BitplaneWeights` sharing one activation spec."""
    z_a = static_zero(a_spec)
    metas = tuple((bw.n, bw.m, bw.bits, bw.scale.shape[0], bw.zero,
                   a_spec.bits, z_a) for bw in ws)
    return build_plan(metas, groups)


# ---------------------------------------------------------------------------
# slot-major packing: every (slot, tile) gets a fixed-size block so all
# BlockSpec index maps stay static (TPU- and interpret-safe)
# ---------------------------------------------------------------------------

def pack_weights(plan: ProgramKernelPlan, leaves: Sequence):
    """leaves[l]: BitplaneWeights → planes_t (S, NT, q_max, TN//32, BM)
    uint32 and scale_t (S, NT, 1, BM) f32. Pad bits/scales are zero; scale
    rows past a layer's true reduction length are zeroed by
    `_expand_scales`, so padded tiles contribute nothing."""
    wb, bm = plan.tn // 32, plan.bm
    per_layer = []
    for L, bw in zip(plan.layers, leaves):
        wl = L.tn // 32
        planes = bp_ops._pad_axis(bw.planes, wl, 1)
        planes = bp_ops._pad_axis(planes, bm, 2)
        scale = bp_ops._pad_axis(
            bp_ops._expand_scales(bw, L.tn, L.n_tiles * L.tn), bm, 1)
        per_layer.append((planes, scale, wl))
    p_rows, s_rows = [], []
    zero_p = jnp.zeros((plan.q_max, wb, bm), jnp.uint32)
    zero_s = jnp.zeros((1, bm), jnp.float32)
    for l, r in zip(plan.slot_layer, plan.slot_mtile):
        L = plan.layers[l]
        planes, scale, wl = per_layer[l]
        p_tiles, s_tiles = [], []
        for nt in range(plan.nt):
            if nt < L.n_tiles:
                blk = planes[:, nt * wl:(nt + 1) * wl, r * bm:(r + 1) * bm]
                blk = jnp.pad(blk, ((0, plan.q_max - L.q), (0, wb - wl),
                                    (0, 0)))
                srow = scale[nt, r * bm:(r + 1) * bm][None, :]
            else:
                blk, srow = zero_p, zero_s
            p_tiles.append(blk)
            s_tiles.append(srow)
        p_rows.append(jnp.stack(p_tiles))
        s_rows.append(jnp.stack(s_tiles))
    return jnp.stack(p_rows), jnp.stack(s_rows)


def pack_codes(plan: ProgramKernelPlan, codes: Sequence[jax.Array],
               br: int):
    """codes[l]: (B, n_l) uint8 → (L, NT, R, TN) with R = B padded to a
    multiple of the row block `br`; padded with each layer's z_a inside its
    live tiles and with 0 on fully-padded tiles."""
    per_layer = []
    for L, c in zip(plan.layers, codes):
        c = _pad_axis(_pad_axis(c, L.tn, 1, value=L.z_a), br, 0)
        tiles = [
            jnp.pad(c[:, nt * L.tn:(nt + 1) * L.tn],
                    ((0, 0), (0, plan.tn - L.tn)),
                    constant_values=L.z_a)
            if nt < L.n_tiles else
            jnp.zeros((c.shape[0], plan.tn), jnp.uint8)
            for nt in range(plan.nt)]
        per_layer.append(jnp.stack(tiles))       # (NT, R, BN)
    return jnp.stack(per_layer)


@functools.lru_cache(maxsize=512)
def pack_params(plan: ProgramKernelPlan) -> np.ndarray:
    """(3·S,) int32 [layer, z_a, z_w] per m-slot — the static scalar-
    prefetch table (SMEM) the codes BlockSpec and the epilogue read."""
    out = np.zeros((plan.slots, 3), np.int32)
    for s, l in enumerate(plan.slot_layer):
        L = plan.layers[l]
        out[s] = (l, L.z_a, L.z_w)
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# the fused kernel body — one grid cell per (m-slot, reduction cell), k
# compute tiles a cell
# ---------------------------------------------------------------------------

def _program_kernel(params_ref, codes_ref, planes_ref, scale_ref, out_ref,
                    *, q_max: int, p_max: int, tn: int, fidelity: str,
                    one_dot: bool):
    slot = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z_a, z_w = params_ref[3 * slot + 1], params_ref[3 * slot + 2]

    # planes of layers with q < q_max are zero-padded; tn is the PADDED
    # envelope TN — see the module docstring for why both stay exact
    def tile(t, acc):
        corr = tile_corr(codes_ref[0, t],
                         [planes_ref[0, t, i] for i in range(q_max)],
                         q=q_max, p=p_max, z_a=z_a, z_w=z_w, tn=tn,
                         fidelity=fidelity, one_dot=one_dot)
        return acc + corr.astype(jnp.float32) * scale_ref[0, t]

    out_ref[0] = jax.lax.fori_loop(0, codes_ref.shape[1], tile, out_ref[0])


def program_gemv(plan: ProgramKernelPlan, codes_t, planes_t, scale_t,
                 params_t, *, fidelity: str = "code",
                 interpret: bool = False) -> jax.Array:
    """ONE pallas_call for the whole decode block → (S, R, BM) f32
    un-activation-scaled outputs, gathered per layer by `gather_outputs`.
    codes_t is (L, NT, R, TN) from `pack_codes`; params_t the `pack_params`
    table, scalar-prefetched into SMEM. A grid cell walks k of the NT
    compute tiles (`ops.cell_tiles`, from the rows)."""
    global LAUNCHES, ONE_DOT_LAUNCHES, GRID_CELLS
    if fidelity not in ("code", "bitserial"):
        raise ValueError(
            f"fidelity must be 'code' or 'bitserial', got {fidelity!r}")
    one_dot = fidelity == "code" and plan.one_dot
    _l, nt, rows, tn = codes_t.shape
    br = row_block(rows)   # rows is already a multiple of it (pack_codes)
    s, bm, q = plan.slots, plan.bm, plan.q_max
    k = bp_ops.cell_tiles(nt, tn, q, bm, br)
    grid = (rows // br, s, nt // k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k, br, tn),
                         lambda ri, si, ni, tbl: (tbl[3 * si], ni, ri, 0)),
            pl.BlockSpec((1, k, q, tn // 32, bm),
                         lambda ri, si, ni, tbl: (si, ni, 0, 0, 0)),
            pl.BlockSpec((1, k, 1, bm),
                         lambda ri, si, ni, tbl: (si, ni, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, br, bm),
                               lambda ri, si, ni, tbl: (si, ri, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_program_kernel, q_max=q, p_max=plan.p_max, tn=tn,
                          fidelity=fidelity, one_dot=one_dot),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, rows, bm), jnp.float32),
        compiler_params=compiler_params(vmem_bytes(q, tn, k, bm, br)),
        interpret=interpret,
    )(params_t, codes_t, planes_t, scale_t)
    LAUNCHES += 1
    ONE_DOT_LAUNCHES += one_dot
    GRID_CELLS += grid[0] * grid[1] * grid[2]
    return out


def gather_outputs(plan: ProgramKernelPlan, out: jax.Array, b: int) -> list:
    """(S, R, BM) slot outputs → per-layer (b, m_l), un-activation-scaled.
    Slot n-tiles were visited in ascending order per slot, so each layer's
    accumulation order matches the per-leaf kernel's — f32 sums included."""
    slot_of = {(l, r): s for s, (l, r)
               in enumerate(zip(plan.slot_layer, plan.slot_mtile))}
    outs = []
    for l, L in enumerate(plan.layers):
        parts = [out[slot_of[(l, r)], :b] for r in range(L.m_tiles)]
        outs.append(jnp.concatenate(parts, axis=-1)[:, :L.m])
    return outs


# ---------------------------------------------------------------------------
# jitted whole-block entry points
# ---------------------------------------------------------------------------

def _run_codes(plan: ProgramKernelPlan, planes_t, scale_t, stacked_codes,
               stacked_scales, *, layout, fidelity: str, interpret: bool):
    """Integer core + epilogue: slice each layer's codes out of its
    quantization bucket, pack, launch once, gather, and apply the
    activation scale. `layout[l] = (bucket, row_start, b)` is static.

    The scale multiply lives INSIDE the jit on purpose: the scale itself
    arrives as an input (computed eagerly — see `_quantize_batched`), and
    a lone elementwise f32 multiply has no reassociation freedom, so XLA
    fusion cannot move it off the per-leaf oracle's bit pattern. What must
    NOT move inside the trace is the absmax/divide chain that *produces*
    the scale."""
    codes = tuple(stacked_codes[bi][s:s + b] for bi, s, b in layout)
    b = codes[0].shape[0]
    codes_t = pack_codes(plan, codes, row_block(b))
    params_t = jnp.asarray(pack_params(plan))
    out = program_gemv(plan, codes_t, planes_t, scale_t, params_t,
                       fidelity=fidelity, interpret=interpret)
    outs = gather_outputs(plan, out, b)
    return tuple(o * stacked_scales[bi][s:s + b]
                 for o, (bi, s, b) in zip(outs, layout))


_STATIC = ("plan", "layout", "fidelity", "interpret")
_run_codes_jit = jax.jit(_run_codes, static_argnames=_STATIC)
# donating the packed codes helps on accelerators; on CPU jax warns that
# donation is unsupported, so the non-donating variant serves there
_run_codes_jit_donated = jax.jit(_run_codes, static_argnames=_STATIC,
                                 donate_argnums=(3,))


def _quantize_batched(xs: Sequence[jax.Array],
                      specs: Sequence[QuantSpec]) -> tuple:
    """Quantize every layer's activations, batching same-(shape, spec)
    layers into one eager `quantize_activations` call.

    Per-row quantization is rowwise-independent (absmax / scale / codes of
    a row never look at another row), so stacking k same-shape (B, n)
    blocks into one (k·B, n) call yields bitwise-identical values and
    scales per row. This matters because the eager quantize dispatches are
    the dominant per-step host cost of a decode block once the weights are
    pre-packed — a q/k/v + up/gate block collapses from L calls to one or
    two. Layers handing in the SAME array object (fused_group_linears)
    share one quantization outright.

    Returns `(stacked_codes, stacked_scales, layout)`: one codes/scales
    array per bucket plus a static per-layer `(bucket, row_start, b)`
    triple that `_run_codes` uses to slice inside the jit — no per-layer
    eager dispatches at all."""
    buckets: dict = {}
    raw: list = [None] * len(xs)
    for i, (x, spec) in enumerate(zip(xs, specs)):
        key = (tuple(x.shape), spec)
        grp = buckets.setdefault(key, {"xs": [], "ids": {}})
        off = grp["ids"].get(id(x))
        if off is None:
            off = len(grp["xs"])
            grp["ids"][id(x)] = off
            grp["xs"].append(x)
        raw[i] = (key, off * x.shape[0], x.shape[0])
    order = list(buckets)
    codes, scales = [], []
    for key in order:
        (shape, spec), grp = key, buckets[key]["xs"]
        stacked = grp[0] if len(grp) == 1 else jnp.concatenate(grp, axis=0)
        aq = quantize_activations(stacked, spec)
        codes.append(aq.values)
        scales.append(aq.scale)
    layout = tuple((order.index(key), s, b) for key, s, b in raw)
    return tuple(codes), tuple(scales), layout


def run_program(plan: ProgramKernelPlan, leaves: Sequence,
                xs: Sequence[jax.Array], specs: Sequence[QuantSpec], *,
                fidelity: str = "code", interpret: bool = False,
                donate: Optional[bool] = None,
                packed: Optional[tuple] = None) -> tuple:
    """Quantize each layer's (B, n_l) activations, execute the whole block
    as ONE fused Pallas launch, return per-layer (B, m_l) f32 outputs —
    integer-identical to per-leaf `bitplane_gemv_bitserial` calls.

    Quantization deliberately stays OUTSIDE the jitted block, exactly like
    `bitplane_gemv_bitserial`: XLA fusion of the absmax/divide inside a
    jit can move the scale by 1 ulp and flip a code, which would break
    bitwise parity with the per-leaf oracle. Everything downstream of the
    eagerly-computed codes and scales — slicing, code packing, the single
    launch, the gather, the scale multiply — is one jitted (and optionally
    donated) call, so a decode step costs a constant number of host
    dispatches regardless of block depth.

    `packed` is the `(planes_t, scale_t)` pair from `pack_weights` —
    weights are static per program, so callers that run many decode steps
    (e.g. `GemvProgram.run_kernel`) pack them ONCE and the per-step work
    is the activation side only."""
    if donate is None:
        donate = jax.default_backend() not in ("cpu",)
    if packed is None:
        packed = pack_weights(plan, tuple(leaves))
    planes_t, scale_t = packed
    stacked_codes, stacked_scales, layout = _quantize_batched(xs, specs)
    fn = _run_codes_jit_donated if donate else _run_codes_jit
    return fn(plan, planes_t, scale_t, stacked_codes, stacked_scales,
              layout=layout, fidelity=fidelity, interpret=interpret)


def fused_group_linears(x: jax.Array, ws: Sequence, act_bits: int, *,
                        fidelity: str = "code",
                        interpret: bool = False) -> tuple:
    """k independent linears sharing ONE input (q/k/v, up/gate) as one
    launch: the serve-side mirror of the program's concurrency groups. The
    input is quantized once — bit-identical to quantizing per leaf, since
    per-row quantization of the same rows is deterministic."""
    spec = QuantSpec(bits=act_bits)
    plan = plan_from_weights(tuple(ws), spec,
                             groups=(tuple(range(len(ws))),))
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    outs = run_program(plan, tuple(ws), (x2,) * len(ws),
                       (spec,) * len(ws), fidelity=fidelity,
                       interpret=interpret)
    return tuple(o.reshape(*lead, bw.m) for o, bw in zip(outs, ws))
