"""Pallas TPU kernels for bit-plane GeMV.

TPU adaptation of the paper's §VI horizontal layout:

  * DRAM bitlines → the 128-lane dimension: a (tn, bm) weight-bit tile is
    MAC'd for all bm outputs at once, the analogue of qM-column parallelism.
  * Bits stay PACKED in HBM (uint32 words carry 32 reduction-dim bits) and
    are expanded only inside VMEM — HBM traffic is q/16 of a bf16 matrix,
    which is exactly the resource the paper saves in DRAM capacity.
  * MAJ-based AND/adder trees → MXU dot products against 0/1 planes with
    power-of-two plane weights folded in f32/int32 accumulators.
  * The paper's processor-side zero-point correction (§II-C2) is the kernel
    epilogue, computed per reduction tile so per-group scales stay local.

Bit-serial fidelity levels (the §V-D linearity collapse): the mathematics
    Σ_k Σ_i 2^(k+i) · (a^(k) · W^(i))  =  (Σ_k 2^k a^(k)) · (Σ_i 2^i W^(i))
                                       =  a_codes · w_codes
holds for both operands, so the q·p plane dots of a tile collapse into ONE
integer dot of the activation codes against the weight codes — exact
integer arithmetic on both sides, so the results are identical, not
approximations. `fidelity="code"` (default) folds the q unpacked weight
planes into one code tile in int32 (`w = Σ_i bit_i << i`) and issues one
dot per tile; `fidelity="bitserial"` retains the fully decomposed q·p-dot
schedule — the command-for-command analogue of what the DRAM executes — as
the tested-equal oracle. `dots_per_tile` gives the issue count.

Zero points, centred (code fidelity): with `ac = a_codes − z_a`,

    Σ_j (a_j − z_a)(w_j − z_w)  =  ac · w  −  z_w · Σ_j ac_j

which is the same integer as the bit-serial epilogue's
`acc − z_a·col_sum − z_w·Σa + tn·z_a·z_w`, without a column-sum reduction
of the weights. Reduction rows padded with codes at z_a centre to 0 and
meet zero weight bits, so they add nothing and no `tn·z_a·z_w` term is
left.

Exactness bound. The dot runs bf16×bf16 with f32 accumulation (Mosaic has
no int32 matmul). |ac| ≤ 255 and w ≤ 255 are exact in bf16 and every
product in f32; the sum is exact while every partial sum stays below 2^24,
i.e. while `tn · max(z_a, 2^p−1−z_a) · (2^q−1) < 2^24` (`one_dot_exact`:
static in q, p, z_a and the compute tile tn). Past it the code path keeps
one dot per weight plane (each partial sum ≤ tn·255) and sums the planes
in int32.

Grid cells and compute tiles. An integer launch's grid cell moves a
(bn, bm) block of planes; its body walks the block in compute tiles of tn
reduction rows (`lax.fori_loop`), one `tile_corr` a tile, and adds each
tile's `corr · scale` into the output in ascending order. The tile, not
the cell, sets every f32 operation and its order, so a launch's outputs
do not depend on bn or bm. Each cell pays a fixed cost on the chip, so
the picker (`ops._pick_blocks`) makes the cell as large as VMEM allows
while the tile stays 512 rows wide; the float kernel's cell is one tile
(bn = tn). Both kernels accumulate across the
reduction grid axis into the output block (grid = (row_tiles, m_tiles,
n_cells), out indexed by (row, m) — revisited blocks persist in VMEM,
initialized at n==0). The activation-row axis is tiled (`row_block`) so
a prefill's B·S rows never have to fit VMEM at once. The per-tile scales
arrive as a (T, 1, M) array; a cell's (k, 1, bm) slab holds one row per
compute tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: per-leaf pallas_call constructions (trace-time) — the contrast counter
#: for the fused program path's one-launch-per-block assertion.
LAUNCHES = 0
#: of those, the launches built with the one-dot code body (trace-time)
ONE_DOT_LAUNCHES = 0
#: the grid cells of those launches, summed over their grids (trace-time)
GRID_CELLS = 0

#: the v5e's default scoped VMEM; a launch whose blocks need more raises
#: its `vmem_limit_bytes` to what they need (`compiler_params`)
SCOPED_VMEM = 16 << 20
#: VMEM bytes a weight of one compute tile takes in the body's temporaries
#: (the unpacked int32 codes, their bf16 copy and the unpacking's steps);
#: each weight plane adds 2 more
TILE_TEMP_BYTES = 16


#: activation rows per grid step: decode batches fit one block, prefill
#: chunks tile (a multiple of the int8 sublane tile, 32)
ROW_BLOCK = 256


def _unpack_words(words: jax.Array, bn: int) -> jax.Array:
    """(W, bm) uint32 → (W*32, bm) {0,1} int8; bit j of word w = row w*32+j."""
    w, bm = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = (words[:, None, :] >> shifts) & jnp.uint32(1)
    return bits.reshape(w * 32, bm)[:bn].astype(jnp.int8)


#: field width f → the (shift, mask) steps that spread the low 32/f bits of
#: a word to every f-th bit (the classic bit-interleave)
_SPREAD = {
    2: ((8, 0x00FF00FF), (4, 0x0F0F0F0F), (2, 0x33333333), (1, 0x55555555)),
    4: ((12, 0x000F000F), (6, 0x03030303), (3, 0x11111111)),
    8: ((14, 0x00030003), (7, 0x01010101)),
}


def _unpack_codes(words: list, bn: int) -> jax.Array:
    """q planes of (W, bm) uint32 → (bn, bm) int32 weight codes
    Σ_i bit_i << i, in [0, 2^q − 1].

    The planes are combined on whole words first: the 32 rows of a word
    split into f chunks of 32/f rows (f = 2, 4 or 8, the least ≥ q); each
    plane's chunk is spread to one f-bit field a row, plane i at bit i, and
    the planes OR together. Each element then costs one shift and one mask
    whatever q is; the combining runs on arrays 1/32 the tile's size."""
    q = len(words)
    w, bm = words[0].shape
    f = 2 if q <= 2 else 4 if q <= 4 else 8
    per = 32 // f
    fields = (jnp.arange(per, dtype=jnp.uint32) * f)[None, :, None]
    chunks = []
    for h in range(f):
        comb = None
        for i, x in enumerate(words):
            c = (x >> (h * per)) & jnp.uint32((1 << per) - 1)
            for s, m in _SPREAD[f]:
                c = (c | (c << s)) & jnp.uint32(m)
            comb = c if comb is None else comb | (c << i)
        chunks.append((comb[:, None, :] >> fields) & jnp.uint32((1 << f) - 1))
    codes = jnp.stack(chunks, axis=1)                 # (W, f, 32/f, bm)
    return codes.reshape(w * 32, bm)[:bn].astype(jnp.int32)


def row_block(rows: int) -> int:
    """Rows per grid step: all of them up to ROW_BLOCK, else ROW_BLOCK."""
    return min(rows, ROW_BLOCK)


def _pad_axis(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def code_dot(a: jax.Array, w: jax.Array) -> jax.Array:
    """Exact int32 (rows, bn)·(bn, bm) of small integers (|a|, |w| ≤ 255,
    partial sums below 2^24), as a bf16 MXU dot with f32 accumulation."""
    d = jax.lax.dot(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    return d.astype(jnp.int32)


def activation_bits(a_codes: jax.Array, p: int) -> list:
    """The p activation bit planes as int8 (widened to int32 first: Mosaic
    cannot shift uint8)."""
    a = a_codes.astype(jnp.int32)
    return [((a >> k) & 1).astype(jnp.int8) for k in range(p)]


def one_dot_exact(q: int, p: int, z_a: int, tn: int) -> bool:
    """Whether one centred dot of (tn,) activation codes against q-bit
    weight codes keeps every f32 partial sum below 2^24 (exact)."""
    return tn * max(z_a, (1 << p) - 1 - z_a) * ((1 << q) - 1) < 1 << 24


def dots_per_tile(q: int, p: int, fidelity: str = "code", *, tn: int,
                  z_a: int) -> int:
    """MXU dot issues per compute tile of tn reduction rows — the §V-D
    collapse on both operands, measurable: 1 within the exactness bound,
    else q; q·p for the bit-serial oracle."""
    if fidelity != "code":
        return q * p
    return 1 if one_dot_exact(q, p, z_a, tn) else q


def tile_corr(a_codes, words: list, *, q: int, p: int, z_a, z_w, tn: int,
              fidelity: str, one_dot: bool) -> jax.Array:
    """The integer core of one (rows, tn) × (tn, bm) compute tile, shared
    by the per-leaf and fused kernels: Σ_j (a_j − z_a)(w_j − z_w) as int32.

    `words` holds the tile's q packed planes, (tn//32, bm) uint32 each;
    `z_a`/`z_w` are Python ints or traced int32 scalars; `one_dot` is the
    caller's static `one_dot_exact` (code fidelity only)."""
    if fidelity == "code":
        ac = a_codes.astype(jnp.int32) - z_a          # exact in bf16
        if one_dot:
            acc = code_dot(ac, _unpack_codes(words, tn))
        else:
            acc = code_dot(ac, _unpack_words(words[0], tn))
            for i in range(1, q):
                acc += (1 << i) * code_dot(ac, _unpack_words(words[i], tn))
        return acc - z_w * jnp.sum(ac, axis=-1, keepdims=True)
    # "bitserial": both operands decomposed, a^(k) AND W^(i) popcount-
    # accumulated as int MXU matmuls, the zero points corrected after
    planes = [_unpack_words(words[i], tn) for i in range(q)]
    col_sum = jnp.zeros((1, planes[0].shape[1]), jnp.int32)  # Σ_j w[j, m]
    for i in range(q):
        col_sum += (1 << i) * jnp.sum(planes[i].astype(jnp.int32), axis=0,
                                      keepdims=True)
    a_bits = activation_bits(a_codes, p)
    acc = jnp.zeros((a_codes.shape[0], planes[0].shape[1]), jnp.int32)
    for i in range(q):
        for k in range(p):
            acc += (1 << (i + k)) * jax.lax.dot(
                a_bits[k], planes[i], preferred_element_type=jnp.int32)
    sum_a = jnp.sum(a_codes.astype(jnp.int32), axis=-1, keepdims=True)
    return acc - z_a * col_sum - z_w * sum_a + tn * z_a * z_w


def vmem_bytes(q: int, tn: int, k: int, bm: int, br: int) -> int:
    """Scoped VMEM of an integer launch whose cell walks k compute tiles:
    the double-buffered planes (q, k·tn/32, bm), codes (br, k·tn), scale
    (k, 1, bm) and output (br, bm) blocks at their (8 or 32, 128) memory
    tiles, plus one tile's body temporaries (`TILE_TEMP_BYTES` a weight)."""
    def up(x, t):
        return -(-x // t) * t
    blocks = (q * up(k * tn // 32, 8) * bm * 4 + up(br, 32) * up(k * tn, 128)
              + k * 8 * bm * 4 + up(br, 8) * bm * 4)
    return 2 * blocks + (TILE_TEMP_BYTES + 2 * q) * tn * bm


def compiler_params(vmem: int):
    """Mosaic parameters of a launch, grid (rows, m, n) with the reduction
    innermost; the scoped VMEM limit is raised past the default only where
    the blocks (`vmem_bytes`) need more."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem if vmem > SCOPED_VMEM else None)


# ---------------------------------------------------------------------------
# float-activation kernel:  out[b, m] = Σ_g scale[g, m]·(Σ_i 2^i a_g·W_g^(i)
#                                                        − z_w·Σ a_g)
# ---------------------------------------------------------------------------

def _gemv_f_kernel(a_ref, planes_ref, scale_ref, out_ref, *, q: int,
                   zero: int, bn: int):
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_blk = a_ref[...].astype(jnp.float32)              # (B, bn)
    # hoisted: every plane expanded exactly once, before the MAC loop
    planes = [_unpack_words(planes_ref[i], bn).astype(jnp.float32)
              for i in range(q)]                         # q ≤ 8: unrolled
    acc = jnp.zeros((a_blk.shape[0], out_ref.shape[1]), jnp.float32)
    for i in range(q):
        acc += (2.0 ** i) * jax.lax.dot(
            a_blk, planes[i], precision=jax.lax.Precision.HIGHEST)
    corr = acc - zero * jnp.sum(a_blk, axis=-1, keepdims=True)
    out_ref[...] += corr * scale_ref[0]                  # (1, bm) broadcast


def gemv_f_pallas(a, planes, scale_tiles, *, q: int, zero: int,
                  bn: int, bm: int, interpret: bool = False):
    """a (B, N) float; planes (q, N//32, M) uint32; scale_tiles (N//bn, M).

    N must divide by bn (pad upstream: a with 0), M by bm.
    """
    b = a.shape[0]
    br = row_block(b)
    a = _pad_axis(a, br, 0)
    out = _leaf_call(
        functools.partial(_gemv_f_kernel, q=q, zero=zero, bn=bn),
        a, planes, scale_tiles, q=q, tn=bn, bn=bn, bm=bm, br=br,
        interpret=interpret)
    return out[:b]


def _leaf_call(body, a, planes, scale_tiles, *, q: int, tn: int, bn: int,
               bm: int, br: int, interpret: bool):
    """The per-leaf launch shared by both kernels: grid (row, m, n) cells
    of (bn, bm), each walking bn/tn compute tiles, accumulating over the
    innermost reduction axis."""
    rows, n = a.shape
    m = planes.shape[-1]
    k = bn // tn    # compute tiles per cell: one scale row each
    return pl.pallas_call(
        body,
        grid=(rows // br, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((br, bn), lambda ri, mi, ni: (ri, ni)),
            pl.BlockSpec((q, bn // 32, bm), lambda ri, mi, ni: (0, ni, mi)),
            pl.BlockSpec((k, 1, bm), lambda ri, mi, ni: (ni, 0, mi)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda ri, mi, ni: (ri, mi)),
        out_shape=jax.ShapeDtypeStruct((rows, m), jnp.float32),
        compiler_params=compiler_params(vmem_bytes(q, tn, k, bm, br)),
        interpret=interpret,
    )(a, planes, scale_tiles.reshape(scale_tiles.shape[0], 1, m))


# ---------------------------------------------------------------------------
# integer kernel: activation codes × packed weight planes, exact int32 per
# tile (`tile_corr`), scaled into the f32 output tile by tile.
# ---------------------------------------------------------------------------

def _gemv_bs_kernel(a_ref, planes_ref, scale_ref, out_ref, *, q: int, p: int,
                    z_a: int, z_w: int, tn: int, fidelity: str,
                    one_dot: bool):
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wt = tn // 32

    def tile(t, acc):
        a = a_ref[:, pl.ds(pl.multiple_of(t * tn, tn), tn)]
        w = pl.multiple_of(t * wt, wt)
        corr = tile_corr(a, [planes_ref[i, pl.ds(w, wt), :]
                             for i in range(q)],
                         q=q, p=p, z_a=z_a, z_w=z_w, tn=tn,
                         fidelity=fidelity, one_dot=one_dot)
        return acc + corr.astype(jnp.float32) * scale_ref[t]

    out_ref[...] = jax.lax.fori_loop(0, scale_ref.shape[0], tile,
                                     out_ref[...])


def gemv_bs_pallas(a_codes, planes, scale_tiles, *, q: int, p: int,
                   z_a: int, z_w: int, tn: int, bn: int, bm: int,
                   fidelity: str = "code", interpret: bool = False):
    """a_codes (B, N) uint8 (pad with z_a); planes (q, N//32, M) uint32;
    scale_tiles (N//tn, M). Cells of (bn, bm), compute tiles of tn rows;
    N must divide by bn, M by bm."""
    global LAUNCHES, ONE_DOT_LAUNCHES, GRID_CELLS
    if fidelity not in ("code", "bitserial"):
        raise ValueError(
            f"fidelity must be 'code' or 'bitserial', got {fidelity!r} "
            f"(a_codes shape {tuple(a_codes.shape)})")
    one_dot = fidelity == "code" and one_dot_exact(q, p, z_a, tn)
    b = a_codes.shape[0]
    br = row_block(b)
    a_codes = _pad_axis(a_codes, br, 0, value=z_a)
    out = _leaf_call(
        functools.partial(_gemv_bs_kernel, q=q, p=p, z_a=z_a, z_w=z_w,
                          tn=tn, fidelity=fidelity, one_dot=one_dot),
        a_codes, planes, scale_tiles, q=q, tn=tn, bn=bn, bm=bm, br=br,
        interpret=interpret)
    LAUNCHES += 1
    ONE_DOT_LAUNCHES += one_dot
    GRID_CELLS += (a_codes.shape[0] // br) * (planes.shape[-1] // bm) * (
        a_codes.shape[1] // bn)
    return out[:b]
