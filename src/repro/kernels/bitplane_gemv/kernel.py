"""Pallas TPU kernels for bit-plane GeMV.

TPU adaptation of the paper's §VI horizontal layout:

  * DRAM bitlines → the 128-lane dimension: a (bn, bm) weight-bit tile is
    MAC'd for all bm outputs at once, the analogue of qM-column parallelism.
  * Bits stay PACKED in HBM (uint32 words carry 32 reduction-dim bits) and
    are expanded only inside VMEM — HBM traffic is q/16 of a bf16 matrix,
    which is exactly the resource the paper saves in DRAM capacity.
  * MAJ-based AND/adder trees → MXU dot products against 0/1 planes with
    power-of-two plane weights folded in f32/int32 accumulators.
  * The paper's processor-side zero-point correction (§II-C2) is the kernel
    epilogue, computed per reduction tile so per-group scales stay local.

Bit-serial fidelity levels (the §V-D linearity collapse): the mathematics
    Σ_k 2^k · (a^(k) · W^(i))  =  (Σ_k 2^k a^(k)) · W^(i)  =  a_codes · W^(i)
means the p activation-plane dots per weight plane collapse into ONE integer
dot against the raw codes — both sides are exact integer arithmetic, so the
results are identical, not approximations. `fidelity="code"` (default)
issues q dots per tile; `fidelity="bitserial"` retains the fully decomposed
q·p-dot schedule — the command-for-command analogue of what the DRAM
executes — as the tested-equal oracle. `dots_per_tile` exposes the issue
count the benchmark trajectory records.

Shared structure: `_unpack_words` expansion of every weight plane is hoisted
out of the (i, k) accumulation loops — each plane is unpacked exactly once
per tile regardless of fidelity. Both kernels accumulate across the
reduction grid axis into the output block (grid = (row_tiles, m_tiles,
n_tiles), out indexed by (row, m) — revisited blocks persist in VMEM,
initialized at n==0). The activation-row axis is tiled (`row_block`) so a
prefill's B·S rows never have to fit VMEM at once.

Mosaic constraints the layout follows: the per-tile scales arrive as a
(T, 1, M) array so their (1, bm) block spans the full second-minor dim, and
the "code" dot runs bf16×bf16 with f32 accumulation — Mosaic has no int32
matmul. That dot stays exact: codes ≤ 255 and 0/1 planes are exact in bf16,
and a tile's partial sum ≤ bn·255 < 2^24 is exact in f32; plane sums then
accumulate in int32.
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


#: activation rows per grid step: decode batches fit one block, prefill
#: chunks tile (a multiple of the int8 sublane tile, 32)
ROW_BLOCK = 256


def _unpack_words(words: jax.Array, bn: int) -> jax.Array:
    """(W, bm) uint32 → (W*32, bm) {0,1} int8; bit j of word w = row w*32+j."""
    w, bm = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = (words[:, None, :] >> shifts) & jnp.uint32(1)
    return bits.reshape(w * 32, bm)[:bn].astype(jnp.int8)


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


def code_dot(a_codes: jax.Array, plane: jax.Array) -> jax.Array:
    """Exact integer (rows, bn)·(bn, bm) of codes against a 0/1 plane, as
    a bf16 MXU dot with f32 accumulation (see the module docstring)."""
    # Mosaic casts uint8 to a float only by way of int32
    a = a_codes.astype(jnp.int32).astype(jnp.bfloat16)
    d = jax.lax.dot(a, plane, preferred_element_type=jnp.float32)
    return d.astype(jnp.int32)


def activation_bits(a_codes: jax.Array, p: int) -> list:
    """The p activation bit planes as int8 (widened to int32 first: Mosaic
    cannot shift uint8)."""
    a = a_codes.astype(jnp.int32)
    return [((a >> k) & 1).astype(jnp.int8) for k in range(p)]


def dots_per_tile(q: int, p: int, fidelity: str = "code") -> int:
    """MXU dot issues per (m, n) grid cell — the §V-D collapse, measurable."""
    return q if fidelity == "code" else q * p


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
        a, planes, scale_tiles, q=q, bn=bn, bm=bm, br=br,
        interpret=interpret)
    return out[:b]


def _leaf_call(body, a, planes, scale_tiles, *, q: int, bn: int, bm: int,
               br: int, interpret: bool):
    """The per-leaf launch shared by both kernels: grid (row, m, n) tiles,
    accumulating over the innermost reduction axis."""
    rows, n = a.shape
    m = planes.shape[-1]
    wpb = bn // 32  # packed words per reduction block
    return pl.pallas_call(
        body,
        grid=(rows // br, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((br, bn), lambda ri, mi, ni: (ri, ni)),
            pl.BlockSpec((q, wpb, bm), lambda ri, mi, ni: (0, ni, mi)),
            pl.BlockSpec((1, 1, bm), lambda ri, mi, ni: (ni, 0, mi)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda ri, mi, ni: (ri, mi)),
        out_shape=jax.ShapeDtypeStruct((rows, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, planes, scale_tiles.reshape(scale_tiles.shape[0], 1, m))


# ---------------------------------------------------------------------------
# bit-serial kernel: both operands decomposed to planes — the exact integer
# computation MVDRAM performs in DRAM (AND + weighted popcount-accumulate).
# fidelity="code" collapses the activation planes back into codes (§V-D
# linearity): q int dots per tile instead of q·p, identical integers.
# ---------------------------------------------------------------------------

def _gemv_bs_kernel(a_ref, planes_ref, scale_ref, out_ref, *, q: int, p: int,
                    z_a: int, z_w: int, bn: int, fidelity: str):
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_codes = a_ref[...]                                  # (B, bn) uint8 codes
    b = a_codes.shape[0]
    bm = out_ref.shape[1]
    # hoisted out of the (i, k) loops: each weight plane unpacked ONCE
    planes = [_unpack_words(planes_ref[i], bn) for i in range(q)]
    col_sum = jnp.zeros((1, bm), jnp.int32)               # Σ_j w_u[j, m]
    for i in range(q):
        col_sum += (1 << i) * jnp.sum(planes[i].astype(jnp.int32), axis=0,
                                      keepdims=True)
    acc = jnp.zeros((b, bm), jnp.int32)
    if fidelity == "code":
        # Σ_k 2^k a^(k) = a_codes ⇒ one dot per weight plane (exact).
        for i in range(q):
            acc += (1 << i) * code_dot(a_codes,
                                       planes[i].astype(jnp.bfloat16))
    else:  # "bitserial": the fully decomposed q·p-dot schedule (oracle)
        a_bits = activation_bits(a_codes, p)
        for i in range(q):
            for k in range(p):
                # a^(k) AND W^(i), popcount-accumulated: an int MXU matmul.
                partial = jax.lax.dot(a_bits[k], planes[i],
                                      preferred_element_type=jnp.int32)
                acc += (1 << (i + k)) * partial
    sum_a = jnp.sum(a_codes.astype(jnp.int32), axis=-1, keepdims=True)
    corr = acc - z_a * col_sum - z_w * sum_a + bn * z_a * z_w
    out_ref[...] += corr.astype(jnp.float32) * scale_ref[0]


def gemv_bs_pallas(a_codes, planes, scale_tiles, *, q: int, p: int,
                   z_a: int, z_w: int, bn: int, bm: int,
                   fidelity: str = "code", interpret: bool = False):
    """a_codes (B, N) uint8 (pad with z_a); planes (q, N//32, M) uint32."""
    global LAUNCHES
    if fidelity not in ("code", "bitserial"):
        raise ValueError(
            f"fidelity must be 'code' or 'bitserial', got {fidelity!r} "
            f"(a_codes shape {tuple(a_codes.shape)})")
    LAUNCHES += 1
    b = a_codes.shape[0]
    br = row_block(b)
    a_codes = _pad_axis(a_codes, br, 0, value=z_a)
    out = _leaf_call(
        functools.partial(_gemv_bs_kernel, q=q, p=p, z_a=z_a, z_w=z_w,
                          bn=bn, fidelity=fidelity),
        a_codes, planes, scale_tiles, q=q, bn=bn, bm=bm, br=br,
        interpret=interpret)
    return out[:b]
