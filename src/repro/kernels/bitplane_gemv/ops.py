"""Public entry points for bit-plane GeMV.

Handles padding to block multiples, scale expansion to per-reduction-tile
rows, activation quantization for the bit-serial mode, and backend dispatch
(`impl="pallas"` TPU kernel / `"pallas_interpret"` CPU-checkable kernel body /
`"jnp"` oracle — the jnp path READS THE SAME PACKED PLANES, so its HLO bytes
reflect the packed-storage memory win and it is what multi-pod dry-runs
lower). The bit-serial entry points take `fidelity`: "code" (default) issues
one integer dot per tile via the §V-D linearity collapse of both operands
(one per weight plane past its exactness bound), "bitserial" the fully
decomposed q·p schedule — identical integers (see kernel.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.bitplane import BitplaneWeights
from ...core.quant import QuantSpec, quantize_activations
from . import kernel, ref
from .kernel import _pad_axis

TILE_N = 512      # compute tile: reduction rows of one dot (32-bit words)
DEFAULT_BM = 256  # output block of a one-tile cell (multiple of 128 lanes)
BM_CAP = 512      # widest output block of an integer launch's cell


def _pick_blocks(n: int, m: int, bn: Optional[int] = None,
                 bm: Optional[int] = None,
                 group_size: Optional[int] = None, *,
                 q: Optional[int] = None, rows: int = 1):
    """A launch's blocks → (tn, bn, bm): the compute tile tn, the
    reduction width of one dot, and the grid cell of bn reduction rows by
    bm output columns.

    tn is TILE_N, less where n (or a given bn) or the scale group is
    smaller, so per-group scales stay tile-local. The float kernel and
    the dequant baseline compute one tile per cell: without `q` the cell
    is one tile and bm is DEFAULT_BM. The integer kernels walk their cell
    tile by tile and give the weight bits `q` and the launch's `rows`:
    then bm is `output_block((m,))` (a fused group's plan takes it over
    every member's m) and the cell holds `cell_tiles` tiles. A given bn
    or bm forces that block. bm stays a multiple of the 128-lane tile even
    when m < 128: callers pad planes/scales up to bm and slice out[:, :m].
    """
    if group_size and group_size > 0 and group_size % 32 != 0:
        raise ValueError(
            f"scale group size must be a multiple of 32 (the bit-plane "
            f"word width), got group_size={group_size} for an "
            f"(N={n}, M={m}) matrix")
    tn = min(TILE_N, bn or n, group_size or TILE_N)
    tn = max(32, (tn // 32) * 32)
    if q is None:
        bm = bm or min(DEFAULT_BM, m)
        return tn, tn, max(128, (bm // 128) * 128)
    bm = max(128, (bm // 128) * 128) if bm else output_block((m,))
    if bn:
        return tn, max(1, bn // tn) * tn, bm
    k = cell_tiles(-(-n // tn), tn, q, bm, kernel.row_block(rows))
    return tn, k * tn, bm


def output_block(ms: tuple) -> int:
    """The widest multiple of 128 up to BM_CAP that divides every m, each
    padded to 128 columns: a cell never straddles two members of a fused
    group, and no output is padded past its 128-column tile."""
    g = math.gcd(*(-(-m // 128) * 128 for m in ms))
    return max(d for d in range(128, BM_CAP + 1, 128) if g % d == 0)


def cell_tiles(nt: int, tn: int, q: int, bm: int, br: int) -> int:
    """Compute tiles per grid cell: the most that divides the reduction's
    nt tiles, keeps the per-leaf blocks on Mosaic's tiling (bn/32 a
    multiple of 8 and bn of 128, or the whole axis) and keeps the launch's
    `kernel.vmem_bytes` within the default scoped VMEM; 1 where none
    does."""
    for k in range(nt, 0, -1):
        aligned = k == nt or (k * tn // 32 % 8 == 0 and k * tn % 128 == 0)
        if (nt % k == 0 and aligned and kernel.vmem_bytes(q, tn, k, bm, br)
                <= kernel.SCOPED_VMEM):
            return k
    return 1


def _expand_scales(bw: BitplaneWeights, tn: int, n_pad: int) -> jax.Array:
    """(G, M) group scales → (n_pad//tn, M) per-compute-tile scales.

    Requires the group length to be a multiple of tn (or G == 1). Scale rows
    covering pure padding are zero so padded tiles contribute nothing.
    """
    g, m = bw.scale.shape
    gs = bw.n // g
    tiles = n_pad // tn
    if g == 1:
        s = jnp.broadcast_to(bw.scale, (tiles, m))
    else:
        if gs % tn:
            raise ValueError(f"group size {gs} must be a multiple of tn={tn}")
        s = jnp.repeat(bw.scale, gs // tn, axis=0)
        s = _pad_axis(s, tiles, 0)[:tiles]
    # zero out tiles that start at/after the true reduction length
    starts = jnp.arange(tiles) * tn
    return jnp.where((starts < bw.n)[:, None], s, 0.0)


@functools.partial(jax.jit, static_argnames=("impl", "bn", "bm"))
def bitplane_gemv(a: jax.Array, bw: BitplaneWeights, *, impl: str = "jnp",
                  bn: Optional[int] = None, bm: Optional[int] = None
                  ) -> jax.Array:
    """Float activations (…, N) × packed bit-plane weights → (…, M) f32."""
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    n, m = bw.n, bw.m
    g = bw.scale.shape[0]
    _tn, bn, bm = _pick_blocks(n, m, bn, bm, n // g if g > 1 else None)
    a2 = _pad_axis(a2, bn, 1)
    planes = _pad_axis(bw.planes, bn // 32, 1)       # words along N
    planes = _pad_axis(planes, bm, 2)
    scale_t = _pad_axis(_expand_scales(bw, bn, a2.shape[1]), bm, 1)
    kw = dict(q=bw.bits, zero=bw.zero, bn=bn, bm=bm)
    if impl == "jnp":
        out = ref.gemv_f_ref(a2, planes, scale_t, **kw)
    else:
        out = kernel.gemv_f_pallas(a2, planes, scale_t, **kw,
                                   interpret=(impl == "pallas_interpret"))
    return out[:, :m].reshape(*lead, m)


def bitplane_gemv_bitserial(a: jax.Array, bw: BitplaneWeights,
                            a_spec: QuantSpec, *, impl: str = "jnp",
                            bn: Optional[int] = None,
                            bm: Optional[int] = None,
                            fidelity: str = "code") -> jax.Array:
    """Quantize activations to p-bit codes, then integer bit-plane GeMV —
    the exact integer computation of the paper (§V + §VI combined).

    `fidelity="code"` (default) uses the §V-D linearity collapse (one int
    dot per tile within `kernel.one_dot_exact`); `fidelity="bitserial"`
    issues the fully decomposed q·p-dot schedule. Identical integers
    either way (tested)."""
    aq = quantize_activations(a, a_spec)
    out = bitplane_gemv_codes(aq.values, bw, a_spec.bits, int(aq.zero),
                              impl=impl, bn=bn, bm=bm, fidelity=fidelity)
    return out * aq.scale.reshape(out.shape[:-1] + (1,))


@functools.partial(jax.jit, static_argnames=("p", "z_a", "impl", "bn", "bm",
                                             "fidelity"))
def bitplane_gemv_codes(a_codes: jax.Array, bw: BitplaneWeights, p: int,
                        z_a: int, *, impl: str = "jnp",
                        bn: Optional[int] = None, bm: Optional[int] = None,
                        fidelity: str = "code") -> jax.Array:
    """(…, N) uint8 activation codes × bit-plane weights → un-a-scaled f32."""
    lead = a_codes.shape[:-1]
    a2 = a_codes.reshape(-1, a_codes.shape[-1])
    n, m = bw.n, bw.m
    g = bw.scale.shape[0]
    tn, bn, bm = _pick_blocks(n, m, bn, bm, n // g if g > 1 else None,
                              q=bw.bits, rows=a2.shape[0])
    a2 = _pad_axis(a2, bn, 1, value=z_a)   # pad codes at the zero point
    planes = _pad_axis(bw.planes, bn // 32, 1)
    planes = _pad_axis(planes, bm, 2)
    scale_t = _pad_axis(_expand_scales(bw, tn, a2.shape[1]), bm, 1)
    kw = dict(q=bw.bits, p=p, z_a=z_a, z_w=bw.zero, bm=bm)
    if impl == "jnp":
        out = ref.gemv_bs_ref(a2, planes, scale_t, **kw, bn=tn)
    else:
        out = kernel.gemv_bs_pallas(a2, planes, scale_t, **kw, tn=tn, bn=bn,
                                    fidelity=fidelity,
                                    interpret=(impl == "pallas_interpret"))
    return out[:, :m].reshape(*lead, m)
