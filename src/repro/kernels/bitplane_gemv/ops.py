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
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.bitplane import BitplaneWeights
from ...core.quant import QuantSpec, quantize_activations
from . import kernel, ref
from .kernel import _pad_axis

DEFAULT_BN = 512   # reduction-dim block (multiple of 32-bit packing)
DEFAULT_BM = 256   # output-dim block (multiple of 128 lanes)


def _pick_blocks(n: int, m: int, bn: Optional[int], bm: Optional[int],
                 group_size: Optional[int] = None):
    bn = bn or min(DEFAULT_BN, n)
    bm = bm or min(DEFAULT_BM, m)
    if group_size and group_size > 0:
        if group_size % 32 != 0:
            raise ValueError(
                f"scale group size must be a multiple of 32 (the bit-plane "
                f"word width), got group_size={group_size} for an "
                f"(N={n}, M={m}) matrix")
        bn = min(bn, group_size)   # per-group scales stay tile-local
    bn = max(32, (bn // 32) * 32)
    # bm stays a multiple of the 128-lane tile even when m < 128: callers
    # pad planes/scales up to bm and slice out[:, :m], so a small output
    # dim must never shrink the block into a misaligned Pallas grid
    bm = max(128, (bm // 128) * 128)
    return bn, bm


def _expand_scales(bw: BitplaneWeights, bn: int, n_pad: int) -> jax.Array:
    """(G, M) group scales → (n_pad//bn, M) per-reduction-tile scales.

    Requires the group length to be a multiple of bn (or G == 1). Scale rows
    covering pure padding are zero so padded blocks contribute nothing.
    """
    g, m = bw.scale.shape
    gs = bw.n // g
    tiles = n_pad // bn
    if g == 1:
        s = jnp.broadcast_to(bw.scale, (tiles, m))
    else:
        if gs % bn:
            raise ValueError(f"group size {gs} must be a multiple of bn={bn}")
        s = jnp.repeat(bw.scale, gs // bn, axis=0)
        s = _pad_axis(s, tiles, 0)[:tiles]
    # zero out tiles that start at/after the true reduction length
    starts = jnp.arange(tiles) * bn
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
    bn, bm = _pick_blocks(n, m, bn, bm, n // g if g > 1 else None)
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
    bn, bm = _pick_blocks(n, m, bn, bm, n // g if g > 1 else None)
    a2 = _pad_axis(a2, bn, 1, value=z_a)   # pad codes at the zero point
    planes = _pad_axis(bw.planes, bn // 32, 1)
    planes = _pad_axis(planes, bm, 2)
    scale_t = _pad_axis(_expand_scales(bw, bn, a2.shape[1]), bm, 1)
    kw = dict(q=bw.bits, p=p, z_a=z_a, z_w=bw.zero, bn=bn, bm=bm)
    if impl == "jnp":
        out = ref.gemv_bs_ref(a2, planes, scale_t, **kw)
    else:
        out = kernel.gemv_bs_pallas(a2, planes, scale_t, **kw,
                                    fidelity=fidelity,
                                    interpret=(impl == "pallas_interpret"))
    return out[:, :m].reshape(*lead, m)
