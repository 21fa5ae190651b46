"""Pure-jnp oracles for the bitplane_gemv kernels (shape-for-shape)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.bitplane import unpack_bitplanes


def gemv_f_ref(a, planes, scale_tiles, *, q: int, zero: int, bn: int, bm: int):
    """Same contract as kernel.gemv_f_pallas, evaluated densely."""
    b, n = a.shape
    m = planes.shape[-1]
    w = unpack_bitplanes(planes, n).astype(jnp.float32)      # (q, N, M)
    af = a.astype(jnp.float32)
    t = n // bn
    a_t = af.reshape(b, t, bn)
    w_t = w.reshape(q, t, bn, m)
    # plane weights explicit (2^i), tile-local correction + scaling:
    acc = jnp.einsum("btn,qtnm,q->btm", a_t, w_t,
                     2.0 ** jnp.arange(q, dtype=jnp.float32))
    corr = acc - zero * jnp.sum(a_t, axis=-1)[..., None]
    return jnp.einsum("btm,tm->bm", corr, scale_tiles.astype(jnp.float32))


def gemv_bs_ref(a_codes, planes, scale_tiles, *, q: int, p: int,
                z_a: int, z_w: int, bn: int, bm: int):
    """Same contract as kernel.gemv_bs_pallas, evaluated densely (int32)."""
    b, n = a_codes.shape
    m = planes.shape[-1]
    w = unpack_bitplanes(planes, n).astype(jnp.int32)        # (q, N, M)
    t = n // bn
    a_t = a_codes.astype(jnp.int32).reshape(b, t, bn)
    w_t = w.reshape(q, t, bn, m)
    a_planes = (a_t[:, None] >> jnp.arange(p, dtype=jnp.int32)[:, None, None]
                ) & 1                                        # (B, p, t, bn)
    wts = (1 << (jnp.arange(p)[:, None] + jnp.arange(q)[None, :])).astype(
        jnp.int32)
    acc = jnp.einsum("bptn,qtnm,pq->btm", a_planes, w_t, wts)
    col_sum = jnp.einsum("qtnm,q->tm", w_t,
                         (1 << jnp.arange(q)).astype(jnp.int32))
    sum_a = jnp.sum(a_t, axis=-1)                            # (B, t)
    corr = (acc - z_a * col_sum[None] - z_w * sum_a[..., None]
            + bn * z_a * z_w).astype(jnp.float32)
    scale = scale_tiles.astype(jnp.float32)
    # the kernel's epilogue order, tile by tile: the integer parts are exact,
    # so the same f32 sequence makes the two results bitwise comparable
    out = jnp.zeros((b, m), jnp.float32)
    for i in range(t):
        out = out + corr[:, i] * scale[i]
    return out
