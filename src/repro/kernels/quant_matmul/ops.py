"""Public wrapper for the fused dequant matmul baseline."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.quant import QuantizedTensor
from ..bitplane_gemv.ops import _pad_axis, _pick_blocks
from . import kernel, ref


def pack_weight_codes(values: jax.Array, q: int) -> jax.Array:
    """(N, M) uint codes → (ceil(N/per), M) uint32, packed along N."""
    per = 32 // q
    v = _pad_axis(values.astype(jnp.uint32), per, 0)
    n, m = v.shape
    v = v.reshape(n // per, per, m)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * q)[None, :, None]
    return jnp.sum(v << shifts, axis=1).astype(jnp.uint32)


def _expand_scales_qt(wq: QuantizedTensor, bn: int, n_pad: int) -> jax.Array:
    g, m = wq.scale.shape
    n = wq.values.shape[0]
    gs = n // g
    tiles = n_pad // bn
    if g == 1:
        s = jnp.broadcast_to(wq.scale, (tiles, m))
    else:
        if gs % bn:
            raise ValueError(f"group size {gs} must be a multiple of bn={bn}")
        s = jnp.repeat(wq.scale, gs // bn, axis=0)
        pad = tiles - s.shape[0]
        if pad > 0:
            s = jnp.concatenate([s, jnp.zeros((pad, m), s.dtype)], axis=0)
    starts = jnp.arange(tiles) * bn
    return jnp.where((starts < n)[:, None], s, 0.0)


@functools.partial(jax.jit, static_argnames=("impl", "bn", "bm"))
def quant_matmul(a: jax.Array, wq: QuantizedTensor, *, impl: str = "jnp",
                 bn: Optional[int] = None, bm: Optional[int] = None
                 ) -> jax.Array:
    """Float activations (…, N) × packed q-bit codes → (…, M) f32."""
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    n, m = wq.values.shape
    q = wq.spec.bits
    g = wq.scale.shape[0]
    _tn, bn, bm = _pick_blocks(n, m, bn, bm, n // g if g > 1 else None)
    per = 32 // q
    if bn % per != 0:
        raise ValueError(
            f"reduction block bn={bn} must be a multiple of the packing "
            f"density 32//q={per} (q={q}, weight shape {(n, m)})")
    a2 = _pad_axis(a2, bn, 1)
    codes = pack_weight_codes(wq.values, q)                  # zero-padded N
    codes = _pad_axis(codes, bn // per, 0)
    codes = _pad_axis(codes, bm, 1)
    scale_t = _pad_axis(_expand_scales_qt(wq, bn, a2.shape[1]), bm, 1)
    kw = dict(q=q, zero=wq.zero, bn=bn, bm=bm)
    if impl == "jnp":
        out = ref.quant_matmul_ref(a2, codes, scale_t, **kw)
    else:
        out = kernel.quant_matmul_pallas(a2, codes, scale_t, **kw,
                                         interpret=(impl == "pallas_interpret"))
    return out[:, :m].reshape(*lead, m)
