"""Low-bit quantization substrate.

MVDRAM operates on low-bit (1..8 bit) weights and activations. In-DRAM (and
in-kernel) arithmetic is UNSIGNED: values are stored with a zero-point offset
and the signed result is recovered by the processor with the standard
correction terms (paper §II-C2 "properly handling two's complement" — we use
the algebraically-identical zero-point formulation):

    a = a_u - z_a,  w = w_u - z_w
    o = Σ_j a_j w_j
      = Σ a_u w_u  -  z_a Σ w_u  -  z_w Σ a_u  +  N z_a z_w

`Σ w_u` per output row is a static per-matrix vector (precomputed offline);
`Σ a_u` is one scalar per GeMV. Scales are per-group along the reduction dim.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """How a tensor is quantized.

    bits:        1..8
    symmetric:   if True zero_point = 2^(bits-1) (mid), scale covers absmax;
                 if False min/max asymmetric.
    group_size:  group length along the reduction axis; -1 = per-(column|tensor).
    """

    bits: int = 4
    symmetric: bool = True
    group_size: int = -1

    @property
    def levels(self) -> int:
        return 1 << self.bits

    @property
    def zero_point(self) -> int:
        # Symmetric uses the mid-level as the implicit zero point.
        return (1 << (self.bits - 1)) if self.bits > 1 else 0


@dataclasses.dataclass
class QuantizedTensor:
    """Unsigned quantized tensor + metadata.

    values: uint8/int32 codes in [0, 2^bits), shape (..., N, M) with N the
            reduction dim for weights (N, M) or (..., N) for activations.
    scale:  f32, broadcastable: (G, M) for weights with G groups, scalar/(...,1)
            for activations.
    zero:   integer zero point (scalar, static).
    col_sum: Σ_j values[j, m] per output column (weights only; used for the
            zero-point correction — the paper's processor-side aggregation).
    """

    values: jax.Array
    scale: jax.Array
    zero: int
    spec: QuantSpec
    col_sum: Optional[jax.Array] = None

    @property
    def bits(self) -> int:
        return self.spec.bits


jax.tree_util.register_dataclass(
    QuantizedTensor, data_fields=("values", "scale", "col_sum"),
    meta_fields=("zero", "spec"))


def _group_reshape(x: jax.Array, group_size: int):
    """(N, M) -> (G, gs, M) view along the reduction dim."""
    n = x.shape[0]
    gs = n if group_size in (-1, 0) else group_size
    assert n % gs == 0, f"reduction dim {n} not divisible by group {gs}"
    return x.reshape(n // gs, gs, *x.shape[1:]), gs


def quantize_weights(w: jax.Array, spec: QuantSpec) -> QuantizedTensor:
    """Quantize a (N, M) weight matrix (N = reduction dim) to unsigned codes."""
    assert w.ndim == 2
    wg, gs = _group_reshape(w.astype(jnp.float32), spec.group_size)
    if spec.symmetric:
        absmax = jnp.max(jnp.abs(wg), axis=1, keepdims=True)  # (G,1,M)
        # levels//2 - ... symmetric range [-2^(b-1), 2^(b-1)-1] around zero pt
        scale = absmax / jnp.maximum(spec.levels // 2 - 0.5, 0.5)
        zero = spec.zero_point
        q = jnp.round(wg / jnp.maximum(scale, 1e-12)) + zero
    else:
        lo = jnp.min(wg, axis=1, keepdims=True)
        hi = jnp.max(wg, axis=1, keepdims=True)
        scale = (hi - lo) / jnp.maximum(spec.levels - 1, 1)
        zero_f = jnp.round(-lo / jnp.maximum(scale, 1e-12))
        # Asymmetric per-group zero points complicate the correction; we fold
        # them by re-centering to a shared static zero at the mid level.
        zero = spec.levels // 2
        q = jnp.round(wg / jnp.maximum(scale, 1e-12)) + zero
        del zero_f, lo, hi
    q = jnp.clip(q, 0, spec.levels - 1).astype(jnp.uint8)
    q = q.reshape(w.shape)
    scale = scale[:, 0]  # (G, M)
    col_sum = jnp.sum(q.astype(jnp.int32), axis=0)  # (M,)
    return QuantizedTensor(values=q, scale=scale, zero=int(zero), spec=spec,
                           col_sum=col_sum)


def slice_quantized_cols(wq: QuantizedTensor, lo: int, hi: int
                         ) -> QuantizedTensor:
    """Column slice [lo, hi) of a quantized (N, M) weight tensor.

    Slicing COMMUTES with quantization: scales are per-(group, column),
    the zero point is a tensor-wide constant and `col_sum` is per output
    column, so `slice_quantized_cols(quantize_weights(w), lo, hi)` equals
    `quantize_weights(w[:, lo:hi])` code-for-code. This is the algebra the
    fabric's column-chunk tensor-parallel GeMV rests on — each DIMM's
    shard is a genuine quantized sub-matrix, so per-shard outputs are
    bit-identical to the matching columns of the unsharded oracle.
    """
    if wq.values.ndim != 2:
        raise ValueError(
            f"column slicing needs a (N, M) weight tensor, got shape "
            f"{tuple(wq.values.shape)}")
    m = wq.values.shape[1]
    if not 0 <= lo < hi <= m:
        raise ValueError(
            f"column slice [{lo}, {hi}) out of range for M={m}")
    return QuantizedTensor(
        values=wq.values[:, lo:hi], scale=wq.scale[:, lo:hi],
        zero=wq.zero, spec=wq.spec,
        col_sum=None if wq.col_sum is None else wq.col_sum[lo:hi])


@jax.named_scope("act_quant")
def quantize_activations(a: jax.Array, spec: QuantSpec) -> QuantizedTensor:
    """Quantize activations (..., N) per-row (per-token) to unsigned codes."""
    af = a.astype(jnp.float32)
    if spec.symmetric:
        absmax = jnp.max(jnp.abs(af), axis=-1, keepdims=True)
        scale = absmax / jnp.maximum(spec.levels // 2 - 0.5, 0.5)
        zero = spec.zero_point
    else:
        lo = jnp.min(af, axis=-1, keepdims=True)
        hi = jnp.max(af, axis=-1, keepdims=True)
        scale = (hi - lo) / jnp.maximum(spec.levels - 1, 1)
        zero = spec.levels // 2
    q = jnp.clip(jnp.round(af / jnp.maximum(scale, 1e-12)) + zero,
                 0, spec.levels - 1).astype(jnp.uint8)
    return QuantizedTensor(values=q, scale=scale, zero=int(zero), spec=spec)


def dequantize_weights(qt: QuantizedTensor) -> jax.Array:
    """Back to f32 (N, M)."""
    n, m = qt.values.shape
    g = qt.scale.shape[0]
    vg = qt.values.reshape(g, n // g, m).astype(jnp.float32)
    out = (vg - qt.zero) * qt.scale[:, None, :]
    return out.reshape(n, m)


def dequantize_activations(qt: QuantizedTensor) -> jax.Array:
    return (qt.values.astype(jnp.float32) - qt.zero) * qt.scale


def quantized_gemv_reference(aq: QuantizedTensor, wq: QuantizedTensor) -> jax.Array:
    """Integer-domain GeMV with processor-side zero-point correction.

    This is the algebra MVDRAM executes: unsigned integer MACs in DRAM,
    correction + scaling on the processor. Supports per-group weight scales
    only when group covers the whole reduction dim (the in-DRAM path uses
    per-subarray partitions as natural groups; see engine.plan()).
    """
    a_u = aq.values.astype(jnp.int32)  # (..., N)
    w_u = wq.values.astype(jnp.int32)  # (N, M)
    n = a_u.shape[-1]
    g = wq.scale.shape[0]
    gs = n // g
    a_g = a_u.reshape(*a_u.shape[:-1], g, gs)
    w_g = w_u.reshape(g, gs, -1)
    acc = jnp.einsum("...gn,gnm->...gm", a_g, w_g)  # int32 partial per group
    sum_a = jnp.sum(a_g, axis=-1)  # (..., g)
    sum_w = jnp.sum(w_g, axis=1)  # (g, M)
    corr = (acc
            - aq.zero * sum_w          # (g, M) broadcasts over leading dims
            - wq.zero * sum_a[..., None]
            + gs * aq.zero * wq.zero)
    out = jnp.einsum("...gm,gm->...m", corr.astype(jnp.float32), wq.scale)
    return out * aq.scale


# ---------------------------------------------------------------------------
# Straight-through fake quantization, used for QAT so that trained models can
# be served through the bitplane engine.
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant(w: jax.Array, bits: int, group_size: int) -> jax.Array:
    spec = QuantSpec(bits=bits, group_size=group_size)
    if w.ndim == 1:
        qt = quantize_weights(w[:, None], spec)
        return dequantize_weights(qt)[:, 0]
    shape = w.shape
    w2 = w.reshape(shape[0], -1) if w.ndim > 2 else w
    qt = quantize_weights(w2, spec)
    return dequantize_weights(qt).reshape(shape)


def _fq_fwd(w, bits, group_size):
    return fake_quant(w, bits, group_size), None


def _fq_bwd(bits, group_size, _, g):
    return (g,)  # straight-through


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def pack_codes(values: jax.Array, bits: int) -> jax.Array:
    """Pack uint codes along the LAST axis into uint32 words (little-endian
    within the word); zero-pads to a word boundary."""
    per = 32 // bits
    *lead, n = values.shape
    pad = (-n) % per
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((*lead, pad), values.dtype)], axis=-1)
        n += pad
    v = values.astype(jnp.uint32).reshape(*lead, n // per, per)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)
    return jnp.sum(v << shifts, axis=-1).astype(jnp.uint32)


def unpack_codes(packed: jax.Array, bits: int, n: int) -> jax.Array:
    per = 32 // bits
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)
    mask = jnp.uint32((1 << bits) - 1)
    v = (packed[..., None] >> shifts) & mask
    return v.reshape(*packed.shape[:-1], packed.shape[-1] * per)[..., :n].astype(jnp.uint8)
