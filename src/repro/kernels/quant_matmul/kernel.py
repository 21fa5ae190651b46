"""Pallas TPU kernel: matmul against q-bit weight CODES packed in uint32.

The conventional way to serve low-bit weights on a processor (what llama.cpp/
ggml does, paper Table II baselines): keep codes packed in memory, widen to
arithmetic type in registers/VMEM, dequantize with (code − zero)·scale, MAC
in f32. One VMEM tile of codes is (bn//per, bm) uint32 words, per = 32/q
codes per word along the reduction dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_codes(words: jax.Array, q: int, bn: int) -> jax.Array:
    """(W, bm) uint32 → (W·per, bm) uint code planes along the reduction dim."""
    w, bm = words.shape
    per = 32 // q
    shifts = (jnp.arange(per, dtype=jnp.uint32) * q)[None, :, None]
    mask = jnp.uint32((1 << q) - 1)
    codes = (words[:, None, :] >> shifts) & mask
    return codes.reshape(w * per, bm)[:bn]


def _qmm_kernel(a_ref, codes_ref, scale_ref, out_ref, *, q: int, zero: int,
                bn: int):
    n_idx = pl.program_id(1)

    @pl.when(n_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_blk = a_ref[...].astype(jnp.float32)                     # (B, bn)
    codes = _unpack_codes(codes_ref[...], q, bn)               # (bn, bm)
    w_blk = (codes.astype(jnp.float32) - zero) * scale_ref[...]  # dequant
    out_ref[...] += jax.lax.dot(a_blk, w_blk,
                                precision=jax.lax.Precision.HIGHEST)


def quant_matmul_pallas(a, codes, scale_tiles, *, q: int, zero: int,
                        bn: int, bm: int, interpret: bool = False):
    """a (B, N) float; codes (N//per, M) uint32; scale_tiles (N//bn, M)."""
    b, n = a.shape
    m = codes.shape[-1]
    per = 32 // q
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, q=q, zero=zero, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, bn), lambda mi, ni: (0, ni)),
            pl.BlockSpec((bn // per, bm), lambda mi, ni: (ni, mi)),
            pl.BlockSpec((1, bm), lambda mi, ni: (ni, mi)),
        ],
        out_specs=pl.BlockSpec((b, bm), lambda mi, ni: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, codes, scale_tiles)
