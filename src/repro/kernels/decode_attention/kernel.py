"""Pallas TPU flash-decode kernel.

Grid = (batch, kv_blocks); the kv_blocks axis is SEQUENTIAL ("arbitrary"):
running max / denominator / accumulator live in VMEM scratch and survive
across block steps; the output is written at the last block. Per step the
kernel loads one (bk, Hkv, D) cache tile — int8 tiles are widened and
scaled IN VMEM (the whole point: at the XLA level this dequant materializes
in HBM; here it never leaves the core).

Masking is position-stamped (ring-buffer semantics, matching
models/attention.py): a slot participates iff 0 ≤ stamp ≤ pos (+ window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, kpos_ref, ks_ref, vs_ref,
                   out_ref, m_ref, l_ref, acc_ref, *, scale: float,
                   window, int8_kv: bool, blocks: int):
    jb = pl.program_id(1)

    @pl.when(jb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (H, D)
    k = k_ref[0].astype(jnp.float32)                     # (bk, H, D)
    v = v_ref[0].astype(jnp.float32)
    if int8_kv:                                          # fused dequant
        k = k * ks_ref[0].astype(jnp.float32)[..., None]
        v = v * vs_ref[0].astype(jnp.float32)[..., None]
    pos = pos_ref[pl.program_id(0)]                      # per-lane position
    stamps = kpos_ref[0]                                 # (bk,) lane stamps
    ok = (stamps >= 0) & (stamps <= pos)
    if window is not None:
        ok &= (pos - stamps) < window

    # scores (H, bk): per-head dot of q row with the block's keys
    s = jax.lax.dot_general(q, k, (((1,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[None, :], s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (H, bk)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv          # (H, D)·(H, 1)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(jb == blocks - 1)
    def _finish():
        out_ref[...] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30))[None].astype(
                            out_ref.dtype)


def decode_attention_pallas(pos, q, k, v, kv_positions, k_scale, v_scale, *,
                            scale: float, window, block: int,
                            interpret: bool = False):
    """pos (B,) i32 per-lane positions; q (B, H, D); k/v (B, S, H, D)
    [bf16 or int8]; kv_positions (B, S) i32 per-lane stamps;
    k_scale/v_scale (B, S, H) f32 (dummies if bf16).
    KV heads must be pre-expanded to H (GQA repeat upstream)."""
    b, h, d = q.shape
    s = k.shape[1]
    if s % block != 0:
        raise ValueError(
            f"KV sequence length {s} must be a multiple of block={block} "
            f"(k shape {tuple(k.shape)}); pad the cache upstream")
    blocks = s // block
    int8_kv = k.dtype == jnp.int8
    grid = (b, blocks)
    kern = functools.partial(_decode_kernel, scale=scale, window=window,
                             int8_kv=int8_kv, blocks=blocks)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b,), lambda bi, ji: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, d), lambda bi, ji: (bi, 0, 0)),
            pl.BlockSpec((1, block, h, d), lambda bi, ji: (bi, ji, 0, 0)),
            pl.BlockSpec((1, block, h, d), lambda bi, ji: (bi, ji, 0, 0)),
            pl.BlockSpec((1, block), lambda bi, ji: (bi, ji)),
            pl.BlockSpec((1, block, h), lambda bi, ji: (bi, ji, 0)),
            pl.BlockSpec((1, block, h), lambda bi, ji: (bi, ji, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, ji: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),       # running max
            pltpu.VMEM((h, 1), jnp.float32),       # running denom
            pltpu.VMEM((h, d), jnp.float32),       # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, q, k, v, kv_positions, k_scale, v_scale)
