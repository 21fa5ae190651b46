"""Attention variants: GQA (llama/qwen/starcoder/gemma2 family) and MLA
(deepseek-v2 latent attention), each with a full-sequence path (training /
prefill) and a single-token cached path (decode).

KV caches are position-stamped ring buffers: alongside k/v we keep a
`positions` vector (init −1); sliding-window ("local") layers allocate only
`window` slots and rotate, so a 524k-token decode holds a 4k-slot cache for
local layers — this is what makes gemma2 long_500k runnable. Masks are
derived from the stamped positions, never from slot order.

MLA decode uses weight absorption (q_nope folded through W_uk, context read
directly off the compressed c_kv cache) so per-step FLOPs and cache traffic
scale with kv_lora_rank, not heads·head_dim — the paper-aligned low-rank
GeMV shape that the bit-plane engine serves.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import constrain
from .config import AttnConfig, MLAConfig, ModelConfig
from .layers import (apply_rope, dense, dense_group, rope_frequencies,
                     softcap)

NEG_INF = -2.3819763e38  # ~ lowest bf16-representable; used pre-softmax


def _causal_mask(s_q: int, s_k: int, window: Optional[int]) -> jax.Array:
    """(s_q, s_k) additive mask; queries are the LAST s_q of s_k positions."""
    qi = jnp.arange(s_q)[:, None] + (s_k - s_q)
    kj = jnp.arange(s_k)[None, :]
    ok = kj <= qi
    if window is not None:
        ok &= (qi - kj) < window
    return jnp.where(ok, 0.0, NEG_INF)


def _sdpa(q, k, v, mask, cap: Optional[float], scale: float):
    """q (B,Sq,H,D), k/v (B,Sk,Hkv,D'), mask broadcastable (B,1,Sq,Sk)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, cap)
    scores = scores + mask      # (Sq,Sk) or (1,1,1,Sk) — broadcast over bhg
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhgst,bthv->bshgv", w, v.astype(jnp.float32))
    return ctx.reshape(b, sq, h * v.shape[-1]).astype(q.dtype)


FLASH_THRESHOLD = 2048   # use blocked attention above this sequence length
FLASH_BLOCK = 1024
FLASH_Q_CHUNK = 4096     # long prefills also chunk the query axis
FLASH_P_BF16 = False     # score/p tiles in bf16 (flash-kernel recipe):
#                          halves attention HBM traffic at ~1e-2 rel err;
#                          toggled per-run by dryrun --flash-bf16


def _flash_sdpa(q, k, v, window: Optional[int], cap: Optional[float],
                scale: float, block: int = FLASH_BLOCK):
    """Numerically-stable blocked attention (flash-style): lax.scan over KV
    blocks with running (max, denom, acc) — peak memory O(Sq·block) instead
    of O(Sq·Sk). Causal; optional sliding window. Same-length q/k
    (full-sequence training/prefill path).

    KV heads are EXPANDED to the full head count up front so every score /
    accumulator tensor keeps the flat (b, h, …) layout — the head dim then
    shards cleanly over the model axis (a (hkv, g) grouped layout would
    force replication whenever hkv < mesh model size).
    """
    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if hkv != h:                       # query head i attends kv head i//g
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    nb = -(-s // block)
    pad = nb * block - s
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    lowp = FLASH_P_BF16
    qf = q if lowp else q.astype(jnp.float32)
    kb = kp.reshape(b, nb, block, h, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nb, block, h, dv).transpose(1, 0, 2, 3, 4)

    def process(q_c, q_pos):
        """One query chunk (b, sq, h, d) against all KV blocks."""
        sq = q_c.shape[1]

        def body(carry, inp):
            m, l, acc = carry
            jb, k_j, v_j = inp
            k_pos = jb * block + jnp.arange(block)
            ok = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < s)
            if window is not None:
                ok &= (q_pos[:, None] - k_pos[None, :]) < window
            if lowp:  # bf16 operands, f32 accumulation (flash recipe)
                sc = jnp.einsum("bshd,bthd->bhst", q_c, k_j,
                                preferred_element_type=jnp.float32) * scale
            else:
                sc = jnp.einsum("bshd,bthd->bhst", q_c,
                                k_j.astype(jnp.float32)) * scale
            sc = softcap(sc, cap)
            sc = jnp.where(ok[None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            if lowp:
                acc_new = (acc * alpha[..., None]
                           + jnp.einsum("bhst,bthv->bhsv",
                                        p.astype(jnp.bfloat16), v_j,
                                        preferred_element_type=jnp.float32))
            else:
                acc_new = (acc * alpha[..., None]
                           + jnp.einsum("bhst,bthv->bhsv", p,
                                        v_j.astype(jnp.float32)))
            l_new = l * alpha + p.sum(axis=-1)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)
        a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (jnp.arange(nb), kb, vb))
        return acc / jnp.maximum(l, 1e-30)[..., None]   # (b, h, sq, dv)

    # Long prefills additionally chunk the QUERY axis so the score tile is
    # (b, h, Q_CHUNK, block) regardless of sequence length.
    if s > FLASH_Q_CHUNK and s % FLASH_Q_CHUNK == 0:
        nq = s // FLASH_Q_CHUNK
        qc = qf.reshape(b, nq, FLASH_Q_CHUNK, h, d).transpose(1, 0, 2, 3, 4)
        pc = jnp.arange(s).reshape(nq, FLASH_Q_CHUNK)
        ctx = jax.lax.map(lambda t: process(t[0], t[1]), (qc, pc))
        ctx = ctx.transpose(1, 2, 0, 3, 4)              # (b, h, nq, sq, dv)
        ctx = ctx.reshape(b, h, s, dv)
    else:
        ctx = process(qf, jnp.arange(s))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return ctx.astype(q.dtype)


def _attend(q, k, v, window, cap, scale):
    """Dispatch direct vs blocked attention by sequence length."""
    s = q.shape[1]
    if s > FLASH_THRESHOLD:
        return _flash_sdpa(q, k, v, window, cap, scale)
    return _sdpa(q, k, v, _causal_mask(s, s, window), cap, scale)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def gqa_forward(x, p, acfg: AttnConfig, window: Optional[int],
                positions: jax.Array, act_bits=None, impl=None,
                return_kv: bool = False):
    """Full-sequence self-attention. x (B,S,E); positions (S,)."""
    b, s, _ = x.shape
    h, hkv, d = acfg.num_heads, acfg.num_kv_heads, acfg.head_dim
    q, k, v = dense_group(x, (p["wq"], p["wk"], p["wv"]),
                          (p.get("bq"), p.get("bk"), p.get("bv")),
                          act_bits, impl)
    q = q.reshape(b, s, h, d)
    k = k.reshape(b, s, hkv, d)
    v = v.reshape(b, s, hkv, d)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    rd = acfg.rope_dim or d
    cos, sin = rope_frequencies(rd, acfg.rope_base, positions)
    q = apply_rope(q, cos, sin, rd)
    k = apply_rope(k, cos, sin, rd)
    ctx = _attend(q, k, v, window, acfg.softcap, d ** -0.5)
    out = dense(ctx, p["wo"], act_bits=act_bits, impl=impl)
    return (out, (k, v)) if return_kv else out


def _kv_quant(x):
    """(B,1,Hkv,D) → int8 codes + per-(B,1,Hkv) f32 scale (absmax/127)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale):
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


@jax.named_scope("attention")
def gqa_decode(x, p, acfg: AttnConfig, window: Optional[int], cache: dict,
               pos: jax.Array, act_bits=None, impl=None,
               attn_impl: str = "sdpa"):
    """One-token step. x (B,1,E); cache {k,v:(B,Sc,Hkv,D), positions:(Sc,)}.

    When the cache was created with kv_bits=8 (keys "k_scale"/"v_scale"
    present), keys/values are stored as int8 with per-(token, head) scales —
    halving resident cache bytes (beyond-paper optimization, §Perf)."""
    b, _, _ = x.shape
    h, hkv, d = acfg.num_heads, acfg.num_kv_heads, acfg.head_dim
    sc = cache["k"].shape[1]
    int8_kv = "k_scale" in cache
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))  # per-lane
    q, k, v = dense_group(x, (p["wq"], p["wk"], p["wv"]),
                          (p.get("bq"), p.get("bk"), p.get("bv")),
                          act_bits, impl)
    q = q.reshape(b, 1, h, d)
    k = k.reshape(b, 1, hkv, d)
    v = v.reshape(b, 1, hkv, d)
    rd = acfg.rope_dim or d
    cos, sin = rope_frequencies(rd, acfg.rope_base, pos[:, None])  # (B,1,r/2)
    q = apply_rope(q, cos, sin, rd)
    k = apply_rope(k, cos, sin, rd)
    slot = pos if window is None else pos % jnp.asarray(sc)       # (B,)
    lane = jnp.arange(b)
    new_cache = {}
    with jax.named_scope("kv_write"):
        if int8_kv:
            kq, ks = _kv_quant(k)
            vq, vs = _kv_quant(v)
            k_all = cache["k"].at[lane, slot].set(kq[:, 0])
            v_all = cache["v"].at[lane, slot].set(vq[:, 0])
            ks_all = cache["k_scale"].at[lane, slot].set(ks[:, 0])
            vs_all = cache["v_scale"].at[lane, slot].set(vs[:, 0])
            new_cache.update(k_scale=ks_all, v_scale=vs_all)
        else:
            k_all = cache["k"].at[lane, slot].set(k[:, 0])
            v_all = cache["v"].at[lane, slot].set(v[:, 0])
        pos_all = cache["positions"].at[lane, slot].set(pos)      # (B, Sc)
    if int8_kv:
        k_use = _kv_dequant(k_all, ks_all).astype(x.dtype)
        v_use = _kv_dequant(v_all, vs_all).astype(x.dtype)
    else:
        k_use, v_use = k_all, v_all
    if attn_impl != "sdpa" and acfg.softcap is None:
        # fused flash-decode kernel: reads the RAW (possibly int8) cache —
        # no dequant/convert materialization in HBM
        from ..kernels.decode_attention import ops as dk
        ctx = dk.decode_attention(
            pos, q[:, 0], k_all, v_all, pos_all,
            new_cache.get("k_scale"), new_cache.get("v_scale"),
            window=window,
            impl="pallas" if attn_impl == "kernel" else "pallas_interpret")
        ctx = ctx.reshape(b, 1, h * d).astype(x.dtype)
    else:
        k_use = constrain(k_use, "batch", "kv_seq", "kv_heads", None)
        v_use = constrain(v_use, "batch", "kv_seq", "kv_heads", None)
        ok = (pos_all >= 0) & (pos_all <= pos[:, None])
        if window is not None:
            ok &= (pos[:, None] - pos_all) < window
        # (B,1,1,1,Sc): lane dim must align with scores dim0 (b,hkv,g,sq,t)
        mask = jnp.where(ok, 0.0, NEG_INF)[:, None, None, None, :]
        ctx = _sdpa(q, k_use, v_use, mask, acfg.softcap, d ** -0.5)
    out = dense(ctx, p["wo"], act_bits=act_bits, impl=impl)
    new_cache.update(k=k_all, v=v_all, positions=pos_all)
    return out, new_cache


def gqa_cache_init(cfg_batch: int, slots: int, acfg: AttnConfig, dtype,
                   kv_bits=None):
    hkv, d = acfg.num_kv_heads, acfg.head_dim
    if kv_bits == 8:
        return {
            "k": jnp.zeros((cfg_batch, slots, hkv, d), jnp.int8),
            "v": jnp.zeros((cfg_batch, slots, hkv, d), jnp.int8),
            "k_scale": jnp.zeros((cfg_batch, slots, hkv), jnp.float32),
            "v_scale": jnp.zeros((cfg_batch, slots, hkv), jnp.float32),
            "positions": jnp.full((cfg_batch, slots), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((cfg_batch, slots, hkv, d), dtype),
        "v": jnp.zeros((cfg_batch, slots, hkv, d), dtype),
        "positions": jnp.full((cfg_batch, slots), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA (deepseek-v2-lite flavour)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def mla_forward(x, p, acfg: AttnConfig, mla: MLAConfig, positions,
                act_bits=None, impl=None, return_kv: bool = False):
    """Full-sequence MLA. Params: wq (E, H·(dn+dr)), w_dkv (E, L+dr),
    kv_norm (L,), w_uk (L, H·dn), w_uv (L, H·dv), wo (H·dv, E)."""
    from .layers import rmsnorm
    b, s, _ = x.shape
    h = acfg.num_heads
    dn, dr, dv, lr = (mla.qk_nope_head_dim, mla.qk_rope_head_dim,
                      mla.v_head_dim, mla.kv_lora_rank)
    q = dense(x, p["wq"], act_bits=act_bits, impl=impl).reshape(b, s, h, dn + dr)
    dkv = dense(x, p["w_dkv"], act_bits=act_bits, impl=impl)     # (B,S,L+dr)
    c_kv = rmsnorm(dkv[..., :lr], p["kv_norm"]["scale"])
    k_rope = dkv[..., lr:].reshape(b, s, 1, dr)
    cos, sin = rope_frequencies(dr, acfg.rope_base, positions)
    q_rope = apply_rope(q[..., dn:], cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    k_nope = jnp.einsum("btl,lhd->bthd", c_kv.astype(jnp.float32),
                        p["w_uk"].reshape(lr, h, dn).astype(jnp.float32)
                        ).astype(x.dtype)
    v = jnp.einsum("btl,lhd->bthd", c_kv.astype(jnp.float32),
                   p["w_uv"].reshape(lr, h, dv).astype(jnp.float32)
                   ).astype(x.dtype)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))],
                        axis=-1)
    qf = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    ctx = _attend(qf, k, v, None, None, (dn + dr) ** -0.5)
    out = dense(ctx, p["wo"], act_bits=act_bits, impl=impl)
    return (out, (c_kv, k_rope[:, :, 0])) if return_kv else out


@jax.named_scope("attention")
def mla_decode(x, p, acfg: AttnConfig, mla: MLAConfig, cache: dict, pos,
               act_bits=None, impl=None):
    """Absorbed one-token MLA: cache holds only (c_kv, k_rope)."""
    from .layers import rmsnorm
    b = x.shape[0]
    h = acfg.num_heads
    dn, dr, dv, lr = (mla.qk_nope_head_dim, mla.qk_rope_head_dim,
                      mla.v_head_dim, mla.kv_lora_rank)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lane = jnp.arange(b)
    q = dense(x, p["wq"], act_bits=act_bits, impl=impl).reshape(b, 1, h, dn + dr)
    dkv = dense(x, p["w_dkv"], act_bits=act_bits, impl=impl)
    c_kv = rmsnorm(dkv[..., :lr], p["kv_norm"]["scale"])         # (B,1,L)
    k_rope = dkv[..., lr:].reshape(b, 1, 1, dr)
    cos, sin = rope_frequencies(dr, acfg.rope_base, pos[:, None])
    q_rope = apply_rope(q[..., dn:], cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    with jax.named_scope("kv_write"):
        ckv_all = cache["c_kv"].at[lane, pos].set(c_kv[:, 0])
        kr_all = cache["k_rope"].at[lane, pos].set(k_rope[:, 0, 0])
        pos_all = cache["positions"].at[lane, pos].set(pos)      # (B, S)
    ckv_all = constrain(ckv_all, "batch", "kv_seq", None)
    # absorb q_nope through W_uk: (B,1,H,dn)·(L,H,dn) → (B,1,H,L)
    q_abs = jnp.einsum("bshd,lhd->bshl", q[..., :dn].astype(jnp.float32),
                       p["w_uk"].reshape(lr, h, dn).astype(jnp.float32))
    scores = (jnp.einsum("bshl,btl->bhst", q_abs,
                         ckv_all.astype(jnp.float32))
              + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                           kr_all.astype(jnp.float32))) * (dn + dr) ** -0.5
    ok = (pos_all >= 0) & (pos_all <= pos[:, None])
    scores = scores + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :]
    w = jax.nn.softmax(scores, axis=-1)
    ctx_l = jnp.einsum("bhst,btl->bshl", w, ckv_all.astype(jnp.float32))
    ctx = jnp.einsum("bshl,lhd->bshd", ctx_l,
                     p["w_uv"].reshape(lr, h, dv).astype(jnp.float32))
    ctx = ctx.reshape(b, 1, h * dv).astype(x.dtype)
    out = dense(ctx, p["wo"], act_bits=act_bits, impl=impl)
    return out, {"c_kv": ckv_all, "k_rope": kr_all, "positions": pos_all}


def mla_cache_init(batch: int, slots: int, mla: MLAConfig, dtype):
    return {
        "c_kv": jnp.zeros((batch, slots, mla.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, slots, mla.qk_rope_head_dim), dtype),
        "positions": jnp.full((batch, slots), -1, jnp.int32),
    }
