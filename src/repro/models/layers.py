"""Shared building blocks: norms, rotary embeddings, FFNs, embedding table,
and the quantization-aware `dense` — the single choke point through which
every GeMV-shaped projection runs, so the MVDRAM bit-plane engine can take
over any linear layer at serving time by swapping the weight leaf.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import backends
from ..core.bitplane import BitplaneWeights
from ..core.quant import QuantSpec, QuantizedTensor
from ..parallel.sharding import constrain


def dense(x: jax.Array, w, b: Optional[jax.Array] = None,
          act_bits: Optional[int] = None, impl=None) -> jax.Array:
    """x (..., N) @ w (N, M). `w` may be:

      jnp.ndarray        — dense matmul (training / bf16 serving)
      BitplaneWeights    — MVDRAM bit-plane engine (float or bit-serial acts)
      QuantizedTensor    — fused-dequant baseline kernel

    `impl` is a `core.backends.Backend` (or None for the default backend,
    resolved through the registry — no backend-name literals live here), a
    kernel-registry impl string, or a callable `(x, w, act_bits) -> out`
    (e.g. `core.engine.EngineLinear`) that routes every BitplaneWeights
    linear — the serve batch's lane-batched GeMVs — through the MVDRAM
    engine; non-bitplane leaves fall back to the callable's `.mode` string.
    """
    if isinstance(w, BitplaneWeights):
        if callable(impl):
            out = impl(x, w, act_bits).astype(x.dtype)
        else:
            from ..kernels.bitplane_gemv import ops as bp
            impl = backends.resolve_impl(impl)
            if act_bits:
                out = bp.bitplane_gemv_bitserial(
                    x, w, QuantSpec(bits=act_bits), impl=impl)
            else:
                out = bp.bitplane_gemv(x, w, impl=impl)
            out = out.astype(x.dtype)
    elif isinstance(w, QuantizedTensor):
        from ..kernels.quant_matmul import ops as qm
        impl = backends.resolve_impl(getattr(impl, "mode", impl))
        out = qm.quant_matmul(x, w, impl=impl).astype(x.dtype)
    else:
        out = jnp.einsum("...n,nm->...m", x, w.astype(x.dtype))
    if b is not None:
        out = out + b.astype(out.dtype)
    return out


def dense_group(x: jax.Array, ws, bs=None, act_bits: Optional[int] = None,
                impl=None) -> tuple:
    """k independent projections of ONE input — q/k/v, up/gate — the
    grouped analogue of `dense`. An `impl` exposing a `.group` hook (an
    `EngineLinear`: its Pallas backends fuse the group's BitplaneWeights
    into ONE kernel launch, mirroring the compiled decode program's
    concurrency groups) takes the fused path; anything else falls back to
    per-leaf `dense` with identical results."""
    ws = tuple(ws)
    bs = tuple(bs) if bs is not None else (None,) * len(ws)
    group = getattr(impl, "group", None)
    if (group is not None and act_bits and len(ws) > 1
            and all(isinstance(w, BitplaneWeights) for w in ws)):
        outs = [o.astype(x.dtype) for o in group(x, ws, act_bits)]
        return tuple(o if b is None else o + b.astype(o.dtype)
                     for o, b in zip(outs, bs))
    return tuple(dense(x, w, b, act_bits, impl) for w, b in zip(ws, bs))


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
            zero_centered: bool = True) -> jax.Array:
    """RMSNorm with (1+γ) parametrization (gemma/llama-compatible)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    g = scale.astype(jnp.float32)
    y = y * (1.0 + g) if zero_centered else y * g
    return y.astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


@jax.named_scope("norm")
def norm(x, p, norm_type: str):
    if norm_type == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# -- rotary ------------------------------------------------------------------

def rope_frequencies(dim: int, base: float, positions: jax.Array) -> tuple:
    """positions (...,) → cos/sin (..., dim/2) for rotate-half RoPE."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               rope_dim: Optional[int] = None) -> jax.Array:
    """x (..., S, H, D); cos/sin (..., S, d/2) broadcast over heads."""
    d = rope_dim or x.shape[-1]
    xr, xp = x[..., :d], x[..., d:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]      # add head axis
    rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), xp], axis=-1)


# -- FFN ---------------------------------------------------------------------

@jax.named_scope("ffn")
def ffn(x: jax.Array, p, ffn_type: str, act_bits=None, impl=None):
    """GLU (SwiGLU/GeGLU) or classic 2-layer MLP."""
    if ffn_type == "glu":
        up, gate = dense_group(x, (p["up"], p["gate"]), act_bits=act_bits,
                               impl=impl)
        h = jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = dense(x, p["up"], p.get("up_b"), act_bits=act_bits, impl=impl)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = constrain(h, "batch", "seq", "mlp")
    return dense(h, p["down"], p.get("down_b"), act_bits=act_bits, impl=impl)


# -- embedding / head --------------------------------------------------------

def embed(tokens: jax.Array, table: jax.Array, scale: bool,
          d_model: int) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(d_model, x.dtype) ** 0.5
    return x


def lm_head(x: jax.Array, w, cap: Optional[float],
            act_bits=None, impl=None) -> jax.Array:
    logits = dense(x, w, act_bits=act_bits, impl=impl).astype(jnp.float32)
    logits = softcap(logits, cap)
    return constrain(logits, "batch", "seq", "vocab")
