"""The shared pieces of the plain float32 references of served low-bit
models, independent of the program under test.

A block family (`families/<family>.py`) builds its reference from these:
the seeded weight draw (one truncated-normal leaf per parameter, keys split
from the seed in the parameter tree's sorted order, drawn one (n, m) block
of a stacked leaf at a time), symmetric weight quantization, the quantized
linear with its per-token activation quantization, the norms, RoPE, causal
attention and the comparison of served tokens with the reference's logits.
None of it imports the program or reads an array the program made. Every
quantized linear is an exact integer product of centred codes (bf16 holds
every code exactly and the f32 accumulation stays below 2**24), times the
two scales; attention, norms, RoPE and the GELU are float32 at `highest`
precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the seeded weight draw, one (n, m) block of a stacked leaf at a time
# ---------------------------------------------------------------------------

def model_key(seed: int) -> jax.Array:
    """The model key of a run: the seed's low 32 bits (a raw uint32 pair)."""
    return jax.random.PRNGKey(int(seed) % (1 << 32))


def flatten_layout(tree: dict) -> list:
    """[(path, shape, init)] of a {name: subtree | (shape, init)} tree, in
    the sorted-path order in which the keys are split."""
    out = []

    def walk(t, path):
        if isinstance(t, dict):
            for k in sorted(t):
                walk(t[k], path + (k,))
        else:
            out.append((path, t[0], t[1]))
    walk(tree, ())
    return out


@functools.partial(jax.jit, static_argnames=("shape",))
def normal_block(key, offset, std, *, shape):
    """Elements [offset, offset + prod(shape)) of the flat truncated-normal
    draw `jax.random.truncated_normal(key, -3, 3, full_shape) * std`
    (partitionable threefry: element i's bits hash the counter i), without
    drawing the rest of the leaf."""
    n = math.prod(shape)
    lo = (jnp.arange(n, dtype=jnp.uint32) + offset).reshape(shape)
    hi = jnp.zeros(shape, jnp.uint32)
    k1 = jnp.broadcast_to(key[0], shape)
    k2 = jnp.broadcast_to(key[1], shape)
    b1, b2 = threefry2x32_p.bind(k1, k2, hi, lo)
    bits = b1 ^ b2
    f = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    sqrt2 = np.array(np.sqrt(2), np.float32)
    a = jax.lax.erf(np.float32(-3) / sqrt2)
    b = jax.lax.erf(np.float32(3) / sqrt2)
    u = jnp.maximum(a, f * (b - a) + a)
    out = sqrt2 * jax.lax.erf_inv(u)
    out = jnp.clip(out, jnp.nextafter(np.float32(-3), np.float32(np.inf)),
                   jnp.nextafter(np.float32(3), np.float32(-np.inf)))
    return out * std


class Weights:
    """The seeded parameters of one model, drawn on demand. `layout` is its
    family's `leaf_layout`."""

    def __init__(self, layout: list, seed: int):
        self.layout = {p: (i, shape, init) for i, (p, shape, init)
                       in enumerate(layout)}
        self.keys = jax.random.split(model_key(seed), len(self.layout))

    def get(self, path: tuple, layer=None) -> jax.Array:
        """Leaf `path` (layer `layer` of a stacked leaf), float32."""
        i, shape, init = self.layout[path]
        block = shape[1:] if layer is not None else shape
        if init == "zeros":
            return jnp.zeros(block, jnp.float32)
        if init == "ones":
            return jnp.ones(block, jnp.float32)
        std = 1.0 / math.sqrt(shape[-2])
        offset = (layer or 0) * math.prod(block)
        return normal_block(self.keys[i], jnp.uint32(offset),
                            jnp.float32(std), shape=tuple(block))


def layer_block(keys, layout: dict, path: tuple, layer) -> jax.Array:
    """Inside a traced draw: layer `layer` (a traced uint32) of the stacked
    leaf `path`; `layout` maps a path to its (key index, shape, init)."""
    i, shape, init = layout[path]
    block = shape[1:]
    if init == "zeros":
        return jnp.zeros(block, jnp.float32)
    if init == "ones":
        return jnp.ones(block, jnp.float32)
    return normal_block(keys[i], layer * jnp.uint32(math.prod(block)),
                        jnp.float32(1.0 / math.sqrt(shape[-2])),
                        shape=tuple(block))


# ---------------------------------------------------------------------------
# quantization: symmetric, per output column (weights) and per token
# (activations), codes centred on the mid level
# ---------------------------------------------------------------------------

def code_step(bits: int) -> float:
    """The step of `bits`-bit symmetric codes, in units of their scale."""
    return max((1 << bits) // 2 - 0.5, 0.5)


def _zero(bits: int) -> int:
    return (1 << (bits - 1)) if bits > 1 else 0


def quantize_weight(w, bits: int):
    """(n, m) float → centred integer codes (as bf16, exact) and (m,) scale."""
    return quantize_codes(w, jnp.float32(code_step(bits)), bits=bits)


@functools.partial(jax.jit, static_argnames=("bits",))
def quantize_codes(w, step, *, bits: int):
    """`quantize_weight` with its step given: inside a traced draw, pass
    `jnp.float32(code_step(bits))` in as an argument."""
    # `step` is an argument, not a constant: a constant divisor would be
    # compiled into a multiply by its rounded reciprocal
    scale = jnp.max(jnp.abs(w), axis=0) / step
    codes = jnp.clip(jnp.round(w / jnp.maximum(scale, 1e-12)) + _zero(bits),
                     0, (1 << bits) - 1)
    return (codes - _zero(bits)).astype(jnp.bfloat16), scale


def qlinear(x, wq, act_bits: int):
    """x (..., n) float32 through quantized weights `wq` = (codes, scale):
    per-token symmetric act_bits codes, an exact integer product, both
    scales."""
    codes, w_scale = wq
    s_a = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / code_step(act_bits)
    c = jnp.clip(jnp.round(x / jnp.maximum(s_a, 1e-12)) + _zero(act_bits),
                 0, (1 << act_bits) - 1) - _zero(act_bits)
    acc = jnp.einsum("...n,nm->...m", c.astype(jnp.bfloat16), codes,
                     preferred_element_type=jnp.float32)
    return acc * w_scale * s_a


@functools.partial(jax.jit, static_argnames=("act_bits",))
def head_logits(x, head, *, act_bits):
    """The output head: `qlinear` of the final hidden states."""
    return qlinear(x, head, act_bits)


# ---------------------------------------------------------------------------
# the float32 pieces of a block
# ---------------------------------------------------------------------------

def layer_norm(x, eps: float):
    """LayerNorm at its initial gain 1 and bias 0."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rms_norm(x, eps: float):
    """RMSNorm at its initial gain 1."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi).astype(np.float32)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, theta: float):
    """x (B, S, H, D), rotate-half RoPE at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, lengths):
    """Causal attention over each row's own tokens, each group of query
    heads sharing one key/value head; q (B,S,H,D), k (B,S,KV,D), v
    (B,S,KV,Dv) → (B, S, H*Dv)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, d)
    sc = jnp.einsum("bshgd,bthd->bhgst", qg, k, precision=HIGHEST) / math.sqrt(d)
    i = jnp.arange(s)
    ok = (i[None, :] <= i[:, None])[None] & (i[None, None, :] < lengths[:, None, None])
    sc = jnp.where(ok[:, None, None], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bhgst,bthd->bshgd", w, v, precision=HIGHEST)
    return ctx.reshape(b, s, -1)


def freeze(m: dict) -> tuple:
    """The scalar entries of a configuration's `as_run`, hashable: the
    static argument of a family's jitted layer."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


@jax.jit
def token_gaps(logits, tokens):
    """Per row: how far the token's logit lies below the row's best, that gap
    in units of the row's logit standard deviation, and the token's rank
    (0 = the reference's own first choice)."""
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    gap = best - mine
    std = jnp.std(logits, axis=-1)
    rank = jnp.sum(logits > mine[:, None], axis=-1)
    return gap, gap / std, rank
