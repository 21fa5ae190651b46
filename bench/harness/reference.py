"""Plain float32 reference of a served low-bit model, independent of the
program under test.

It rebuilds the model from the seed alone: the same seeded weight draw
(one truncated-normal leaf per parameter, keys split from the seed in the
parameter tree's sorted order), its own symmetric weight quantization and
its own per-token activation quantization. It never imports the program
and never reads an array the program made. Every quantized linear is an
exact integer product of centred codes (bf16 holds every code exactly and
the f32 accumulation stays below 2**24), times the two scales; attention,
norms, RoPE and the GELU are float32 at `highest` precision.

The forward pass is teacher-forced over whole token sequences (prompt and
served answer), one layer at a time, so a full-width model never has to be
resident: each layer's weights are drawn, quantized, used and dropped.
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


def leaf_layout(m: dict) -> list:
    """[(path, shape, init)] of every parameter leaf, in the sorted-path order
    in which the keys are split. `m` is the configuration's `as_run`."""
    L, E, F, V = m["layers"], m["d_model"], m["d_ff"], m["vocab"]
    H, KV, D = m["heads"], m["kv_heads"], m["head_dim"]

    def norm(stack):
        if m["norm"] == "layernorm":
            return {"bias": (stack + (E,), "zeros"),
                    "scale": (stack + (E,), "ones")}
        return {"scale": (stack + (E,), "zeros")}

    attn = {"wq": ((L, E, H * D), "normal"), "wk": ((L, E, KV * D), "normal"),
            "wv": ((L, E, KV * D), "normal"), "wo": ((L, H * D, E), "normal")}
    if m["qkv_bias"]:
        attn.update(bq=((L, H * D), "zeros"), bk=((L, KV * D), "zeros"),
                    bv=((L, KV * D), "zeros"))
    if m["ffn"] == "glu":
        ffn = {"up": ((L, E, F), "normal"), "gate": ((L, E, F), "normal"),
               "down": ((L, F, E), "normal")}
    else:
        ffn = {"up": ((L, E, F), "normal"), "up_b": ((L, F), "zeros"),
               "down": ((L, F, E), "normal"), "down_b": ((L, E), "zeros")}
    tree = {"embed": ((V, E), "normal"), "final_norm": norm(()),
            "stages": {"0": {"attn": attn, "ffn": ffn, "ln1": norm((L,)),
                             "ln2": norm((L,))}}}
    if not m["tie_embeddings"]:
        tree["lm_head"] = ((E, V), "normal")
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
def _normal_block(key, offset, std, *, shape):
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
    """The seeded parameters of one model, drawn on demand."""

    def __init__(self, as_run: dict, seed: int):
        self.m = as_run
        self.layout = {p: (i, shape, init) for i, (p, shape, init)
                       in enumerate(leaf_layout(as_run))}
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
        return _normal_block(self.keys[i], jnp.uint32(offset),
                             jnp.float32(std), shape=tuple(block))


# ---------------------------------------------------------------------------
# quantization: symmetric, per output column (weights) and per token
# (activations), codes centred on the mid level
# ---------------------------------------------------------------------------

def _step(bits: int) -> float:
    return max((1 << bits) // 2 - 0.5, 0.5)


def _zero(bits: int) -> int:
    return (1 << (bits - 1)) if bits > 1 else 0


def quantize_weight(w, bits: int):
    """(n, m) float → centred integer codes (as bf16, exact) and (m,) scale."""
    return _quantize_weight(w, jnp.float32(_step(bits)), bits=bits)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_weight(w, step, *, bits: int):
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
    s_a = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / _step(act_bits)
    c = jnp.clip(jnp.round(x / jnp.maximum(s_a, 1e-12)) + _zero(act_bits),
                 0, (1 << act_bits) - 1) - _zero(act_bits)
    acc = jnp.einsum("...n,nm->...m", c.astype(jnp.bfloat16), codes,
                     preferred_element_type=jnp.float32)
    return acc * w_scale * s_a


def _norm(x, m: dict):
    if m["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m["norm_eps"])
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + m["norm_eps"])


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi).astype(np.float32)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, theta: float):
    """x (B, S, H, D), rotate-half RoPE at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, lengths):
    """Causal GQA over each row's own tokens; q (B,S,H,D), k/v (B,S,KV,D)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, d)
    sc = jnp.einsum("bshgd,bthd->bhgst", qg, k, precision=HIGHEST) / math.sqrt(d)
    i = jnp.arange(s)
    ok = (i[None, :] <= i[:, None])[None] & (i[None, None, :] < lengths[:, None, None])
    sc = jnp.where(ok[:, None, None], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bhgst,bthd->bshgd", w, v, precision=HIGHEST)
    return ctx.reshape(b, s, h * d)


@functools.partial(jax.jit, static_argnames=("m", "act_bits"))
def _layer(x, lengths, p, *, m, act_bits):
    m = dict(m)
    b, s, _ = x.shape
    h = _norm(x, m)
    q = qlinear(h, p["wq"], act_bits) + p["bq"]
    k = qlinear(h, p["wk"], act_bits) + p["bk"]
    v = qlinear(h, p["wv"], act_bits) + p["bv"]
    hd = m["head_dim"]
    q = _rope(q.reshape(b, s, -1, hd), m["rope_theta"])
    k = _rope(k.reshape(b, s, -1, hd), m["rope_theta"])
    v = v.reshape(b, s, -1, hd)
    x = x + qlinear(_attention(q, k, v, lengths), p["wo"], act_bits)
    h = _norm(x, m)
    if m["ffn"] == "glu":
        a = _gelu_tanh(qlinear(h, p["gate"], act_bits)) * qlinear(
            h, p["up"], act_bits)
    else:
        a = _gelu_tanh(qlinear(h, p["up"], act_bits) + p["up_b"])
    return x + qlinear(a, p["down"], act_bits) + p["down_b"]


def _freeze(m: dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def _layer_params(wts: Weights, layer: int, wbits: int) -> dict:
    """Layer `layer`'s weights, drawn and quantized in one call."""
    return _draw_layer(wts.keys, jnp.uint32(layer), jnp.float32(_step(wbits)),
                       m=_freeze(wts.m), wbits=wbits)


@functools.partial(jax.jit, static_argnames=("m", "wbits"))
def _draw_layer(keys, layer, step, *, m, wbits):
    m = dict(m)
    layout = {p: (i, shape, init)
              for i, (p, shape, init) in enumerate(leaf_layout(m))}

    def get(*path):
        i, shape, init = layout[("stages", "0") + path]
        block = shape[1:]
        if init == "zeros":
            return jnp.zeros(block, jnp.float32)
        if init == "ones":
            return jnp.ones(block, jnp.float32)
        return _normal_block(keys[i], layer * jnp.uint32(math.prod(block)),
                             jnp.float32(1.0 / math.sqrt(shape[-2])),
                             shape=tuple(block))

    def quantized(*path):
        return _quantize_weight(get(*path), step, bits=wbits)

    p = {name: quantized("attn", name) for name in ("wq", "wk", "wv", "wo")}
    for name, width in (("bq", m["heads"]), ("bk", m["kv_heads"]),
                        ("bv", m["kv_heads"])):
        p[name] = (get("attn", name) if m["qkv_bias"]
                   else jnp.zeros((width * m["head_dim"],), jnp.float32))
    glu = m["ffn"] == "glu"
    for name in ("up", "gate", "down") if glu else ("up", "down"):
        p[name] = quantized("ffn", name)
    p["up_b"] = (jnp.zeros((m["d_ff"],), jnp.float32) if glu
                 else get("ffn", "up_b"))
    p["down_b"] = (jnp.zeros((m["d_model"],), jnp.float32) if glu
                   else get("ffn", "down_b"))
    return p


@functools.partial(jax.jit, static_argnames=("m",))
def _head_norm(x, *, m):
    return _norm(x, dict(m))


@functools.partial(jax.jit, static_argnames=("act_bits",))
def _logits(x, head, *, act_bits):
    return qlinear(x, head, act_bits)


def forward_logits(as_run: dict, seed: int, tokens: np.ndarray,
                   lengths: np.ndarray, rows: np.ndarray,
                   variants: tuple) -> list:
    """Teacher-forced logits of `tokens` (B, S) at the flat positions `rows`
    (indices into B*S), one (len(rows), vocab) float32 array per variant.

    A variant is a (weight_bits, act_bits) pair; all variants share the
    seeded float weights and run layer by layer side by side."""
    m = as_run
    wts = Weights(m, seed)
    key = _freeze(m)
    embed = wts.get(("embed",))
    tok = jnp.asarray(tokens, jnp.int32)
    xs = [jnp.take(embed, tok, axis=0) for _ in variants]
    del embed
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in range(m["layers"]):
        params = {}
        for i, (wb, ab) in enumerate(variants):
            if wb not in params:
                params[wb] = _layer_params(wts, layer, wb)
            xs[i] = _layer(xs[i], lens, params[wb], m=key, act_bits=ab)
        del params
    head_w = (wts.get(("lm_head",)) if not m["tie_embeddings"]
              else wts.get(("embed",)).T)
    rows = jnp.asarray(rows, jnp.int32)
    out, heads = [], {}
    for x, (wb, ab) in zip(xs, variants):
        if wb not in heads:
            heads[wb] = quantize_weight(head_w, bits=wb)
        h = _head_norm(x.reshape(-1, x.shape[-1])[rows], m=key)
        out.append(_logits(h, heads[wb], act_bits=ab))
    return out


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
