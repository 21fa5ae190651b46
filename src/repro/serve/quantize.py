"""Model → MVDRAM serving transform.

Swaps every GeMV-shaped weight leaf for its packed bit-plane representation
(BitplaneWeights); `models.layers.dense` then routes those projections
through the bit-plane engine. Mirrors the paper's deployment: weights are
loaded once into the "computational memory" format (step ① of §IV), norms /
embeddings / router / SSM recurrence stay in floating point on the
"processor" side.

Routed-expert tensors are quantized per-expert (E-stacked bit-planes) and
served through models.moe._expert_mm — the per-expert GeMV batch of the
paper's low-bit path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.bitplane import BitplaneWeights, make_bitplane_weights
from ..core.quant import QuantSpec
from ..models.params import ParamDef, init_leaf
from .spans import phase

# weight-leaf basenames served by the bit-plane engine
# w_uk/w_uv stay fp: the MLA absorbed-decode path contracts them per-head
# (reshape + einsum), not through `dense`; they are the small low-rank
# factors (kv_lora_rank × H·d ≈ 1M params/layer) anyway.
QUANT_LEAF_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "w_dkv",
    "up", "gate", "down", "shared_up", "shared_gate", "shared_down",
    "in_proj", "out_proj", "lm_head",
    # routed experts: E-stacked bit-planes, served per-expert through
    # models.moe._expert_mm (vmap'd bit-plane GeMV)
    "w_up", "w_gate", "w_down",
})


def _walk(tree, fn, path=()):
    if isinstance(tree, dict):
        return {k: _walk(v, fn, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


def _quantize_leaf(w: jax.Array, bits: int) -> BitplaneWeights:
    spec = QuantSpec(bits=bits, group_size=-1)
    if w.ndim == 2:
        return make_bitplane_weights(w, spec)
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    # block on each layer: eager dispatch would otherwise run ahead of the
    # device and hold many layers' full-size quantize temporaries at once
    parts = [jax.block_until_ready(make_bitplane_weights(flat[i], spec))
             for i in range(flat.shape[0])]
    stack = lambda xs: jnp.stack(xs).reshape(lead + xs[0].shape)
    return BitplaneWeights(
        planes=stack([p.planes for p in parts]),
        scale=stack([p.scale for p in parts]),
        zero=parts[0].zero,
        col_sum=stack([p.col_sum for p in parts]),
        n=w.shape[-2], spec=spec)


def _servable(path, leaf) -> bool:
    return bool(path) and path[-1] in QUANT_LEAF_NAMES and leaf.ndim >= 2


def quantize_params(params, bits: int):
    """Concrete params → serving params (bit-plane leaves swapped in).
    Leaves that are already `BitplaneWeights` pass through unchanged."""
    def fn(path, leaf):
        if not isinstance(leaf, BitplaneWeights) and _servable(path, leaf):
            return _quantize_leaf(leaf, bits)
        return leaf
    return _walk(params, fn)


_init_leaf_jit = jax.jit(init_leaf, static_argnums=0)


def init_quantized_params(defs, key: jax.Array, bits: int):
    """`quantize_params(init_params(defs, key), bits)` one leaf at a time:
    each leaf is initialized (same per-leaf keys as `init_params`),
    quantized, and its float copy dropped before the next leaf, so peak
    memory is one float leaf plus the packed tree — a full-width model
    never exists in float32. The init is jitted so XLA fuses the random
    draw instead of materializing its intermediates at full leaf size."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    with phase("init"):
        keys = jax.random.split(key, len(leaves))
        vals = []
        for (path, d), k in zip(leaves, keys):
            leaf = _init_leaf_jit(d, k)
            names = tuple(getattr(p, "key", p) for p in path)
            if _servable(names, leaf):
                leaf = _quantize_leaf(leaf, bits)
            vals.append(leaf)
        jax.block_until_ready(vals)
    return jax.tree_util.tree_unflatten(treedef, vals)


def quantize_defs(defs, bits: int):
    """Abstract variant for .lower(): ParamDef tree → tree where servable
    leaves become BitplaneWeights over ShapeDtypeStructs (no allocation)."""
    def fn(path, d: ParamDef):
        sds = jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype))
        if not (path and path[-1] in QUANT_LEAF_NAMES and len(d.shape) >= 2):
            return sds
        *lead, n, m = d.shape
        spec = QuantSpec(bits=bits, group_size=-1)
        words = (n + 31) // 32
        return BitplaneWeights(
            planes=jax.ShapeDtypeStruct((*lead, bits, words, m), jnp.uint32),
            scale=jax.ShapeDtypeStruct((*lead, 1, m), jnp.float32),
            zero=spec.zero_point,
            col_sum=jax.ShapeDtypeStruct((*lead, m), jnp.int32),
            n=n, spec=spec)
    return _walk(
        jax.tree_util.tree_map(lambda d: d, defs,
                               is_leaf=lambda x: isinstance(x, ParamDef)),
        fn)


def serving_bytes(defs, bits: int) -> dict:
    """HBM bytes: bf16 dense vs packed bit-plane serving (the capacity win)."""
    dense_b = packed_b = 0
    def fn(path, d: ParamDef):
        nonlocal dense_b, packed_b
        size = d.size
        if path and path[-1] in QUANT_LEAF_NAMES and len(d.shape) >= 2:
            *lead, n, m = d.shape
            k = 1
            for x in lead:
                k *= x
            dense_b += size * 2
            packed_b += k * (bits * ((n + 31) // 32) * m * 4 + m * 4 + m * 4)
        else:
            dense_b += size * 2
            packed_b += size * 2
        return d
    _walk(jax.tree_util.tree_map(lambda d: d, defs,
                                 is_leaf=lambda x: isinstance(x, ParamDef)),
          fn)
    return {"dense_bf16": dense_b, "bitplane": packed_b,
            "ratio": dense_b / max(packed_b, 1)}
