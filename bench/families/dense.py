"""The dense block family: GQA attention (q/k/v/o, optional q/k/v biases,
rotate-half RoPE) and a GLU (up/gate/down) or a plain MLP with biases
(up/down), under RMSNorm or LayerNorm. What the benchmark knows of a
configuration's shapes, for the configurations whose file says
`"family": "dense"`:

- `program_config`: the program's ModelConfig of the run;
- `forward_logits`: the plain float32 teacher-forced reference, built on
  the shared pieces of `harness/reference.py`: its parameters drawn from
  the seed (laid out by `leaf_layout`), its own quantization, one layer at
  a time, so a full-width model never has to be resident;
- `step_flops`, `step_bytes` and `kernel_calls`: operations and bytes of
  one inner decode step, from shapes alone (`linears`,
  `kv_bytes_per_position`; see `harness/counts.py`). Every launch runs in
  the `bitplane_gemv` kernel set. The window's program counters, which
  these three take, do not change a dense step's counts.

Those five are what the harness reads; the rest is this family's own.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import counts, reference
from harness.reference import qlinear

#: the kernel set of every launch of a dense step
KERNELS = "bitplane_gemv"


def program_config(a: dict):
    """The program's ModelConfig for a configuration's `as_run` block."""
    # the only import of the program here: the reference below uses none
    from repro.configs import get_config
    base = get_config(a["arch"])
    attn = dataclasses.replace(
        base.attn, num_heads=a["heads"], num_kv_heads=a["kv_heads"],
        head_dim=a["head_dim"], rope_base=a["rope_theta"],
        qkv_bias=a["qkv_bias"])
    return dataclasses.replace(
        base, num_layers=a["layers"], d_model=a["d_model"], d_ff=a["d_ff"],
        vocab_size=a["vocab"], attn=attn, weight_bits=a["weight_bits"],
        norm_type=a["norm"], ffn_type=a["ffn"],
        tie_embeddings=a["tie_embeddings"], dtype=a["dtype"])


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

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
    return reference.flatten_layout(tree)


def _norm(x, m: dict):
    if m["norm"] == "layernorm":
        return reference.layer_norm(x, m["norm_eps"])
    return reference.rms_norm(x, m["norm_eps"])


@functools.partial(jax.jit, static_argnames=("m", "act_bits"))
def _layer(x, lengths, p, *, m, act_bits):
    m = dict(m)
    b, s, _ = x.shape
    h = _norm(x, m)
    q = qlinear(h, p["wq"], act_bits) + p["bq"]
    k = qlinear(h, p["wk"], act_bits) + p["bk"]
    v = qlinear(h, p["wv"], act_bits) + p["bv"]
    hd = m["head_dim"]
    q = reference.rope(q.reshape(b, s, -1, hd), m["rope_theta"])
    k = reference.rope(k.reshape(b, s, -1, hd), m["rope_theta"])
    v = v.reshape(b, s, -1, hd)
    x = x + qlinear(reference.attention(q, k, v, lengths), p["wo"], act_bits)
    h = _norm(x, m)
    if m["ffn"] == "glu":
        a = reference.gelu_tanh(qlinear(h, p["gate"], act_bits)) * qlinear(
            h, p["up"], act_bits)
    else:
        a = reference.gelu_tanh(qlinear(h, p["up"], act_bits) + p["up_b"])
    return x + qlinear(a, p["down"], act_bits) + p["down_b"]


def _layer_params(wts: reference.Weights, m: dict, layer: int,
                  wbits: int) -> dict:
    """Layer `layer`'s weights, drawn and quantized in one call."""
    return _draw_layer(wts.keys, jnp.uint32(layer),
                       jnp.float32(reference.code_step(wbits)),
                       m=reference.freeze(m), wbits=wbits)


@functools.partial(jax.jit, static_argnames=("m", "wbits"))
def _draw_layer(keys, layer, step, *, m, wbits):
    m = dict(m)
    layout = {p: (i, shape, init)
              for i, (p, shape, init) in enumerate(leaf_layout(m))}

    def get(*path):
        return reference.layer_block(keys, layout, ("stages", "0") + path,
                                     layer)

    def quantized(*path):
        return reference.quantize_codes(get(*path), step, bits=wbits)

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


def forward_logits(as_run: dict, seed: int, tokens: np.ndarray,
                   lengths: np.ndarray, rows: np.ndarray,
                   variants: tuple) -> list:
    """Teacher-forced logits of `tokens` (B, S) at the flat positions `rows`
    (indices into B*S), one (len(rows), vocab) float32 array per variant.

    A variant is a (weight_bits, act_bits) pair; all variants share the
    seeded float weights and run layer by layer side by side."""
    m = as_run
    wts = reference.Weights(leaf_layout(m), seed)
    key = reference.freeze(m)
    embed = wts.get(("embed",))
    tok = jnp.asarray(tokens, jnp.int32)
    xs = [jnp.take(embed, tok, axis=0) for _ in variants]
    del embed
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in range(m["layers"]):
        params = {}
        for i, (wb, ab) in enumerate(variants):
            if wb not in params:
                params[wb] = _layer_params(wts, m, layer, wb)
            xs[i] = _layer(xs[i], lens, params[wb], m=key, act_bits=ab)
        del params
    head_w = (wts.get(("lm_head",)) if not m["tie_embeddings"]
              else wts.get(("embed",)).T)
    rows = jnp.asarray(rows, jnp.int32)
    out, heads = [], {}
    for x, (wb, ab) in zip(xs, variants):
        if wb not in heads:
            heads[wb] = reference.quantize_weight(head_w, bits=wb)
        h = _head_norm(x.reshape(-1, x.shape[-1])[rows], m=key)
        out.append(reference.head_logits(h, heads[wb], act_bits=ab))
    return out


# ---------------------------------------------------------------------------
# operations and bytes of one inner decode step
# ---------------------------------------------------------------------------

def linears(m: dict) -> list:
    """[(name, n, m, fused_group)] of every bit-plane linear of one step, in
    the order the step calls them; `fused_group` names the one-launch group
    (q/k/v, and up/gate for a GLU) or is None for a per-leaf launch."""
    E, F, V = m["d_model"], m["d_ff"], m["vocab"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    per_layer = [("wq", E, q, "qkv"), ("wk", E, kv, "qkv"),
                 ("wv", E, kv, "qkv"), ("wo", q, E, None)]
    if m["ffn"] == "glu":
        per_layer += [("up", E, F, "upgate"), ("gate", E, F, "upgate"),
                      ("down", F, E, None)]
    else:
        per_layer += [("up", E, F, None), ("down", F, E, None)]
    out = [(f"{name}#{layer}", n, mm, g and f"{g}#{layer}")
           for layer in range(m["layers"]) for name, n, mm, g in per_layer]
    return out + [("lm_head", E, V, None)]


def weight_bytes(m: dict, bits: int) -> int:
    return sum(counts.packed_bytes(n, mm, bits) for _, n, mm, _ in linears(m))


def linear_params(m: dict) -> int:
    return sum(n * mm for _, n, mm, _ in linears(m))


def kv_bytes_per_position(m: dict) -> int:
    """K and V of one position over all layers, at bf16."""
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * 2


def step_bytes(m: dict, bits: int, positions: list,
               counters: dict | None = None) -> int:
    """One inner step with active lanes at `positions` (each lane writes its
    position and attends to it and every earlier one): every packed weight
    once, the KV entries attended, the new entries written."""
    kv = kv_bytes_per_position(m)
    return (weight_bytes(m, bits)
            + sum((p + 1) * kv for p in positions) + len(positions) * kv)


def step_flops(m: dict, positions: list,
               counters: dict | None = None) -> int:
    """Model FLOPs of one inner step: one multiply-add per weight and active
    lane, and attention's scores and weighted sum over the attended
    positions."""
    attn = 4 * m["layers"] * m["heads"] * m["head_dim"]
    return sum(2 * linear_params(m) + attn * (p + 1) for p in positions)


def kernel_calls(m: dict, bits: int, act_bits: int, rows: int,
                 counters: dict | None = None) -> list:
    """[(launch, kernel set, bytes, ops)] of the bit-plane kernel launches of
    one executed step at `rows` activation rows (all lanes, frozen ones
    included: the kernels compute every row). A launch reads its packed
    planes and scales and its rows' activation codes once, and writes f32
    outputs; its ops are one multiply-add per weight and row."""
    code_bytes = math.ceil(act_bits / 8)
    launches: dict = {}
    for name, n, mm, group in linears(m):
        key = group or name
        b, o, n0 = launches.get(key, (0, 0, None))
        b += counts.packed_bytes(n, mm, bits) + rows * mm * 4
        if n0 is None:                 # a group reads its one input once
            b += rows * n * code_bytes
        launches[key] = (b, o + 2 * rows * n * mm, n)
    return [(k, KERNELS, b, o) for k, (b, o, _) in launches.items()]
