"""Operations and bytes of one inner decode step, from shapes alone.

Every count is a minimum: what any implementation of the step has to read
and compute, so a time derived from it can only lie at or below the time
the chip really took. The packed-weight arithmetic is that of the
program's `serving_bytes`, without `col_sum`, which the kernels never read.
"""
from __future__ import annotations

import math


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


def packed_bytes(n: int, m: int, bits: int) -> int:
    """HBM bytes of one packed weight: its bit planes and its scales."""
    return bits * math.ceil(n / 32) * m * 4 + m * 4


def weight_bytes(m: dict, bits: int) -> int:
    return sum(packed_bytes(n, mm, bits) for _, n, mm, _ in linears(m))


def linear_params(m: dict) -> int:
    return sum(n * mm for _, n, mm, _ in linears(m))


def kv_bytes_per_position(m: dict) -> int:
    """K and V of one position over all layers, at bf16."""
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * 2


def step_bytes(m: dict, bits: int, positions: list) -> int:
    """One inner step with active lanes at `positions` (each lane writes its
    position and attends to it and every earlier one): every packed weight
    once, the KV entries attended, the new entries written."""
    kv = kv_bytes_per_position(m)
    return (weight_bytes(m, bits)
            + sum((p + 1) * kv for p in positions) + len(positions) * kv)


def step_flops(m: dict, positions: list) -> int:
    """Model FLOPs of one inner step: one multiply-add per weight and active
    lane, and attention's scores and weighted sum over the attended
    positions."""
    attn = 4 * m["layers"] * m["heads"] * m["head_dim"]
    return sum(2 * linear_params(m) + attn * (p + 1) for p in positions)


def step_roofline_s(m: dict, bits: int, positions: list, peak: dict) -> float:
    """The least time one inner step can take on a chip with `peak`."""
    return max(step_flops(m, positions) / peak["flops_per_s"],
               step_bytes(m, bits, positions) / peak["hbm_bytes_per_s"])


def kernel_calls(m: dict, bits: int, act_bits: int, rows: int) -> list:
    """[(launch, bytes, ops)] of the bit-plane kernel launches of one executed
    step at `rows` activation rows (all lanes, frozen ones included: the
    kernels compute every row). A launch reads its packed planes and scales
    and its rows' activation codes once, and writes f32 outputs; its ops
    are one multiply-add per weight and row."""
    code_bytes = math.ceil(act_bits / 8)
    launches: dict = {}
    for name, n, mm, group in linears(m):
        key = group or name
        b, o, n0 = launches.get(key, (0, 0, None))
        b += packed_bytes(n, mm, bits) + rows * mm * 4
        if n0 is None:                 # a group reads its one input once
            b += rows * n * code_bytes
        launches[key] = (b, o + 2 * rows * n * mm, n)
    return [(k, b, o) for k, (b, o, _) in launches.items()]


def kernel_roofline_s(m: dict, bits: int, act_bits: int, rows: int,
                      peak: dict) -> float:
    """Least time of one executed step's bit-plane kernel launches."""
    return sum(max(o / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
               for _, b, o in kernel_calls(m, bits, act_bits, rows))
