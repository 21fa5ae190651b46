"""Bytes and operations of one inner step, against figures worked by hand,
as the dense block family counts them."""
import json
import os

import _paths  # noqa: F401
from harness import counts, spec

CONFIGS = os.path.join(_paths.BENCH, "configs")
dense = spec.family("dense")


def _as_run(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["as_run"]


def test_qwen2_7b_w2_step():
    a = _as_run("qwen2-7b-w2a4")
    # per layer: q and o 3584x3584, k and v 3584x512, up/gate/down 3584x18944
    layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    params = 28 * layer + 3584 * 152064
    assert dense.linear_params(a) == params == 7_070_285_824
    # 2-bit planes are n*m/4 bytes; one f32 scale per output column
    scales = 4 * (28 * (3584 + 512 + 512 + 3584 + 18944 + 18944 + 3584)
                  + 152064)
    assert dense.weight_bytes(a, 2) == params // 4 + scales == 1_773_742_080
    kv = 2 * 28 * 4 * 128 * 2
    assert dense.kv_bytes_per_position(a) == kv == 57_344
    # two lanes writing positions 10 and 20: 11 + 21 entries read, 2 written
    assert dense.step_bytes(a, 2, [10, 20]) == 1_773_742_080 + 34 * kv
    attn = 4 * 28 * 28 * 128
    assert dense.step_flops(a, [10, 20]) == 2 * 2 * params + attn * 32


def test_starcoder2_3b_w4_step():
    a = _as_run("starcoder2-3b-w4a4")
    layer = 2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288
    params = 30 * layer + 3072 * 49152
    assert dense.linear_params(a) == params == 3_029_336_064
    scales = 4 * (30 * (3072 + 256 + 256 + 3072 + 12288 + 3072) + 49152)
    assert dense.weight_bytes(a, 4) == params // 2 + scales == 1_517_506_560
    assert dense.kv_bytes_per_position(a) == 2 * 30 * 2 * 128 * 2
    assert dense.step_bytes(a, 4, []) == 1_517_506_560
    assert dense.step_flops(a, [0]) == 2 * params + 4 * 30 * 24 * 128


def test_kernel_launches_of_one_step():
    a = _as_run("qwen2-7b-w2a4")
    step = dense.kernel_calls(a, 2, 4, 4)
    assert {kset for _, kset, _, _ in step} == {"bitplane_gemv"}
    calls = {k: (b, o) for k, _, b, o in step}
    # 28 layers of q/k/v, o, up/gate, down, and the lm_head
    assert len(calls) == 28 * 4 + 1
    qkv = (3584 * 3584 + 2 * 3584 * 512)
    b, o = calls["qkv#0"]
    # planes and scales of the three, the input's codes once, f32 outputs
    assert b == qkv // 4 + 4 * 4608 + 4 * 3584 + 4 * 4608 * 4
    assert o == 2 * 4 * qkv
    b, o = calls["lm_head"]
    assert b == 3584 * 152064 // 4 + 4 * 152064 + 4 * 3584 + 4 * 152064 * 4
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # memory-bound at 4 rows: the least time is the bytes over bandwidth
    total = sum(b for b, _ in calls.values())
    sets = counts.kernel_roofline_s(dense, a, 2, 4, 4, peak)
    assert list(sets) == ["bitplane_gemv"]
    assert sets["bitplane_gemv"]["launches_per_step"] == len(calls)
    assert abs(sets["bitplane_gemv"]["kernel_step_s"]
               - total / 819e9) < 1e-12
