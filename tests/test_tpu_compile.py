"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e, at
llama2-7b widths (d_model 4096, d_ff 11008), with no chip attached.

Interpret mode cannot see Mosaic's tiling rules or its scoped-VMEM limit;
the TPU compiler can, and it is installed here. Each case compiles one
kernel for one described v5e chip and checks that the program holds the
kernel (`tpu_custom_call`). The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the one that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bitplane import BitplaneWeights
from repro.core.quant import QuantSpec
from repro.kernels.bitplane_gemv import ops, program

D_MODEL, D_FF = 4096, 11008
ACT_BITS = 4
Z_A = QuantSpec(bits=ACT_BITS).zero_point


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weights(sharding, n, m, bits):
    """Abstract packed weights, as `serve.quantize.quantize_defs` builds."""
    spec = QuantSpec(bits=bits, group_size=-1)
    return BitplaneWeights(
        planes=_sds(sharding, (bits, (n + 31) // 32, m), jnp.uint32),
        scale=_sds(sharding, (1, m), jnp.float32), zero=spec.zero_point,
        col_sum=_sds(sharding, (m,), jnp.int32), n=n, spec=spec)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bits", [2, 4, 1, 3, 8])
@pytest.mark.parametrize("rows", [4, 512], ids=["decode", "prefill_chunk"])
def test_code_kernel_compiles(one_chip, bits, rows):
    """The per-leaf §V-D code kernel: a 4-lane decode step and a 512-row
    prefill chunk (the activation-row axis is tiled) on the d_ff-wide up
    projection. Widths 1, 3 and 8 build their code tiles from 2-, 4- and
    8-bit fields."""
    _compile(lambda a, w: ops.bitplane_gemv_codes(a, w, ACT_BITS, Z_A,
                                                  impl="pallas"),
             _sds(one_chip, (rows, D_MODEL), jnp.uint8),
             _weights(one_chip, D_MODEL, D_FF, bits))


@pytest.mark.parametrize("bits", [2, 4])
def test_float_kernel_compiles(one_chip, bits):
    """The float-activation kernel on the d_ff → d_model down projection."""
    _compile(lambda a, w: ops.bitplane_gemv(a, w, impl="pallas"),
             _sds(one_chip, (4, D_FF), jnp.bfloat16),
             _weights(one_chip, D_FF, D_MODEL, bits))


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("widths", [(D_MODEL,) * 3, (D_FF,) * 2],
                         ids=["qkv", "up_gate"])
def test_fused_group_compiles(one_chip, bits, widths):
    """`fused_group_linears` — one launch for q/k/v and one for up/gate,
    as the served decode step issues them."""
    ws = tuple(_weights(one_chip, D_MODEL, m, bits) for m in widths)
    _compile(lambda x, ws: program.fused_group_linears(x, ws, ACT_BITS),
             _sds(one_chip, (4, D_MODEL), jnp.bfloat16), ws)


@pytest.mark.parametrize("n,widths,bits", [
    (18944, (3584,), 2),            # qwen2-7b down: a 2.4 MB cell
    (12288, (3072,), 4),            # starcoder2-3b down: a 3.1 MB cell
    (3584, (18944,) * 2, 2),        # qwen2-7b up/gate
    (3072, (3072, 256, 256), 4),    # starcoder2-3b q/k/v: 256-wide cells
], ids=["qwen2_down", "starcoder2_down", "qwen2_up_gate", "starcoder2_qkv"])
def test_served_cells_compile(one_chip, n, widths, bits):
    """The largest grid cells of the benchmark's configurations, each the
    whole reduction by 512 (256) output columns, within the default scoped
    VMEM that Mosaic enforces here."""
    ws = tuple(_weights(one_chip, n, m, bits) for m in widths)
    if len(ws) == 1:
        _compile(lambda a, w: ops.bitplane_gemv_codes(a, w, ACT_BITS, Z_A,
                                                      impl="pallas"),
                 _sds(one_chip, (4, n), jnp.uint8), ws[0])
    else:
        _compile(lambda x, ws: program.fused_group_linears(x, ws, ACT_BITS),
                 _sds(one_chip, (4, n), jnp.bfloat16), ws)
