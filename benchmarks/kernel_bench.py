"""Large-shape correctness check of the interpret-mode bit-plane kernel
against the jnp oracle (CPU; no timing: kernel speed is measured on the
chip by `bench/run.py`)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.bitplane import make_bitplane_weights
from repro.core.quant import QuantSpec
from repro.kernels.bitplane_gemv import ops as bp


def kernel_interpret_check(emit):
    rng = np.random.default_rng(0)
    n, b = 4096, 4
    w = jnp.asarray(rng.normal(size=(n, 512)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=4))
    ref = bp.bitplane_gemv(a, bw, impl="jnp")
    got = bp.bitplane_gemv(a, bw, impl="pallas_interpret")
    err = float(jnp.abs(ref - got).max() / (jnp.abs(ref).max() + 1e-9))
    emit("kernel.interpret_vs_oracle_relerr", err, "must be ~1e-6")
    assert err < 1e-4


ALL = [kernel_interpret_check]
