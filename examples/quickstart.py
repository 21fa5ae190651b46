"""Quickstart: the MVDRAM idea end-to-end in two minutes (CPU).

1.  Take one GeMV with low-bit weights.
2.  Run it three ways — bit-exact PUD command-stream simulation (what the
    paper's FPGA rig does inside unmodified DDR4), the pure-jnp bit-plane
    oracle, and the TPU Pallas kernel (interpret mode here) — and check they
    agree.
3.  Price the same GeMV on the calibrated DDR4 timing model vs the CPU/GPU
    baselines (the paper's Fig. 12 experiment).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import backends
from repro.core.engine import MVDRAMEngine
from repro.core.pud.gemv import PudGeometry
from repro.core.quant import QuantSpec

key = jax.random.PRNGKey(0)

# A small GeMV so the bit-level DRAM simulation stays fast. The engine's
# partition plan and pricing use the REAL geometry (65,536-column subarrays,
# 4 channels × 16 banks); the simulated subarray is narrowed to 256 columns.
N, M = 256, 48
w = jax.random.normal(key, (N, M), jnp.float32)
a = jax.random.normal(jax.random.fold_in(key, 1), (N,), jnp.float32)

engine = MVDRAMEngine(geom=PudGeometry(subarray_cols=256))
handle = engine.register("ffn_up", w, w_spec=QuantSpec(bits=3),
                         a_spec=QuantSpec(bits=4))

out_sim, report = engine.gemv(handle, a, backend=backends.SIM)
out_jnp = engine.gemv(handle, a, backend=backends.JNP)
out_pal = engine.gemv(handle, a[None], backend=backends.PALLAS_INTERPRET)[0]

print("=== correctness (three backends) ===")
print("PUD sim vs jnp oracle  max|Δ|:",
      float(jnp.abs(out_sim - out_jnp).max()))
print("Pallas  vs jnp oracle  max|Δ|:",
      float(jnp.abs(out_pal - out_jnp).max()))
print(f"command stream: {report.runtime.pud_ops} PUD ops over "
      f"{report.tiles} subarray tiles; {report.skipped_bits} zero "
      f"activation bits skipped (on-the-fly encoding, §V-D)")

print("\n=== pricing a production-size GeMV (paper Fig. 12 anchor) ===")
big = MVDRAMEngine()
h = big.register("llama_head", jnp.zeros((4096, 32000)),
                 w_spec=QuantSpec(bits=2), a_spec=QuantSpec(bits=1))
price = big.price(h)
print(f"MVDRAM total: {price['mvdram']['t_total']*1e3:.3f} ms "
      f"(paper: 0.19 ms)")
print(f"CPU baseline: {price['cpu_s']*1e3:.2f} ms (paper: 1.44 ms)")
print(f"speedup     : {price['cpu_s']/price['mvdram']['t_total']:.2f}x "
      f"(paper: 7.29x)")
print(f"conventional PUD would take "
      f"{price['conventional_pud']['t_total']*1e3:.2f} ms "
      f"(pre-arrange {price['conventional_pud']['t_prearrange']*1e3:.2f} ms)")
