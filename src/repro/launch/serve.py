"""Serving launcher — batched generation, optionally through the MVDRAM
bit-plane engine (the paper's deployment mode).

    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b \
        --quantized --bits 2 --act-bits 4 --tokens 64

The quantized linears run the compiled Pallas kernels (`--backend pallas`,
TPU only). On a CPU, name `--backend pallas_interpret` (the kernel bodies
in interpret mode) or `--backend jnp` (the reference), with `--tiny`.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config, tiny_config
from ..core import backends
from ..models.model import param_defs
from ..models.params import init_params
from ..serve.engine import ServeEngine
from ..serve.quantize import init_quantized_params, serving_bytes

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache(root: pathlib.Path = REPO_ROOT) -> None:
    """Persistent compile cache: where JAX_COMPILATION_CACHE_DIR says (JAX
    reads the variable itself), else the fixed `<repo>/.jax_cache` — a path
    that does not move between runs, so later runs hit it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--quantized", action="store_true",
                    help="serve linears through the bit-plane engine")
    ap.add_argument("--backend", default="pallas",
                    choices=("pallas", "pallas_interpret", "jnp"),
                    help="how quantized linears execute")
    ap.add_argument("--bits", type=int, default=None)
    ap.add_argument("--act-bits", type=int, default=None)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = tiny_config(args.arch) if args.tiny else get_config(args.arch)
    if args.bits:
        cfg = dataclasses.replace(cfg, weight_bits=args.bits)
    if cfg.input_mode == "embeddings":
        raise SystemExit(f"{cfg.name} has a stubbed frontend; serve via "
                         "examples/serve_lowbit.py embedding driver")
    defs = param_defs(cfg)
    key = jax.random.PRNGKey(args.seed)
    # quantized: one leaf at a time, so the float model never exists whole
    params = (init_quantized_params(defs, key, cfg.weight_bits)
              if args.quantized else init_params(defs, key))
    print("serving bytes:", serving_bytes(defs, cfg.weight_bits))

    eng = ServeEngine(cfg, params,
                      max_seq=args.prompt_len + args.tokens + 1,
                      batch_slots=args.batch, quantized=args.quantized,
                      act_bits=args.act_bits,
                      impl=backends.get_backend(args.backend))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    out = eng.generate(prompts, max_new=args.tokens)
    print("generated shape:", out.shape)
    print(f"tokens/s on {jax.devices()[0].device_kind}:",
          round(eng.throughput_tokens_per_s(
              b=args.batch, n=min(args.tokens, 16)), 2))


if __name__ == "__main__":
    main()
