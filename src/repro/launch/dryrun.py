import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract the roofline terms.

MUST be run as its own process (`python -m repro.launch.dryrun --arch …`),
on a CPU host and never on a chip — the first two lines above force 512
host devices BEFORE jax initializes;
nothing else in the repo sets this flag (smoke tests and benchmarks see the
real single device).

Per cell this produces a JSON record with:
  memory_analysis      per-device argument/output/temp/peak bytes
  cost_analysis        HLO FLOPs + bytes accessed (per-device, SPMD)
  collective_bytes     Σ operand bytes of all-gather / all-reduce /
                       reduce-scatter / all-to-all / collective-permute in
                       the post-optimization HLO (per-device shard sizes)
  roofline             compute / memory / collective times on v5e constants
                       + MODEL_FLOPS = 6·N_active·D and usefulness ratio
"""
import argparse
import dataclasses
import json
import re
import time
from functools import partial

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, cells, get_config
from .hlo_analysis import analyze_hlo
from ..core.pud.timing import TPU_V5E
from ..data.pipeline import SyntheticLM
from ..models.model import Model, param_defs, stack_plan
from ..models.params import abstract_params, count_params, param_bytes
from ..optim.adamw import AdamWConfig
from ..parallel.sharding import (LONG_CONTEXT_RULES, axis_rules,
                                 defs_to_shardings, logical_to_pspec)
from ..serve.engine import cache_pspecs, make_serve_step
from ..train.step import make_train_step
from .mesh import make_production_mesh

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_of(hlo_text: str) -> dict:
    """Per-op-kind Σ operand bytes from post-optimization HLO."""
    out = {k: 0 for k in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in COLLECTIVE_OPS:
            # match "= <shape> kind(" and "kind-start(" variants
            if re.search(rf"= [^=]*\b{kind}(-start)?\(", stripped):
                inside = stripped.split("(", 1)[1]
                shapes = _SHAPE_RE.findall(inside)
                if not shapes:  # operands referenced w/o types: use result
                    shapes = _SHAPE_RE.findall(stripped.split("=")[1]
                                               .split("(")[0])
                out[kind] += sum(_shape_bytes(d, s) for d, s in shapes)
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in COLLECTIVE_OPS)
    return out


def roofline(flops: float, bytes_hbm: float, coll_bytes: float,
             model_flops: float, chips: int) -> dict:
    """All inputs are PER-DEVICE (SPMD HLO); model_flops is global."""
    t_c = flops / TPU_V5E.peak_flops_bf16
    t_m = bytes_hbm / TPU_V5E.hbm_bw
    t_x = coll_bytes / TPU_V5E.ici_bw
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    useful = model_flops / max(flops * chips, 1.0)
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bottleneck": dom[1], "bound_s": dom[0],
            "model_flops_global": model_flops,
            "useful_flops_ratio": useful,
            "roofline_fraction": (model_flops / chips
                                  / TPU_V5E.peak_flops_bf16) / max(dom[0],
                                                                   1e-30)}


def model_flops_for(cfg, profile, n_active: int) -> float:
    """6·N_active·D for training; 2·N_active·D per generated/processed token
    at inference."""
    tokens = profile.global_batch * profile.seq_len
    if profile.kind == "train":
        return 6.0 * n_active * tokens
    if profile.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * profile.global_batch  # decode: one token/lane


def _mem_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    out["peak_bytes_estimate"] = (out.get("argument_size_in_bytes", 0)
                                  + out.get("output_size_in_bytes", 0)
                                  + out.get("temp_size_in_bytes", 0)
                                  - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape: str, multi_pod: bool,
             microbatches: int = 1, remat: bool = False,
             extra_rules: dict | None = None, kv_bits: int | None = None,
             quant_bits: int | None = None,
             flash_bf16: bool = False,
             flash_block: int | None = None,
             ssd_chunk: int | None = None) -> dict:
    cfg = get_config(arch)
    if ssd_chunk and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssd_chunk))
    profile = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    if shape == "long_500k":
        rules = dict(LONG_CONTEXT_RULES)
    elif profile.kind in ("decode", "prefill"):
        rules = {"kv_seq": "model"}   # sequence-sharded KV (flash-decoding)
    else:
        rules = {}
    rules.update(extra_rules or {})
    if flash_bf16 or flash_block:
        from ..models import attention as _attn
        if flash_bf16:
            _attn.FLASH_P_BF16 = True
        if flash_block:
            _attn.FLASH_BLOCK = flash_block
    model = Model(cfg, remat=remat, kv_bits=kv_bits)
    defs = param_defs(cfg)
    n_params = count_params(defs)
    n_active = cfg.active_param_count()
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           "params": n_params, "active_params": n_active,
           "param_bytes_f32": param_bytes(defs), "kind": profile.kind,
           "microbatches": microbatches, "remat": remat,
           "kv_bits": kv_bits, "quant_bits": quant_bits,
           "rules": {k: str(v) for k, v in rules.items()}}
    t0 = time.time()

    with axis_rules(mesh, rules):
        param_sh = defs_to_shardings(defs)
        params_abs = abstract_params(defs)
        if profile.kind != "train":
            # serving runs on bf16 weights (the f32 masters live in the
            # training job); halves inference argument bytes
            params_abs = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                if s.dtype == jnp.float32 and len(s.shape) >= 2 else s,
                params_abs)
        if quant_bits and profile.kind != "train":
            # MVDRAM serving: GeMV weights as packed bit-planes. The param
            # shardings for swapped leaves follow the packed layout (last
            # dim = outputs keeps the dense leaf's output-dim sharding).
            from ..serve.quantize import quantize_defs
            params_abs = quantize_defs(defs, quant_bits)
            param_sh = jax.tree_util.tree_map(
                lambda sds: jax.sharding.NamedSharding(
                    mesh, logical_to_pspec(
                        (None,) * (len(sds.shape) - 1) + ("mlp",),
                        sds.shape)),
                params_abs)

        if profile.kind == "train":
            emb = cfg.d_model if cfg.input_mode == "embeddings" else 0
            data = SyntheticLM(vocab=cfg.vocab_size, seq=profile.seq_len,
                               batch=profile.global_batch, embed_dim=emb)
            batch_abs = data.specs()
            batch_sh = jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(
                    mesh, logical_to_pspec(
                        ("batch",) + (None,) * (len(s.shape) - 1), s.shape)),
                batch_abs)
            opt_abs = {"m": params_abs, "v": params_abs,
                       "count": jax.ShapeDtypeStruct((), jnp.int32)}
            opt_sh = {"m": param_sh, "v": param_sh,
                      "count": jax.sharding.NamedSharding(
                          mesh, jax.sharding.PartitionSpec())}
            step = make_train_step(model, AdamWConfig(),
                                   num_microbatches=microbatches)
            lowered = jax.jit(step, donate_argnums=(0, 1),
                              in_shardings=(param_sh, opt_sh, batch_sh)
                              ).lower(params_abs, opt_abs, batch_abs)

        elif profile.kind == "prefill":
            emb = cfg.d_model if cfg.input_mode == "embeddings" else 0
            if emb:
                batch_abs = {"embeddings": jax.ShapeDtypeStruct(
                    (profile.global_batch, profile.seq_len, emb),
                    jnp.bfloat16)}
                spec = ("batch", None, None)
            else:
                batch_abs = {"tokens": jax.ShapeDtypeStruct(
                    (profile.global_batch, profile.seq_len), jnp.int32)}
                spec = ("batch", None)
            batch_sh = {k: jax.sharding.NamedSharding(
                mesh, logical_to_pspec(spec, v.shape))
                for k, v in batch_abs.items()}
            fn = partial(model.prefill, max_seq=profile.seq_len)
            # pin OUTPUT cache shardings (kv_seq over model) — otherwise SPMD
            # propagation may replicate caches whose head count does not
            # divide the model axis (musicgen: 24 MHA heads on 16)
            logits_abs, cache_struct = jax.eval_shape(
                fn, params_abs, batch_abs)
            cache_out_sh = jax.tree_util.tree_map(
                lambda sp: jax.sharding.NamedSharding(mesh, sp),
                cache_pspecs(cache_struct))
            logits_sh = jax.sharding.NamedSharding(
                mesh, logical_to_pspec(("batch", "vocab"), logits_abs.shape))
            lowered = jax.jit(fn, in_shardings=(param_sh, batch_sh),
                              out_shardings=(logits_sh, cache_out_sh)
                              ).lower(params_abs, batch_abs)

        else:  # decode
            b = profile.global_batch
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(b, profile.seq_len))
            cache_sh = jax.tree_util.tree_map(
                lambda sp: jax.sharding.NamedSharding(mesh, sp),
                cache_pspecs(cache_abs))
            if cfg.input_mode == "embeddings":
                inp_abs = jax.ShapeDtypeStruct((b, cfg.d_model), jnp.bfloat16)
                inp_spec = ("batch", None)
            else:
                inp_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
                inp_spec = ("batch",)
            inp_sh = jax.sharding.NamedSharding(
                mesh, logical_to_pspec(inp_spec, inp_abs.shape))
            pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
            pos_sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            step = make_serve_step(model)
            lowered = jax.jit(step, donate_argnums=(1,),
                              in_shardings=(param_sh, cache_sh, inp_sh,
                                            pos_sh)
                              ).lower(params_abs, cache_abs, inp_abs, pos_abs)

        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        rec["memory"] = _mem_summary(compiled)
        ca = compiled.cost_analysis() or {}
        rec["cost_analysis_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "note": "CPU backend counts while bodies ONCE — see hlo_analysis"}
        hlo = compiled.as_text()
        an = analyze_hlo(hlo)
        rec["hlo_analysis"] = {
            k: an[k] for k in ("flops", "write_bytes", "arg_bytes",
                               "hbm_bytes_estimate", "collective_bytes",
                               "coll_count", "all-reduce", "all-gather",
                               "reduce-scatter", "all-to-all",
                               "collective-permute")}
        rec["hlo_analysis"]["unresolved_loops"] = len(an["unresolved_loops"])
        rec["hlo_bytes"] = len(hlo)
        mf = model_flops_for(cfg, profile, n_active)
        hbm_bytes = an["arg_bytes"] + an["write_bytes"]
        rec["roofline"] = roofline(an["flops"], hbm_bytes,
                                   an["collective_bytes"], mf, chips)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--rules", default=None,
                    help='JSON logical-rule overrides, e.g. {"kv_seq":"data"}')
    ap.add_argument("--kv-bits", type=int, default=None)
    ap.add_argument("--quant-bits", type=int, default=None)
    ap.add_argument("--flash-bf16", action="store_true")
    ap.add_argument("--flash-block", type=int, default=None)
    ap.add_argument("--ssd-chunk", type=int, default=None)
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        live, skipped = cells()
        for a, s in live:
            print(f"RUN  {a} {s}")
        for a, s in skipped:
            print(f"SKIP {a} {s} (long_500k needs sub-quadratic attention)")
        return

    extra = json.loads(args.rules) if args.rules else None
    rec = run_cell(args.arch, args.shape, args.mesh == "multi",
                   args.microbatches, args.remat, extra,
                   kv_bits=args.kv_bits, quant_bits=args.quant_bits,
                   flash_bf16=args.flash_bf16, flash_block=args.flash_block,
                   ssd_chunk=args.ssd_chunk)
    js = json.dumps(rec, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)
    print(js)
    print(f"\nOK {args.arch} × {args.shape} × {rec['mesh']}: "
          f"peak/dev = {rec['memory']['peak_bytes_estimate']/2**30:.2f} GiB, "
          f"bottleneck = {rec['roofline']['bottleneck']}")


if __name__ == "__main__":
    main()
