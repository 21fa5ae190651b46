#!/usr/bin/env python3
"""Chip smoke: serve full-width llama2-7b on one TPU through the Pallas
bit-plane kernels, at weight_bits 2 and 4 with 4-bit activations.

    python3 chip_smoke.py [--seed 0]

Per weight width, in one process (it starts no children):

  1. init+quantize  random weights from --seed, packed one leaf at a time
                    (`init_quantized_params`: no float32 copy of the model)
  2. residency      `ServeEngine(impl=PALLAS)` with 4 lanes, which places the
                    packed linears into the simulated DRAM pool; a placement
                    that falls back to program-less serving is printed as such
  3. compile        the batcher's one-step decode tick (its HLO must hold
                    `tpu_custom_call`), a checked PALLAS decode step and a
                    free-running JNP reference step
  4. check          the first decode step: every served linear (the lm_head,
                    whose output is the logits, included) against the JNP
                    reference on the same input, over the same packed params;
                    the free-running PALLAS-vs-JNP logits drift is printed
  5. serve          8 requests of mixed prompt (≤ 512) and answer (≤ 64)
                    lengths through `ContinuousBatcher`, all to completion

Every phase prints one JSON object per line. The last line is
`{"ok": true, "device": {...}}`, printed only when every phase passed; a
failure, or a JAX that finds no TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LANES = 4
ACT_BITS = 4
PREFILL_CHUNK = 16
MAX_PROMPT = 512
MAX_NEW = 64
MAX_SEQ = 640            # ≥ MAX_PROMPT + MAX_NEW + the frozen-lane slot
N_REQUESTS = 8
# Each served linear against the jnp reference on the SAME input (the
# input the served path itself produced): integer-exact accumulation, so
# only f32 rounding of the epilogue can differ. The first-step logits are
# the lm_head's output, so they are held to this bound too.
LINEAR_RTOL = 1e-5


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def requests(seed: int, vocab: int):
    """N_REQUESTS requests: prompts in multiples of the prefill chunk (so
    the batcher needs only its 1-step and chunk-step tick executables),
    including one prompt of MAX_PROMPT tokens and one answer of MAX_NEW."""
    import numpy as np
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    chunks = rng.integers(1, MAX_PROMPT // PREFILL_CHUNK, N_REQUESTS - 2)
    plens = [MAX_PROMPT, PREFILL_CHUNK] + list(chunks * PREFILL_CHUNK)
    news = [MAX_NEW, 1] + list(rng.integers(1, MAX_NEW + 1, N_REQUESTS - 2))
    return [Request(rid=i, prompt=[int(t) for t in rng.integers(1, vocab, p)],
                    max_new=int(n))
            for i, (p, n) in enumerate(zip(plens, news))]


class CheckedLinear:
    """The served linear hook with the reference beside it: every call runs
    the served (PALLAS) linear and, on the same input, the JNP reference,
    and hands the relative difference to the host. Free-running PALLAS and
    JNP decode steps cannot be compared this tightly: with 4-bit
    activations, a one-ulp difference anywhere flips codes downstream."""

    def __init__(self, served, errs: list):
        self.served, self.errs = served, errs

    def _check(self, x, outs, ws, act_bits):
        import jax
        import jax.numpy as jnp
        from repro.core import backends
        for out, w in zip(outs, ws):
            with jax.default_matmul_precision("highest"):
                ref = backends.JNP.linear(None, x, w, act_bits)
            err = jnp.abs(out - ref).max() / jnp.abs(ref).max()
            label = f"{w.n}x{w.m}"
            jax.debug.callback(
                lambda e, label=label: self.errs.append((label, float(e))),
                err)

    def __call__(self, x, w, act_bits=None):
        out = self.served(x, w, act_bits)
        self._check(x, (out,), (w,), act_bits)
        return out

    def group(self, x, ws, act_bits=None):
        outs = self.served.group(x, ws, act_bits)
        self._check(x, outs, ws, act_bits)
        return outs


def run_config(cfg, backend, seed: int) -> None:
    """Every phase for one weight width; raises on the first failure."""
    import jax
    import jax.numpy as jnp
    from repro.core import backends
    from repro.models.model import Model, param_defs
    from repro.serve.engine import ServeEngine
    from repro.serve.quantize import init_quantized_params
    from repro.serve.scheduler import ContinuousBatcher

    bits = cfg.weight_bits
    tag = f"q{bits}a{ACT_BITS}"
    phase, peak = {}, {}

    def peak_bytes():
        return (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")

    t = time.perf_counter()
    params = init_quantized_params(param_defs(cfg), jax.random.PRNGKey(seed),
                                   bits)
    jax.block_until_ready(params)
    phase["init_quantize_s"] = time.perf_counter() - t
    peak["init_quantize"] = peak_bytes()

    t = time.perf_counter()
    eng = ServeEngine(cfg, params, max_seq=MAX_SEQ, batch_slots=LANES,
                      quantized=True, act_bits=ACT_BITS, impl=backend)
    phase["residency_s"] = time.perf_counter() - t
    peak["residency"] = peak_bytes()
    stats = eng.residency_stats()
    emit(config=tag, residency={k: stats[k] for k in (
        "placement_fallback", "resident_program", "registered",
        "placements")})
    check(stats["resident_program"] == (not stats["placement_fallback"]),
          "residency flags disagree")

    t = time.perf_counter()
    batcher = ContinuousBatcher(cfg, None, engine=eng,
                                prefill_chunk=PREFILL_CHUNK)
    ints = jnp.zeros((LANES,), jnp.int32)
    tick = batcher._tick_fn(1).lower(
        eng.params, batcher.cache, jnp.zeros((LANES, 1), jnp.int32), ints,
        ints).compile()
    kernels = tick.as_text().count("tpu_custom_call")
    emit(config=tag, decode_tick_tpu_custom_calls=kernels)
    check(kernels > 0, "the compiled decode tick runs no Pallas kernel")
    errs: list = []
    checked = Model(cfg, act_bits=ACT_BITS, impl=CheckedLinear(eng.model.impl,
                                                               errs))
    cache = eng.model.init_cache(LANES, MAX_SEQ)
    tok = jax.random.randint(jax.random.PRNGKey(seed + 1), (LANES,), 0,
                             cfg.vocab_size, jnp.int32)
    step = jax.jit(checked.decode_step).lower(
        eng.params, cache, tok, ints).compile()
    # the reference model run free: its logits drift from the served ones
    # by however far a few flipped activation codes carry (printed, not
    # gated)
    ref = Model(cfg, act_bits=ACT_BITS, impl=backends.JNP)
    ref_step = jax.jit(ref.decode_step).lower(
        eng.params, cache, tok, ints).compile()
    phase["compile_s"] = time.perf_counter() - t
    peak["compile"] = peak_bytes()

    logits = step(eng.params, cache, tok, ints)[0]
    jax.effects_barrier()
    n_linears = 7 * cfg.num_layers + 1
    check(len(errs) == n_linears,
          f"checked {len(errs)} served linears, expected {n_linears}")
    worst = max(errs, key=lambda e: e[1])
    lm_head = f"{cfg.d_model}x{cfg.vocab_size}"
    emit(config=tag, served_linears_vs_reference={
        "linears": len(errs), "max_rel": worst[1], "worst": worst[0],
        "logits_rel": max(e for lbl, e in errs if lbl == lm_head),
        "tolerance_rel": LINEAR_RTOL})
    check(bool(jnp.isfinite(logits).all()), "non-finite logits")
    check(worst[1] <= LINEAR_RTOL,
          f"served linear {worst[0]} off the reference by {worst[1]}")
    free = ref_step(eng.params, cache, tok, ints)[0]
    del cache
    delta = float(jnp.abs(logits - free).max())
    emit(config=tag, free_running_logits={
        "max_abs_delta": delta,
        "rel": delta / float(jnp.abs(free).max()),
        "argmax_agree": int((logits.argmax(-1) == free.argmax(-1)).sum())})
    del step, ref_step, logits, free
    peak["check"] = peak_bytes()

    t = time.perf_counter()
    for r in requests(seed, cfg.vocab_size):
        batcher.submit(r)
    done = batcher.run()
    phase["serve_s"] = time.perf_counter() - t
    peak["serve"] = peak_bytes()
    check(len(done) == N_REQUESTS and all(r.done for r in done),
          f"{sum(r.done for r in done)}/{N_REQUESTS} requests done")
    check(all(len(r.out) == r.max_new for r in done), "short answers")
    emit(config=tag, tokens_served=batcher.tokens_out,
         prompt_tokens=sum(len(r.prompt) for r in done),
         ticks=batcher.ticks, decode_steps=batcher.program_ticks)
    emit(config=tag, phase_s=phase)
    # the process-wide peak after each phase (it never resets, so a phase
    # shows only when it raises the peak); then what stays in use
    emit(config=tag, peak_bytes_in_use_after=peak,
         bytes_in_use=(jax.devices()[0].memory_stats() or {}).get(
             "bytes_in_use"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.core import backends
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    emit(device_kind=dev.device_kind, jax=jax.__version__)
    for bits in (2, 4):
        cfg = dataclasses.replace(get_config("llama2-7b"), weight_bits=bits)
        run_config(cfg, backends.PALLAS, args.seed)
        gc.collect()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
