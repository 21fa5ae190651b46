#!/usr/bin/env python3
"""Chip benchmark: serve one cell of BENCHMARK.json through the program's
normal path and print its metrics as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children. The window drives `ContinuousBatcher.tick()` on a
`ServeEngine(quantized=True, impl=PALLAS)` with the configuration's weight
and activation bits; the packed weights come from the program's own seeded
init. Closed-loop sessions (zero think time) keep one request each in flight.

Set-up (`setup_s`, process start to window open): init and quantize, engine
set-up, one tick of every trip bucket the traffic uses, and `warm_ticks`
ticks of the sessions' own traffic. Then the window runs for `--seconds`;
nothing compiles inside it. After it closes the peak device memory is read,
the program is freed, and a sample of the requests the window finished is
checked against the plain reference of the configuration's block family
(`families/<family>.py`, built on `harness/reference.py`).

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its per-layer
metrics (each read by `metrics/<name>.py`) and a `breakdown`, from a
profiler trace of the window. Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
# libtpu logs under the run's own temporary directory, not a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import counts, spec, trace as trace_mod  # noqa: E402
from harness.traffic import Sessions  # noqa: E402

#: a traced run traces the last seconds of its window: a decode step of
#: these models issues some 30,000 device ops, and the trace is read event
#: by event on the host
TRACE_SECONDS = 2.0


def program_config(a: dict):
    """The dense family's `program_config`, under the name through which
    the program's own tests build a benchmark configuration. A run asks its
    cell's family."""
    return spec.family("dense").program_config(a)


def _log(what: str, t_start: float) -> None:
    print(f"bench: {what} at {time.perf_counter() - t_start:.2f} s",
          file=sys.stderr, flush=True)


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Served:
    """What one run served, as plain data (the program is freed after)."""
    setup_s: float
    window_open: float
    window_s: float
    ticks: list            # (t_return, [(steps, p0, prefill)] per lane)
    stamps: dict           # rid -> [t of each output token]
    finished: list         # (t_finish, prompt, out, max_new) in order
    counters: dict         # the batcher's occupancy counter over the window
    memory_peak_bytes: int      # the process's peak (init and quantize)
    memory_in_use_bytes: int    # what serving holds when the window opens
    vocab: int


def serve(cell: spec.Cell, seed: int, seconds: float, traced: bool,
          backend, t_start: float, patch=None,
          trace_dir: str | None = None) -> Served:
    """Set up the program, warm it, run the closed-loop window."""
    import jax
    from harness.reference import model_key
    from repro.models.model import param_defs
    from repro.serve.engine import ServeEngine
    from repro.serve.quantize import init_quantized_params
    from repro.serve.scheduler import ContinuousBatcher, Request

    a, mix = cell.config["as_run"], cell.traffic
    cfg = cell.family.program_config(a)
    params = init_quantized_params(param_defs(cfg), model_key(seed),
                                   a["weight_bits"])
    eng = ServeEngine(cfg, params, max_seq=mix["max_seq"],
                      batch_slots=mix["lanes"], quantized=True,
                      act_bits=a["act_bits"], impl=backend)
    del params
    _log("init, quantize and engine set-up done", t_start)
    chunk = mix["prefill_chunk"]
    batcher = ContinuousBatcher(cfg, None, engine=eng, prefill_chunk=chunk)
    if patch is not None:
        patch(batcher)
    rids = iter(range(1 << 62))

    # one tick of every trip bucket (1, 2, 4, ... prefill_chunk): each lane
    # gets a prompt of the bucket's length and a one-token answer
    trip = 1
    while True:
        for _ in range(mix["lanes"]):
            batcher.submit(Request(rid=next(rids), prompt=[1] * trip,
                                   max_new=1))
        batcher.run()
        _log(f"trip bucket {trip} warmed", t_start)
        if trip >= chunk:
            break
        trip = min(2 * trip, chunk)

    sessions = Sessions(mix, seed, cfg.vocab_size)
    live, seen = {}, {}
    ticks, stamps, finished = [], {}, []

    def submit(s, prompt, max_new):
        with _span("bench.submit", traced):
            r = Request(rid=next(rids), prompt=prompt, max_new=max_new)
            batcher.submit(r)
            live[s], seen[s], stamps[r.rid] = r, 0, []

    for s in range(mix["sessions"]):
        submit(s, *sessions.first(s))

    def one_tick():
        before = [(l.req, l.pos, len(l.req.out) if l.req else 0)
                  for l in batcher.lanes]
        with _span("bench.tick", traced):
            batcher.tick()
        t = time.perf_counter()
        with _span("bench.bookkeeping", traced):
            lanes = []
            for (rb, pb, ob), l in zip(before, batcher.lanes):
                if rb is not None:
                    lanes.append((l.pos - pb, pb, ob == 0))
                elif l.req is not None or l.pos != pb:
                    lanes.append((l.pos, 0, True))     # admitted this tick
                else:
                    lanes.append((0, 0, False))
            ticks.append((t, lanes))
            done = []
            for s, r in live.items():
                n = len(r.out)
                stamps[r.rid] += [t] * (n - seen[s])
                seen[s] = n
                if r.done:
                    finished.append((t, r.prompt, list(r.out), r.max_new))
                    done.append(s)
        for s in done:
            submit(s, *sessions.next(s))

    for _ in range(mix["warm_ticks"]):
        one_tick()
    _log("warm traffic done; window opens", t_start)
    occ0 = dict(batcher.occupancy_ticks)
    n0, f0 = len(ticks), len(finished)
    dev = jax.devices()[0]
    in_use = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    span = None
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        if (traced and span is None
                and time.perf_counter() - t_open >= seconds - TRACE_SECONDS):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
        one_tick()
    t_close = time.perf_counter()
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    occ = {k: v - occ0.get(k, 0) for k, v in batcher.occupancy_ticks.items()
           if v - occ0.get(k, 0)}
    counters = {"occupancy_ticks": occ, "lanes": mix["lanes"]}
    in_window = ticks[n0:]
    # a short window may finish too few requests for the check: serve on,
    # untimed, until enough of those in flight have finished
    while len(finished) - f0 < cell.traffic["check_requests"] and live:
        one_tick()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    return Served(setup_s=t_open - t_start, window_open=t_open,
                  window_s=t_close - t_open,
                  ticks=in_window, stamps=stamps,
                  finished=[f for f in finished[f0:]],
                  counters=counters, memory_peak_bytes=int(peak),
                  memory_in_use_bytes=int(in_use), vocab=cfg.vocab_size)


# ---------------------------------------------------------------------------
# end-to-end metrics, from the window's host stamps
# ---------------------------------------------------------------------------

def percentile(xs: list, q: float) -> float:
    """Linear-interpolation percentile (numpy's default method)."""
    s = sorted(xs)
    k = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def end_to_end(sv: Served, t0: float) -> dict:
    t1 = t0 + sv.window_s
    gaps, tokens = [], 0
    for ts in sv.stamps.values():
        w = [t for t in ts if t0 <= t <= t1]
        tokens += len(w)
        gaps += [b - a for a, b in zip(w, w[1:])]
    prompt = sum(steps for _, lanes in sv.ticks
                 for steps, _, prefill in lanes if prefill)
    return {"tokens_per_s": tokens / sv.window_s,
            "prompt_tokens_per_s": prompt / sv.window_s,
            "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
            "itl_samples": len(gaps), "tokens": tokens,
            "prompt_tokens": prompt}


def step_context(sv: Served, cell: spec.Cell, peak: dict) -> dict:
    """Roofline sums over the window's useful inner steps, and for each
    kernel set the launches of one executed step and their least time, as
    the cell's block family counts them with the window's counters (see
    harness/counts.py)."""
    fam, a = cell.family, cell.config["as_run"]
    useful_s = 0.0
    for _, lanes in sv.ticks:
        for t in range(max((s for s, _, _ in lanes), default=0)):
            pos = [p0 + t for s, p0, _ in lanes if s > t]
            useful_s += counts.step_roofline_s(fam, a, a["weight_bits"], pos,
                                               peak, sv.counters)
    return {"step_roofline_s": useful_s,
            "kernels": counts.kernel_roofline_s(
                fam, a, a["weight_bits"], a["act_bits"],
                cell.traffic["lanes"], peak, sv.counters)}


# ---------------------------------------------------------------------------
# correctness: served tokens against the plain reference
# ---------------------------------------------------------------------------

def sample(sv: Served, cell: spec.Cell, seed: int) -> dict:
    """A seeded sample of the finished requests, the longest answer always
    among them, laid out for a teacher-forced reference pass: each row is
    a prompt and its answer but the last token, and `rows` are the flat
    positions whose logits chose the served tokens."""
    import numpy as np
    fin = sv.finished
    rng = np.random.default_rng(seed)
    longest = max(range(len(fin)), key=lambda i: len(fin[i][2]))
    rest = [int(i) for i in rng.permutation(len(fin)) if i != longest]
    pick = [longest] + rest[:cell.traffic["check_requests"] - 1]
    seqs = [fin[i][1] + fin[i][2][:-1] for i in pick]
    width = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), width), np.int32)
    rows, served = [], []
    for b, i in enumerate(pick):
        tokens[b, :len(seqs[b])] = seqs[b]
        p, out = len(fin[i][1]), fin[i][2]
        rows += [b * width + p - 1 + k for k in range(len(out))]
        served += out
    bad = sum(1 for _, _, out, n in fin
              if len(out) != n or not all(0 <= t < sv.vocab for t in out))
    return {"tokens": tokens, "lengths": np.array([len(s) for s in seqs]),
            "rows": np.array(rows), "served": np.asarray(served, np.int32),
            "requests": len(pick), "malformed": bad}


def readings(smp: dict, cell: spec.Cell, seed: int,
             controls: tuple = ()) -> dict:
    """The reference's view of the served tokens and, for each control
    variant (weight_bits, act_bits), of the tokens that variant puts first
    at the same positions: gaps below the reference's best (raw and in
    units of the row's logit spread) and ranks (0 = the reference's own
    first choice). The reference is the cell's block family's."""
    import numpy as np
    from harness import reference
    a = cell.config["as_run"]
    variants = ((a["weight_bits"], a["act_bits"]),) + tuple(controls)
    logits = cell.family.forward_logits(a, seed, smp["tokens"],
                                        smp["lengths"], smp["rows"], variants)

    def stats(tokens):
        gap, z, rank = (np.asarray(x) for x in reference.token_gaps(
            logits[0], np.asarray(tokens, np.int32)))
        return {"rank_median": float(np.median(rank)),
                "rank_mean": float(rank.mean()), "rank_max": int(rank.max()),
                "gap_max": float(gap.max()), "gap_sigma_max": float(z.max()),
                "gap_sigma_mean": float(z.mean())}

    out = {"program": stats(smp["served"])}
    for v, lg in zip(variants[1:], logits[1:]):
        out["control w%d a%d" % v] = stats(np.asarray(lg).argmax(-1))
    return out


def judge(got: dict, tokens: int, malformed: int, limits: dict) -> dict:
    """The numbers compared, each beside its limit, and the verdict: the mean
    over the sampled tokens of how many tokens the reference ranks above
    each (`got` is one entry of `readings`), how many tokens were compared,
    and how many finished answers were malformed."""
    numbers = {
        "rank_mean": {"value": got["rank_mean"], "limit": limits["rank_mean"]},
        "tokens_compared": {"value": tokens,
                            "limit": limits["tokens_compared"]},
        "answers_malformed": {"value": malformed, "limit": 0},
    }
    correct = (got["rank_mean"] <= limits["rank_mean"]
               and tokens >= limits["tokens_compared"] and malformed == 0)
    return {"correct": bool(correct), "numbers": numbers}


def check(sv: Served, cell: spec.Cell, seed: int) -> dict:
    """The served tokens of a seeded sample of finished requests against
    the reference (`judge`)."""
    smp = sample(sv, cell, seed)
    got = readings(smp, cell, seed)["program"]
    verdict = judge(got, len(smp["served"]), smp["malformed"], cell.limits)
    info = {k: v for k, v in got.items() if k != "rank_mean"}
    info["requests_compared"] = smp["requests"]
    return {**verdict, "info": info, "failed": smp["malformed"]}


# ---------------------------------------------------------------------------

def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             backend=None, patch=None, t_start: float = T_START,
             cache: bool = True) -> dict:
    """One run: serve, measure, free, check. Returns the result line."""
    import jax
    from repro.core import backends
    from repro.launch.serve import use_compile_cache
    if cache:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    backend = backend or backends.PALLAS
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        sv = serve(cell, seed, seconds, traced, backend, t_start, patch=patch,
                   trace_dir=tmp)
        gc.collect()
        e2e = end_to_end(sv, sv.window_open)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": sv.memory_peak_bytes}
        line: dict = {}
        if traced:
            ctx = _context(cell, sv, dev.device_kind,
                           trace_mod.load_events(tmp))
            metrics = {}
            for m in cell.per_layer:
                v = spec.metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device.update(busy_s=ctx["trace"]["busy_s"],
                          window_s=ctx["trace"]["window_s"])
            line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                                 "idle_gaps": ctx["trace"]["idle_gaps"]}
        else:
            metrics = {m["name"]: {"value": (sv.setup_s if m["name"] ==
                                             "setup_s" else e2e[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    jax.clear_caches()
    gc.collect()
    _log("window closed and read; reference check starts with %d bytes in "
         "use" % (dev.memory_stats() or {}).get("bytes_in_use", 0), t_start)
    verdict = check(sv, cell, seed)
    _log("reference check done", t_start)
    line = {"correct": verdict["correct"], "attempted": len(sv.finished),
            "failed": verdict["failed"], "metrics": metrics,
            "device": device, **line,
            "served": {"itl_samples": e2e["itl_samples"],
                       "tokens": e2e["tokens"],
                       "prompt_tokens": e2e["prompt_tokens"],
                       "window_s": sv.window_s,
                       "memory_in_use_at_open_bytes": sv.memory_in_use_bytes,
                       **verdict["info"]},
            "check": verdict["numbers"]}
    return line


def _context(cell: spec.Cell, sv: Served, kind: str, ev: dict) -> dict:
    """What the per-layer readers read: the window's counters and length,
    its roofline sums, and the reduced trace."""
    peak = spec.peaks(kind)
    return {"counters": sv.counters, "window_s": sv.window_s,
            "steps": step_context(sv, cell, peak),
            "trace": trace_mod.reduce(ev)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, n in line["check"].items():
        print(f"check {name} {n['value']} limit {n['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
