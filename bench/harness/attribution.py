"""Where a traced window's time went, by the program's own names.

`harness.trace` reads the benchmark's host spans and the device's op kinds.
This module reads, from the same `.xplane.pb`, what the program adds: its
`serve.*` host spans (`repro.serve.spans`: a `serve.tick` per scheduler
tick, tiled by `serve.admit`, `serve.plan`, `serve.stage`,
`serve.dispatch`, `serve.wait` and `serve.commit`) and the model region
(`jax.named_scope`: `embed`, `attention`, `attention/kv_write`, `norm`,
`act_quant`, `ffn`, `lm_head`, `freeze_lanes`, `sample`) each device op was
traced under. The TPU trace names an op by its HLO text without metadata,
so the region comes from the compiled executables' text, where each
instruction carries its `op_name` (`op_regions`).

From them: idle device time by the innermost host span open through it,
the longest idle gaps each labelled by the span that covers most of it (of
spans that cover it equally, the shortest), host time per tick (a
`serve.tick` less its `serve.wait`), and device time by region and by
region and op kind. `bench/attribute.py` runs a cell with these beside its metrics.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from harness import trace

SERVE_PREFIX = "serve."
HOST_PREFIXES = (trace.HOST_PREFIX, SERVE_PREFIX)
#: name path components that are transformations or control flow, not
#: regions the program named
_CONTROL = frozenset({"jit", "pjit", "while", "body", "cond", "closed_call",
                      "core_call", "remat", "checkpoint", "scan",
                      "shard_map", "custom_jvp_call", "custom_vjp_call"})
_REGION = re.compile(r"[A-Za-z_]\w*")
UNSCOPED = "(no region)"
UNTRACED = "untraced host work"
SEP = " | "


def scope_of(op_name: str) -> str:
    """"jit(run)/while/body/closed_call/attention/kv_write/scatter" ->
    "attention/kv_write": the named components above the primitive."""
    parts = op_name.split("/")[:-1]
    kept = [p for p in parts if _REGION.fullmatch(p) and p not in _CONTROL]
    return "/".join(kept) or UNSCOPED


def head(hlo: str) -> str:
    """"%fusion.12 = (f32[2]{0}, u32[]) fusion(...), ..." -> "fusion.12
    fusion": an instruction's name and opcode, alike in a trace event's
    name and in compiled HLO text."""
    name, _, rest = hlo.strip().removeprefix("ROOT ").partition(" = ")
    depth = 0
    for i, ch in enumerate(rest):       # skip the (possibly tuple) shape
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            return name.lstrip("%") + " " + rest[i + 1:].split("(", 1)[0]
    return name.lstrip("%")


_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def op_regions(hlo_texts) -> dict:
    """{head: region} over the instructions of compiled HLO modules; an
    instruction whose head means different regions in different modules
    gets them all, joined by " or "."""
    found: dict = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _OP_NAME.search(line)
            if m and " = " in line:
                found.setdefault(head(line), set()).add(scope_of(m.group(1)))
    return {h: " or ".join(sorted(r)) for h, r in found.items()}


def load_events(log_dir: str) -> dict:
    """`trace.load_events`'s {"device", "host"}, with the program's
    `serve.*` spans among the host events, and "ops": per device plane,
    its ops other than control-flow containers as ((head, op kind),
    start_ns, dur_ns)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    device, ops, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs, named, seen = [], [], {}
            for line in plane.lines:
                if line.name != trace.DEVICE_LINE:
                    continue
                for e in line.events:
                    key = seen.get(e.name)
                    if key is None:
                        key = seen[e.name] = (head(e.name),
                                              trace.op_kind(e.name))
                    evs.append((key[1], e.start_ns, e.duration_ns))
                    if key[1] not in trace.CONTAINERS:
                        named.append((key, e.start_ns, e.duration_ns))
            device[plane.name], ops[plane.name] = evs, named
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return {"device": device, "host": host, "ops": ops}


def label(host: list, a: float, b: float) -> str:
    """The host span (other than the window) that covers most of [a, b];
    of spans that cover it equally, the shortest."""
    best, key = UNTRACED, (0, 0)
    for n, s, d in host:
        if n == "bench.window":
            continue
        c = min(b, s + d) - max(a, s)
        if c > 0 and (c, -d) > key:
            best, key = n, (c, -d)
    return best


def innermost(host: list) -> list:
    """[(start, end, name)]: the host timeline cut where any span (other
    than the window) opens or closes, each piece named by the shortest
    span open through it; pieces no span covers are left out."""
    spans = sorted((s, s + d, n) for n, s, d in host if n != "bench.window")
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out, i, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            open_.append(spans[i])
            i += 1
        open_ = [x for x in open_ if x[1] > a]
        if open_:
            out.append((a, b, min(open_, key=lambda x: x[1] - x[0])[2]))
    return out


def idle_by_span(host: list, gaps: list) -> list:
    """[[span, seconds, gaps, enclosed seconds]]: every idle stretch split
    among the innermost host spans open through it ("untraced host work"
    where none is), the number of gaps that touch each span, and the
    seconds of the gaps that lie wholly inside one piece of it (bubbles
    the host does not see, apart from the stretches that run on past the
    span); most seconds first, then by name."""
    pieces = innermost(host)
    starts = [a for a, _, _ in pieces]
    tot: dict = {}
    for a, b in gaps:
        left, touched = b - a, set()
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        while k < len(pieces) and pieces[k][0] < b:
            p0, p1, name = pieces[k]
            c = min(b, p1) - max(a, p0)
            if c > 0:
                t = tot.setdefault(name, [0, 0, 0])
                t[0] += c
                t[2] += c if p0 <= a and b <= p1 else 0
                left -= c
                touched.add(name)
            k += 1
        if left > 0:
            tot.setdefault(UNTRACED, [0, 0, 0])[0] += left
            touched.add(UNTRACED)
        for n in touched:
            tot[n][1] += 1
    return [[n, s / 1e9, k, w / 1e9] for n, (s, k, w) in
            sorted(tot.items(), key=lambda x: (-x[1][0], x[0]))]


def tick_host_s(host: list, w0: float, w1: float) -> list:
    """Host seconds of each `serve.tick` wholly inside [w0, w1], less the
    part its `serve.wait` covers."""
    waits = [(s, s + d) for n, s, d in host if n == SERVE_PREFIX + "wait"]
    out = []
    for n, s, d in host:
        if n != SERVE_PREFIX + "tick" or s < w0 or s + d > w1:
            continue
        w = sum(min(s + d, e) - max(s, a) for a, e in waits
                if a < s + d and e > s)
        out.append((d - w) / 1e9)
    return out


def attribute(ev: dict, regions: dict, k: int = 10) -> dict:
    """The window's idle time by host span, its longest idle gaps (each
    with its label and the op kinds that end before and start after it),
    its host time per tick, and its device time by region (`op_regions`)
    and by region and op kind, on the first chip that ran anything."""
    w0, w1 = trace.window(ev["host"])
    planes = [(trace.clip(evs, w0, w1), ev["ops"].get(name, []))
              for name, evs in ev["device"].items()]
    plane, named = next(((p, o) for p, o in planes if p), ([], []))
    gaps = trace.idle_gaps(plane, w0, w1)
    ticks = tick_host_s(ev["host"], w0, w1)
    bench_ticks = [d for n, s, d in ev["host"]
                   if n == "bench.tick" and s >= w0 and s + d <= w1]
    by_kind = [(regions.get(h, UNSCOPED) + SEP + kind, s, d)
               for (h, kind), s, d in trace.clip(named, w0, w1)]
    by_region = [(n.split(SEP, 1)[0], s, d) for n, s, d in by_kind]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    ops = [e for e in plane if e[0] not in trace.CONTAINERS]
    ends = sorted((s + d, n) for n, s, d in ops)
    starts = sorted((s, n) for n, s, d in ops)

    def around(a, b):
        i = bisect.bisect_right(ends, (a, "\uffff")) - 1
        j = bisect.bisect_left(starts, (b, ""))
        return (ends[i][1] if i >= 0 else None,
                starts[j][1] if j < len(starts) else None)
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "ticks": len(ticks),
        "host_ms_per_tick": (1e3 * sum(ticks) / len(ticks)
                             if ticks else None),
        "bench_ticks": len(bench_ticks),
        "bench_tick_ms": (sum(bench_ticks) / len(bench_ticks) / 1e6
                          if bench_ticks else None),
        "idle_by_span": idle_by_span(ev["host"], gaps),
        "idle_gaps": [[label(ev["host"], a, b), (b - a) / 1e9,
                       *around(a, b)] for a, b in longest],
        "device_scopes": trace.top_ops(by_region, k),
        "device_scope_ops": trace.top_ops(by_kind, k),
    }
