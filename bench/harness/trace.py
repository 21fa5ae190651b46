"""From a profiler trace to device busy time, idle gaps and kernel time.

`load_events` reads the `.xplane.pb` the JAX profiler writes into plain
event lists; everything else works on those lists, so the reduction can be
checked on a small recorded trace without a chip.

Device events are the ops on each TPU plane's "XLA Ops" line, named there
by their whole HLO text; `op_kind` keeps the instruction's name without its
number, and marks the Pallas kernels (custom calls to "tpu_custom_call"),
which the trace names after their jitted wrappers: `_run_codes` for the
fused group kernel `_program_kernel`, `bitplane_gemv_codes` for the per-leaf
`_gemv_bs_kernel`. Host spans are the benchmark's own `TraceAnnotation`s
(names starting "bench."), on the same clock.
"""
from __future__ import annotations

import glob
import os

DEVICE_LINE = "XLA Ops"
HOST_PREFIX = "bench."
KERNEL = "tpu_custom_call:"
#: the served bit-plane kernels, as `op_kind` names their events
BITPLANE_KERNELS = (KERNEL + "_run_codes",
                    KERNEL + "bitplane_gemv_codes")


def op_kind(hlo: str) -> str:
    """"%fusion.12 = f32[..] fusion(..)" -> "fusion"; a Pallas kernel's
    custom call -> "tpu_custom_call:<its jitted wrapper>"."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    base = head.rsplit(".", 1)[0] if head[-1:].isdigit() else head
    return KERNEL + base if 'custom_call_target="tpu_custom_call"' in hlo \
        else base


def load_events(log_dir: str) -> dict:
    """{"device": {plane: [(name, start_ns, dur_ns)]}, "host": [(name,
    start_ns, dur_ns)]} from the newest trace under `log_dir`."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs, kinds = [], {}
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    for e in line.events:
                        name = e.name
                        kind = kinds.get(name)
                        if kind is None:
                            kind = kinds[name] = op_kind(name)
                        evs.append((kind, e.start_ns, e.duration_ns))
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return {"device": device, "host": host}


def window(host: list, name: str = "bench.window") -> tuple:
    """(start_ns, end_ns) of the host span that brackets the measurement."""
    spans = [(s, s + d) for n, s, d in host if n == name]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(spans)}")
    return spans[0]


def clip(events: list, w0: float, w1: float) -> list:
    """Events cut to [w0, w1]; those wholly outside are dropped."""
    out = []
    for n, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((n, a, b - a))
    return out


def union(events: list) -> list:
    """Merged [start, end) intervals covered by any event."""
    merged = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return merged


def busy_ns(events: list) -> float:
    return sum(b - a for a, b in union(events))


def idle_gaps(events: list, w0: float, w1: float) -> list:
    """[(start, end)] of the window's stretches with no device op."""
    gaps, t = [], w0
    for a, b in union(events):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def host_label(host: list, a: float, b: float) -> str:
    """The benchmark's host span (other than the window) that covers most
    of [a, b]."""
    best, cover = "untraced host work", 0.0
    for n, s, d in host:
        if n == "bench.window":
            continue
        c = min(b, s + d) - max(a, s)
        if c > cover:
            best, cover = n, c
    return best


def kernel_ns(events: list, names: tuple) -> float:
    """Summed device time of the events whose name contains any of `names`."""
    return sum(d for n, _, d in events if any(k in n for k in names))


#: control-flow ops whose events span the ops they run
CONTAINERS = ("while", "conditional", "call")


def top_ops(events: list, k: int = 10) -> list:
    """The k op kinds that took most device time (containers left out)."""
    tot: dict = {}
    for n, _, d in events:
        if n not in CONTAINERS:
            tot[n] = tot.get(n, 0) + d
    return [[n, t / 1e9] for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]


def reduce(ev: dict) -> dict:
    """Per-chip busy time and the window's device ops and idle gaps. Times
    are averaged over the chips that ran anything in the window; `planes`
    keeps each such chip's events, cut to the window."""
    w0, w1 = window(ev["host"])
    planes = [clip(evs, w0, w1) for evs in ev["device"].values()]
    planes = [p for p in planes if p] or [[]]
    busy = [busy_ns(p) for p in planes]
    gaps = sorted(idle_gaps(planes[0], w0, w1), key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": top_ops(planes[0]),
        "idle_gaps": [[host_label(ev["host"], a, b), (b - a) / 1e9]
                      for a, b in gaps[:10]],
        "planes": planes,
    }


def kernel_s(red: dict, names: tuple) -> float:
    """Device seconds of the named kernels, averaged over the chips."""
    per = [kernel_ns(p, names) for p in red["planes"]]
    return sum(per) / len(per) / 1e9


def kernel_count(red: dict, names: tuple) -> float:
    """Events of the named kernels in the window, averaged over the chips."""
    per = [sum(1 for n, _, _ in p if any(k in n for k in names))
           for p in red["planes"]]
    return sum(per) / len(per)
