"""Reduce a JAX profiler trace to what the per-layer metrics read.

`load` reads an ``.xplane.pb`` with `jax.profiler.ProfileData` and keeps
the device operations of each device plane (name, start, end in ns), the
runs of each compiled program on it (``XLA Modules``), the harness's own
host spans (``bench.*`` trace annotations) and the program's
(``repro.*``, `repro.trace`, with their stats).
The rest are plain functions over those intervals: the union of busy
time, the idle gaps between operations, the host span that was open
during each gap, operations matched by name, and the operations that
took the most time.
"""
from __future__ import annotations

import dataclasses
import glob
import os

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    # device plane name -> [(op name, start ns, end ns)], sorted by start
    device_ops: dict
    # [(span name, start ns, end ns)] of the harness's host annotations
    host_spans: list
    # device plane name -> [(module name, start ns, end ns)], by start
    modules: dict
    # [(span name, host line, start ns, end ns, {stat: value})] of the
    # program's host spans, by start
    program_spans: list

    def window(self, name: str = SPAN_PREFIX + "window"):
        """(start, end) ns of the host span ``name``; None if absent."""
        for n, s, e in self.host_spans:
            if n == name:
                return s, e
        return None


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` that `jax.profiler.start_trace(trace_dir)`
    wrote."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names an operation by its
    whole HLO line (``%fusion.3 = bf16[...] fusion(...), ...``), whose
    operands name other instructions."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _events(plane, line_name: str):
    return sorted(((e.name, int(e.start_ns), int(e.end_ns))
                   for line in plane.lines if line.name == line_name
                   for e in line.events), key=lambda o: o[1])


def load(path: str, span_prefix: str = SPAN_PREFIX) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, modules, host_spans, program_spans = {}, {}, [], []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            ops = [(op_name(n), s, e) for n, s, e in _events(plane,
                                                              OPS_LINE)]
            if ops:
                device_ops[plane.name] = ops
            mods = _events(plane, MODULES_LINE)
            if mods:
                modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        host_spans.append((e.name, int(e.start_ns),
                                           int(e.end_ns)))
                    if e.name.startswith(PROGRAM_PREFIX):
                        program_spans.append(
                            (e.name, line.name, int(e.start_ns),
                             int(e.end_ns), dict(e.stats)))
    return Reduced(device_ops, sorted(host_spans, key=lambda s: s[1]),
                   modules, sorted(program_spans, key=lambda s: s[2]))


def clip(ops, lo: int, hi: int):
    """Operations cut to [lo, hi]; those wholly outside are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops
            if e > lo and s < hi]


def merge(intervals):
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Length of the union of the operations' intervals inside [lo, hi]."""
    return sum(e - s for s, e in merge((s, e) for _, s, e in
                                        clip(ops, lo, hi)))


def idle_gaps(ops, lo: int, hi: int):
    """Gaps in [lo, hi] in which no operation ran, as (start, end)."""
    gaps, t = [], lo
    for s, e in merge((s, e) for _, s, e in clip(ops, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def open_span(spans, t: int, exclude=(SPAN_PREFIX + "window",)) -> str:
    """Name of the innermost host span open at time t (the latest to start
    among those covering t), or "none"."""
    best = None
    for n, s, e in spans:
        if n not in exclude and s <= t < e and (best is None or s >= best[1]):
            best = (n, s)
    return best[0] if best else "none"


def labelled_gaps(ops, spans, lo: int, hi: int, n: int = 10):
    """The n longest idle gaps, longest first, as [label, seconds]: the
    label is the host span open at the gap's midpoint."""
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[open_span(spans, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps]


def matching(ops, patterns):
    """Operations whose name holds any of ``patterns`` (case-insensitive)."""
    pats = [p.lower() for p in patterns]
    return [o for o in ops if any(p in o[0].lower() for p in pats)]


def self_times(ops):
    """[(name, ns)] of each operation's own time: its duration less that of
    the operations nested inside it (a TPU trace lists a while loop, and
    then every operation of its body inside the loop's interval)."""
    out, stack = [], []           # stack: [name, end, own ns]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out += [tuple(x[::2]) for x in reversed(stack)]
    return out


def top_ops(ops, n: int = 10):
    """The n operation names with the most summed own device time
    (`self_times`), as [name, seconds], most first."""
    tot = {}
    for name, ns in self_times(ops):
        tot[name] = tot.get(name, 0) + ns
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
