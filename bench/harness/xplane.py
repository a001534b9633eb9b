"""Reduction of a profiler trace (``*.xplane.pb``) to the numbers the
metrics read: device busy time, the host spans the harness wrote, idle
gaps attributed to what the host was doing, and the operations that took
the most device time.

Device time is taken from the device planes (``/device:TPU:<n>``): the
``XLA Ops`` line where the plane has one, else its ``XLA Modules`` line.
Busy time is the union of those intervals; a chip's busy share over
several chips is their mean.  Host spans are the ``TraceAnnotation``s
whose names start with ``bench.``.

The profiler puts host and device events on one clock only up to an
offset (about a millisecond on a v5e).  Where the harness wrote blocking
spans (``bench.decode``, ``bench.prefill``: each ends on its result, so
its device work lies inside it), ``load`` shifts the device events by the
offset that puts the most device time inside those spans.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
#: spans that end on their device work's result (see ``align``)
BLOCKING_SPANS = ("bench.decode", "bench.prefill")


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane -> sorted [(start, end, name)] ns
    modules: dict      # device plane -> sorted [(start, end, name)] ns
    spans: list        # sorted [(start, end, name)] host spans, ns
    shift_ns: float = 0.0   # added to every device event by ``load``


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> list:
    return sorted((float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
                   e.name) for e in line.events)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if _DEVICE_PLANE.match(plane.name):
            mod = _events(lines["XLA Modules"]) if "XLA Modules" in lines \
                else []
            op = _events(lines["XLA Ops"]) if "XLA Ops" in lines else mod
            if op:
                ops[plane.name], modules[plane.name] = op, mod
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e[2].startswith(SPAN_PREFIX))
    tr = Trace(ops=ops, modules=modules, spans=sorted(spans))
    return shifted(tr, align(tr))


def _busy_before(merged):
    """``B(t)``: device busy time before ``t``, for arrays of ``t``."""
    st = np.array([a for a, _ in merged])
    en = np.array([b for _, b in merged])
    cum = np.concatenate([[0.0], np.cumsum(en - st)])

    def B(t):
        i = np.searchsorted(st, t, side="right")       # intervals started
        part = np.where(i > 0, np.minimum(t, en[np.maximum(i - 1, 0)])
                        - st[np.maximum(i - 1, 0)], 0.0)
        return cum[np.maximum(i - 1, 0)] * (i > 0) + np.maximum(part, 0.0)
    return B


def align(tr: Trace, reach_ns: float = 5e6) -> float:
    """The shift (ns, added to device times) within ``+-reach_ns`` that puts
    the most device time of the first device plane inside the blocking
    spans; 0 without such spans.  Ties take the middle of the best run."""
    spans = [(s, e) for s, e, n in tr.spans if n in BLOCKING_SPANS]
    if not spans or not tr.ops:
        return 0.0
    B = _busy_before(merge(next(iter(tr.ops.values()))))
    s0 = np.array([s for s, _ in spans])
    e0 = np.array([e for _, e in spans])

    def best(cands):
        inside = np.array([np.sum(B(e0 - d) - B(s0 - d)) for d in cands])
        top = np.flatnonzero(inside >= inside.max() - 1e-6)
        return cands[top[len(top) // 2]]

    d = best(np.linspace(-reach_ns, reach_ns, 1001))
    step = 2 * reach_ns / 1000
    return float(best(np.linspace(d - step, d + step, 201)))


def shifted(tr: Trace, d: float) -> Trace:
    move = {p: [(s + d, e + d, n) for s, e, n in v] for p, v in
            tr.ops.items()}
    mods = {p: [(s + d, e + d, n) for s, e, n in v] for p, v in
            tr.modules.items()}
    return Trace(ops=move, modules=mods, spans=tr.spans,
                 shift_ns=tr.shift_ns + d)


def merge(intervals) -> list:
    """Union of ``(start, end, ...)`` intervals as sorted ``(start, end)``."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def covered(merged, windows) -> float:
    """Length of ``merged`` (disjoint, sorted) inside ``windows`` (disjoint,
    sorted ``(lo, hi)``), ns."""
    total, j = 0.0, 0
    for lo, hi in windows:
        while j < len(merged) and merged[j][1] <= lo:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < hi:
            total += min(merged[k][1], hi) - max(merged[k][0], lo)
            k += 1
    return total


def spans_named(trace: Trace, name: str) -> list:
    return [(s, e) for s, e, n in trace.spans if n == name]


def window(trace: Trace) -> tuple:
    """``(lo, hi)`` of the harness's ``bench.window`` span."""
    w = spans_named(trace, SPAN_PREFIX + "window")
    if not w:
        raise ValueError("the trace holds no bench.window span")
    return w[0]


def busy_ns(trace: Trace, windows) -> float:
    """Device busy time inside ``windows``, mean over device planes."""
    if not trace.ops:
        return 0.0
    return sum(covered(merge(iv), windows)
               for iv in trace.ops.values()) / len(trace.ops)


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """``[name, seconds]`` of the ``n`` operations with the most device
    time inside ``[lo, hi]`` (mean over planes), each named
    ``<program>/<op>`` by the module that encloses it."""
    tot: dict = {}
    for plane, ops in trace.ops.items():
        mods, j = trace.modules.get(plane, []), 0
        for s, e, name in ops:
            if e <= lo or s >= hi:
                continue
            while j < len(mods) and mods[j][1] <= s:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else ""
            name = short_op(name)
            key = f"{mod}/{name}" if mod and mod != name else name
            tot[key] = tot.get(key, 0.0) + (min(e, hi) - max(s, lo))
    k = max(len(trace.ops), 1)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in rows]


_HLO = re.compile(r"^(%?[\w.\-]+) = (\w+\[[^\]]*\])")


def short_op(text: str) -> str:
    """``%fusion.7 bf16[1024,64,2,128]`` from an HLO instruction's text
    (the device line names each op by its whole instruction)."""
    m = _HLO.match(text)
    if not m:
        return text.split(" = ")[0][:120]
    kind = re.search(r"custom_call_target=\"(\w+)\"|kind=(\w+)", text)
    tag = f" {kind.group(1) or kind.group(2)}" if kind else ""
    return f"{m.group(1)} {m.group(2)}{tag}"


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """``[host activity, seconds]``: device idle time inside ``[lo, hi]``
    (first device plane), summed by the innermost ``bench.`` host span
    over each gap's midpoint; gaps under no span are the engine's own
    host code."""
    if not trace.ops:
        return []
    busy = clip(merge(next(iter(trace.ops.values()))), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    inner = [sp for sp in trace.spans if sp[2] != SPAN_PREFIX + "window"]
    tot: dict = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        label = "host outside the model calls"
        for s, e, name in inner:
            if s <= mid < e:
                label = "host inside " + name[len(SPAN_PREFIX):]
        tot[label] = tot.get(label, 0.0) + (g1 - g0)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]
