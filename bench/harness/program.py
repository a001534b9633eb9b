"""The program's own host spans (``repro.<layer>.<phase>``, written by
``repro.spans``) read from a profiler trace, and the device's idle time
put down to the phase the host was in.

``xplane.load`` keeps only the harness's ``bench.`` spans; ``load`` reads
the ``repro.`` spans of the same file.  Both are host events, so they
share one clock; the device events of the ``Trace`` that ``xplane.load``
returns are already moved onto it.  A span's name keeps no encoded
arguments: the profiler stores them as the event's stats, read into
``Span.args``.

``idle_split`` cuts every device-idle interval of a window at each span
boundary inside it and gives each piece to the innermost program span
over it (the one opened last), or to ``NO_SPAN``; the pieces sum to the
window's idle time exactly.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness import xplane

PREFIX = "repro."
#: the label of idle time under no program span
NO_SPAN = "no program span"
#: the serving engine's own host work: scheduling, admission and page
#: books, release, preemption, checkpoints (``serve.pages.*`` too)
ENGINE = ("serve.tick", "serve.admit", "serve.release", "serve.preempt",
          "serve.ckpt")


@dataclasses.dataclass(frozen=True)
class Span:
    start: float        # ns, host clock
    end: float
    name: str           # without the ``repro.`` prefix
    args: dict


def load(path: str) -> list:
    """The ``repro.`` spans of every host thread, sorted by start (an
    enclosing span before the spans it holds)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = float(e.start_ns)
                    out.append(Span(s, s + float(e.duration_ns),
                                    e.name[len(PREFIX):], dict(e.stats)))
    return sorted(out, key=lambda sp: (sp.start, -sp.end))


def is_engine(name: str) -> bool:
    return name in ENGINE or name.startswith("serve.pages.")


def named(spans: list, name: str, lo: float = -np.inf,
          hi: float = np.inf) -> list:
    """Spans called ``name`` that lie inside ``[lo, hi]``."""
    return [sp for sp in spans if sp.name == name and lo <= sp.start
            and sp.end <= hi]


def inside(spans: list, outer: Span, name: str = None) -> list:
    """The spans (called ``name``, if given) nested in ``outer``."""
    return [sp for sp in spans if sp is not outer
            and outer.start <= sp.start and sp.end <= outer.end
            and (name is None or sp.name == name)]


def self_ns(spans: list, outer: Span, name: str = None) -> float:
    """``outer``'s duration less the part of it that the spans nested in
    it (called ``name``, if given) cover."""
    kids = xplane.merge((sp.start, sp.end) for sp in inside(spans, outer,
                                                            name))
    return (outer.end - outer.start) - sum(e - s for s, e in kids)


def _labels(spans: list, cuts: np.ndarray) -> list:
    """The innermost span's name over each ``[cuts[i], cuts[i + 1]]``:
    every span boundary inside the range is one of ``cuts``, so no piece
    straddles one.  A stack of the open spans, topped by the one opened
    last; a span that closed under a later one leaves when it surfaces."""
    order = sorted((sp for sp in spans
                    if sp.end > cuts[0] and sp.start < cuts[-1]),
                   key=lambda sp: (sp.start, -sp.end))
    stack, j, out = [], 0, []
    for c in cuts[:-1]:
        while j < len(order) and order[j].start <= c:
            stack.append(order[j])
            j += 1
        while stack and stack[-1].end <= c:
            stack.pop()
        out.append(stack[-1].name if stack else NO_SPAN)
    return out


def idle_split(trace: xplane.Trace, spans: list, lo: float,
               hi: float) -> dict:
    """``{innermost program span: idle ns}`` over the device-idle time of
    ``[lo, hi]`` (first device plane, as ``xplane.idle_gaps``)."""
    if not trace.ops:
        return {}
    merged = xplane.clip(xplane.merge(next(iter(trace.ops.values()))),
                         lo, hi)
    inner = {lo, hi}
    for sp in spans:
        inner.update(t for t in (sp.start, sp.end) if lo < t < hi)
    cuts = np.array(sorted(inner))
    if merged:
        busy = xplane._busy_before(merged)(cuts)
    else:
        busy = np.zeros_like(cuts)
    idle = np.diff(cuts) - np.diff(busy)
    out: dict = {}
    for label, ns in zip(_labels(spans, cuts), idle):
        if ns > 0:
            out[label] = out.get(label, 0.0) + float(ns)
    return out


def engine_idle_share(split: dict, lo: float, hi: float) -> float:
    """Device idle under engine bookkeeping (an ``idle_split`` of
    ``[lo, hi]``) over the window, %."""
    return 100.0 * sum(ns for k, ns in split.items()
                       if is_engine(k)) / (hi - lo)


def admit_host_ms(spans: list, lo: float, hi: float):
    """Mean host time an admission adds beyond its prefill, ms: each
    ``serve.admit`` in ``[lo, hi]`` less its ``serve.prefill``."""
    adm = named(spans, "serve.admit", lo, hi)
    if not adm:
        return None
    return float(np.mean([self_ns(spans, a, "serve.prefill")
                          for a in adm])) / 1e6


def decode_dispatch_ms(spans: list, lo: float, hi: float):
    """Median ``model.decode`` in ``[lo, hi]``, ms: the host's time to
    enqueue one pool decode step."""
    d = [sp.end - sp.start for sp in named(spans, "model.decode", lo, hi)]
    return float(np.median(d)) / 1e6 if d else None


def window_captures(spans: list, lo: float, hi: float) -> int:
    """Region captures (``tapir.capture``) that began in ``[lo, hi]``."""
    return sum(1 for sp in spans if sp.name == "tapir.capture"
               and lo <= sp.start < hi)


def report(trace: xplane.Trace, spans: list) -> dict:
    """The traced window's idle split (seconds, largest first) beside its
    whole idle time, and the span metrics, over ``bench.window``."""
    lo, hi = xplane.window(trace)
    split = idle_split(trace, spans, lo, hi)
    busy = xplane.covered(xplane.merge(next(iter(trace.ops.values()))),
                          [(lo, hi)]) if trace.ops else 0.0
    return {
        "window_s": (hi - lo) / 1e9, "idle_s": (hi - lo - busy) / 1e9,
        "phases": {k: ns / 1e9 for k, ns in
                   sorted(split.items(), key=lambda kv: -kv[1])},
        "engine_idle_share": engine_idle_share(split, lo, hi),
        "admit_host_ms": admit_host_ms(spans, lo, hi),
        "decode_dispatch_ms": decode_dispatch_ms(spans, lo, hi),
        "window_captures": window_captures(spans, lo, hi)}
