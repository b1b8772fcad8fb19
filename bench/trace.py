"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What the planes hold on a TPU (read by hand from a recorded trace, kept
as ``bench/tests/data/decode_prefill_2layer.xplane.pb``):

- ``/device:TPU:<n>`` planes, line ``XLA Modules``: one event per program
  execution, named ``jit_<python name>(<fingerprint>)``; the runner's
  steps are ``jit__prefill_impl(...)`` and ``jit__decode_impl(...)``.
- the same planes, line ``XLA Ops``: one event per HLO op, named by the
  op's HLO text (``%paged_attention.6 = ... custom-call(...)``).  Control
  flow ops (``%while``) enclose the ops of their body.
- ``/host:CPU``: one line per host thread; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans are events named ``bench.*``.

Host and device events share one clock (nanoseconds from trace start).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]        # seconds on the trace clock

_FINGERPRINT = re.compile(r"\(\d+\)$")
_CONTROL_FLOW = ("while", "conditional", "call")


@dataclass
class Trace:
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)    # device -> [(program, start_s, end_s)]
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)    # device -> [(op, start_s, end_s)]
    spans: List[Tuple[str, float, float, str]] = field(
        default_factory=list)    # [(name, start_s, end_s, thread)]

    @property
    def devices(self) -> List[str]:
        return sorted(self.modules)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def program_name(event_name: str) -> str:
    """``jit__decode_impl(123)`` -> ``jit__decode_impl``."""
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%paged_attention.6 = bf16[...] custom-call(...)`` ->
    ``paged_attention``; ``%copy.118 = ...`` -> ``copy``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax._src.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                evs = [(e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                target = tr.modules if line.name == "XLA Modules" else tr.ops
                target.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        tr.spans.append((e.name, e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns) * 1e-9,
                                         line.name))
    for d in list(tr.ops):
        tr.modules.setdefault(d, [])
    return tr


def span_window(tr: Trace, name: str) -> Optional[Interval]:
    """First and last instant of the host spans called ``name``."""
    hits = [(s, e) for n, s, e, _ in tr.spans if n == name]
    if not hits:
        return None
    return min(s for s, _ in hits), max(e for _, e in hits)


def _merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_intervals(tr: Trace, device: str, window: Interval) -> List[Interval]:
    """Merged intervals in which some program ran on ``device``."""
    return _merge(_clip([(s, e) for _, s, e in tr.modules.get(device, [])],
                        window))


def busy_seconds(tr: Trace, window: Interval) -> float:
    """Device-busy seconds in ``window``, averaged over the devices."""
    devs = tr.devices
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(tr, d, window))
               for d in devs) / len(devs)


def idle_share(tr: Trace, window: Interval) -> float:
    length = window[1] - window[0]
    return 1.0 - busy_seconds(tr, window) / length


def program_durations(tr: Trace, program: str,
                      window: Optional[Interval] = None) -> List[float]:
    """Device seconds of each execution of ``program`` (a name such as
    ``jit__decode_impl``) starting inside ``window``."""
    out = []
    for evs in tr.modules.values():
        for n, s, e in evs:
            if program_name(n) == program and (
                    window is None or window[0] <= s < window[1]):
                out.append(e - s)
    return out


def op_durations(tr: Trace, op: str,
                 window: Optional[Interval] = None) -> List[float]:
    """Device seconds of each execution of HLO op ``op`` (e.g. the paged
    kernel's custom call ``paged_attention``) starting inside ``window``."""
    out = []
    for evs in tr.ops.values():
        for n, s, e in evs:
            if op_name(n) == op and (window is None
                                     or window[0] <= s < window[1]):
                out.append(e - s)
    return out


def top_ops(tr: Trace, window: Interval, k: int = 10) -> List[List]:
    """The device ops that took the most time in ``window``, by op name,
    leaving out control-flow ops whose time is their body's."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for n, s, e in evs:
            name = op_name(n)
            if name.split(".")[0] in _CONTROL_FLOW:
                continue
            for cs, ce in _clip([(s, e)], window):
                tot[name] = tot.get(name, 0.0) + (ce - cs)
    ndev = max(1, len(tr.devices))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / ndev] for n, t in ranked]


def _innermost_span(tr: Trace, t: float) -> str:
    best = None
    for n, s, e, _ in tr.spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "no span (host idle or untraced)"


def idle_gaps(tr: Trace, window: Interval, k: int = 10,
              exclude: Tuple[str, ...] = ()) -> List[List]:
    """Device-idle seconds in ``window``, summed by the innermost host
    span open at the middle of each gap (what the host was doing), on the
    first device.  Spans named in ``exclude`` (whole-window markers) are
    not candidates."""
    devs = tr.devices
    if not devs:
        return []
    busy = busy_intervals(tr, devs[0], window)
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    keep = Trace(spans=[sp for sp in tr.spans if sp[0] not in exclude])
    tot: Dict[str, float] = {}
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e - s <= 0:
            continue
        label = _innermost_span(keep, (s + e) / 2)
        tot[label] = tot.get(label, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
