"""Read the program's own spans and scheduler counters in one traced run
of a cell: what holds the chip idle, and how long admission waits.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``'s (the same set-up, warm-up,
traffic and traced slice, ``bench.cell._tracer``) without the reference
check, so it decides no ``correct``.  It prints one JSON line:

- ``idle_in_kv_hop_share``: % of the traced slice in which no program ran
  on the device while some thread was inside the KV hop
  (``omni.<stage>.extract_kv``, ``omni.conn.send``, ``omni.conn.recv``,
  ``omni.<stage>.inject_kv``);
- ``idle_in_step_host_share``: % of the slice in which the device was
  idle, no thread was in the KV hop, and the output stage's thread was
  inside ``omni.<output>.step``;
- ``sched_wait_p90_ms``: p90 of the output engine's admission waits
  (``AREngine.sched_stats``) of the requests due in the window;
- ``kv_reserved_used_share``: % of the output engine's reserved
  page-steps that held written KV, as both moved in the window;
- ``idle_by_leaf_s``: the slice's device-idle seconds, each stretch given
  once: to the KV hop, else to the output thread's innermost span in its
  step, else to the other threads' innermost spans, else to no span;
  and ``idle_in_no_leaf_share``, the share of the idle time in which no
  thread was inside a span below a ``.step``.

Spans are read per thread: a device-idle stretch is not given to
whichever thread happens to hold the shortest span, as the printed
``breakdown.idle_gaps`` of ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:          # run as a script
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import trace as T  # noqa: E402

PREFIX = "omni."
#: the program's spans of the prefill -> decode KV hop: device gather and
#: copy to the host, the connector's pack and unpack, upload and pool writes
KV_HOP_SPANS = ("omni.conn.send", "omni.conn.recv")
KV_HOP_PARTS = (".extract_kv", ".inject_kv")

Span = Tuple[str, float, float, str]      # (name, start_s, end_s, thread)
Stretch = Tuple[float, float, Dict[str, Tuple[str, ...]]]


def in_kv_hop(name: str) -> bool:
    """Whether program span ``name`` is part of the KV hop."""
    return name in KV_HOP_SPANS or name.endswith(KV_HOP_PARTS)


def load_spans(path: str) -> List[Span]:
    """The program's ``omni.*`` spans of an ``.xplane.pb``.  A host line
    is one OS thread, and line names repeat (every Python thread's may
    read ``python``), so a span's thread is its line's name and index."""
    from jax._src.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                f"{line.name}#{i}"))
    return out


def _stacks(spans: List[Tuple[str, float, float]]
            ) -> List[Tuple[float, float, Tuple[str, ...]]]:
    """One thread's spans as stretches over which the set of open spans
    does not change: (start, end, open span names, outermost first).
    Stretches with no open span are left out."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    events = sorted([(s, 1, -e, i) for i, (_, s, e) in enumerate(spans)]
                    + [(e, 0, 0.0, i) for i, (_, s, e) in enumerate(spans)])
    out: List[Tuple[float, float, Tuple[str, ...]]] = []
    stack: List[int] = []
    prev = None
    for t, starts, _, i in events:        # at one instant: ends first
        if stack and t > prev:
            out.append((prev, t, tuple(spans[j][0] for j in stack)))
        if starts:
            stack.append(i)
        else:
            stack.remove(i)
        prev = t
    return out


def idle_by_thread(tr: T.Trace, spans: Iterable[Span],
                   window: T.Interval) -> List[Stretch]:
    """The device-idle time of ``window`` (first device, as
    ``bench.trace.idle_share``) cut where any thread enters or leaves one
    of ``spans``: (start, end, {thread: its open spans, outermost
    first}) for each stretch, the innermost span last.  Threads inside
    no span are left out of the dict."""
    devs = tr.devices
    if not devs:
        return []
    busy = T.busy_intervals(tr, devs[0], window)
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    by_thread: Dict[str, List[Tuple[str, float, float]]] = {}
    for n, s, e, th in spans:
        by_thread.setdefault(th, []).append((n, s, e))
    lo, hi = window
    stacks = {th: [(max(s, lo), min(e, hi), names)
                   for s, e, names in _stacks(sp) if e > lo and s < hi]
              for th, sp in by_thread.items()}
    cuts = sorted({t for iv in idle for t in iv}
                  | {t for segs in stacks.values() for s, e, _ in segs
                     for t in (s, e)})
    at = {th: 0 for th in stacks}
    out: List[Stretch] = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        if k == len(idle) or idle[k][0] > a:
            continue                        # the device is busy here
        open_ = {}
        for th, segs in stacks.items():
            j = at[th]
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            at[th] = j
            if j < len(segs) and segs[j][0] <= a:
                open_[th] = segs[j][2]
        out.append((a, b, open_))
    return out


def _hop(open_: Dict[str, Tuple[str, ...]]) -> bool:
    return any(in_kv_hop(st[-1]) for st in open_.values())


def kv_hop_idle_share(stretches: Sequence[Stretch],
                      window: T.Interval) -> float:
    """% of ``window`` idle with some thread inside a KV-hop span."""
    hop = sum(b - a for a, b, open_ in stretches if _hop(open_))
    return 100.0 * hop / (window[1] - window[0])


def step_host_idle_share(stretches: Sequence[Stretch], window: T.Interval,
                         output: str) -> float:
    """% of ``window`` idle, outside the KV hop, with the output stage's
    thread inside its ``omni.<output>.step``."""
    step = f"{PREFIX}{output}.step"
    host = sum(b - a for a, b, open_ in stretches if not _hop(open_)
               and any(step in st for st in open_.values()))
    return 100.0 * host / (window[1] - window[0])


def idle_by_leaf(stretches: Sequence[Stretch], output: str
                 ) -> Tuple[Dict[str, float], float]:
    """Each idle stretch given once: ``hop:<spans>``, else
    ``step:<innermost>`` of the output thread's step, else
    ``other:<innermost spans>``, else ``no span``; and the seconds in
    which no thread was inside a span below a ``.step`` (no span, or
    between a step's leaves)."""
    step = f"{PREFIX}{output}.step"
    got: Dict[str, float] = defaultdict(float)
    no_leaf = 0.0
    for a, b, open_ in stretches:
        d = b - a
        inner = {st[-1] for st in open_.values()}
        if all(n.endswith(".step") for n in inner):
            no_leaf += d
        hop = sorted({n for n in inner if in_kv_hop(n)})
        out = [st for st in open_.values() if step in st]
        if hop:
            got["hop:" + "+".join(hop)] += d
        elif out:
            got["step:" + out[0][-1]] += d
        elif inner:
            got["other:" + "+".join(sorted(inner))] += d
        else:
            got["no span"] += d
    return dict(got), no_leaf


def sched_wait_p90_ms(waits: Iterable[Tuple[int, float]],
                      req_ids: Iterable[int]) -> Optional[float]:
    """p90, in ms, of the admission waits (``(req_id, seconds)``) of the
    requests ``req_ids``; a request admitted twice (preempted and
    re-queued) counts each wait."""
    ids = set(req_ids)
    w = [s for r, s in waits if r in ids]
    return float(np.percentile(w, 90)) * 1e3 if w else None


def kv_reserved_used_share(open_: Dict, close: Dict) -> Optional[float]:
    """% of the reserved page-steps that held written KV, as both moved
    between two ``sched_stats`` snapshots."""
    reserved = close["reserved_page_steps"] - open_["reserved_page_steps"]
    used = close["used_page_steps"] - open_["used_page_steps"]
    return 100.0 * used / reserved if reserved else None


def probe(cell, seed: int, seconds: float, t_start: float) -> Dict:
    """Serve one traced window of ``cell`` and read the program's spans
    and counters (the module's docstring)."""
    from bench.cell import Bench, _tracer
    b = Bench(cell, seed, True, t_start, seconds)
    out_eng = b.system.engines[b.system.output]
    snaps: Dict[str, Dict] = {}
    tdir = tempfile.mkdtemp(prefix="program_trace_")
    try:
        hooks, tstate = _tracer(tdir, seconds, b.recorder)
        hooks += [(0.0, lambda: snaps.update(open=out_eng.sched_stats)),
                  (seconds, lambda: snaps.update(close=out_eng.sched_stats))]
        run = b.serve(cell.traffic, hooks=hooks)
        waits = out_eng.sched_stats["admission_waits"]
    finally:
        b.close()
    try:
        path = T.find_xplane(tdir)
        tr, spans = T.load(path), load_spans(path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    w = T.span_window(tr, "bench.traced")
    output = b.system.output
    stretches = idle_by_thread(tr, spans, w)
    idle = sum(e - s for s, e, _ in stretches)
    leaves, no_leaf = idle_by_leaf(stretches, output)
    res = {
        "workload": cell.name, "seed": seed,
        "window_s": w[1] - w[0], "idle_s": idle,
        "spans": len(spans), "threads": len({th for *_, th in spans}),
        "sched_wait_p90_ms": sched_wait_p90_ms(
            waits, [r.req.req_id for r in run.records]),
        "kv_reserved_used_share": kv_reserved_used_share(
            snaps["open"], snaps["close"]),
        "idle_in_kv_hop_share": None, "idle_in_step_host_share": None,
        "idle_in_no_leaf_share": None,
        "idle_by_leaf_s": sorted(leaves.items(), key=lambda kv: -kv[1]),
    }
    if tr.devices and spans:
        res.update(
            idle_in_kv_hop_share=kv_hop_idle_share(stretches, w),
            idle_in_step_host_share=step_host_idle_share(stretches, w,
                                                         output),
            idle_in_no_leaf_share=100.0 * no_leaf / idle if idle else None)
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("program_trace: no TPU", file=sys.stderr)
        return 3
    from bench.spec import resolve
    print(json.dumps(probe(resolve(a.workload), a.seed, a.seconds,
                           T_START)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
